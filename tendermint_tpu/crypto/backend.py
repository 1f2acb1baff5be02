"""Pluggable crypto backends: the seam between consensus and the TPU.

The reference verifies one signature at a time behind `PubKey.VerifyBytes`
(reference `types/vote_set.go:175`, `types/validator_set.go:247-249`).
This framework routes every bulk verification through a `Backend` so the
caller (VoteSet tally, ValidatorSet.VerifyCommit, fast-sync, light client)
never knows whether signatures are checked by the bigint reference, a
native CPU library, or a TPU batch kernel — the `--crypto-backend` flag
from BASELINE.md picks the implementation.

Batches are padded to power-of-two buckets so the TPU backend compiles a
handful of shapes once and reuses them for any workload size.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Protocol

import numpy as np

from tendermint_tpu.crypto import pure_ed25519 as _ref
from tendermint_tpu.utils import metrics, tracing
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.metrics import REGISTRY

log = get_logger("crypto")

MIN_BUCKET = 16

# -- XLA compile/cache observability -----------------------------------------
# jax's own jit cache is opaque, so we shadow it: per jit entry point,
# the set of input (shape, dtype) signatures already dispatched.  A
# signature seen before is a cache HIT; a new signature is a MISS (jit
# will trace, and compile unless the persistent cache serves it); a new
# signature on an entry that was already warm is shape DRIFT — the
# _bucket() padding leaked a shape and the node just paid a silent
# 100s-class recompile.  The monitoring listener in
# enable_compile_cache() counts the REAL backend compiles; the pair of
# views separates "dispatched cold" from "actually compiled".
_jit_shapes: dict[str, set] = {}
_jit_lock = threading.Lock()


def _note_dispatch(entry: str, *arrays) -> bool:
    """Track `entry`'s seen input signatures; True when this dispatch is
    COLD (first time this entry sees these shapes/dtypes)."""
    sig = tuple((tuple(getattr(a, "shape", ())),
                 str(getattr(a, "dtype", ""))) for a in arrays)
    with _jit_lock:
        seen = _jit_shapes.setdefault(entry, set())
        if sig in seen:
            hit = True
        else:
            hit = False
            drift = bool(seen)
            seen.add(sig)
    if hit:
        REGISTRY.xla_cache_hits.inc()
        return False
    REGISTRY.xla_cache_misses.inc()
    if drift:
        REGISTRY.xla_recompiles.inc()
    return True


_cold_in_flight = 0      # cold dispatches + table builds running now


@contextmanager
def _cold_section():
    """Mark work that compiles (a first call of a shape, a table build):
    minutes on a cold cache, and not a hung device.  The supervised
    ladder reads `cold_dispatch_in_flight()` before it calls a slow
    device call a fault."""
    global _cold_in_flight
    with _jit_lock:
        _cold_in_flight += 1
    try:
        yield
    finally:
        with _jit_lock:
            _cold_in_flight -= 1


def cold_dispatch_in_flight() -> bool:
    with _jit_lock:
        return _cold_in_flight > 0


@contextmanager
def _firstcall(entry: str, cold: bool):
    """Time a cold dispatch under an `xla.firstcall` span (category
    `compile` for the attribution partition) — warm dispatches pass
    through untimed."""
    if not cold:
        yield
        return
    t0 = time.perf_counter()
    with _cold_section(), tracing.span("xla.firstcall", entry=entry):
        yield
    REGISTRY.xla_first_call_seconds.observe(time.perf_counter() - t0)


def _h2d(*arrays) -> None:
    """Count host->device upload bytes for a dispatch (numpy inputs that
    are about to become device arrays)."""
    n = 0
    for a in arrays:
        nb = getattr(a, "nbytes", 0)
        if nb:
            n += int(nb)
    if n:
        REGISTRY.h2d_bytes.inc(n)


def _d2h(out) -> None:
    nb = getattr(out, "nbytes", 0)
    if nb:
        REGISTRY.d2h_bytes.inc(int(nb))


def _loaded(fn, *args):
    """`fn`'s executable for these arguments (arrays, or their
    `ShapeDtypeStruct`s), traced and compiled or read from the compile
    cache WITHOUT a run; a later `fn(...)` of the same shapes finds
    trace, lowering and executable in jit's caches and only dispatches.
    A stand-in without `lower` (the tests' stubs) is returned as it is."""
    lower = getattr(fn, "lower", None)
    return fn if lower is None else lower(*args).compile()


def _count_call(n: int, b: int, out) -> None:
    """One device verify: `n` lanes asked for, in the program of `b`
    lanes it rode (its own bucket, or the warm one it was padded into)."""
    REGISTRY.sigs_requested.inc(n)
    REGISTRY.sigs_verified.inc(int(out[:n].sum()))
    REGISTRY.verify_batches.inc()
    REGISTRY.verify_lanes_padded.inc(b)
    REGISTRY.batch_occupancy.observe(n / b)
    REGISTRY.batch_occupancy_hist.observe(n / b)


class Backend(Protocol):
    name: str

    def verify_batch(self, pubkeys: np.ndarray, msgs: np.ndarray,
                     sigs: np.ndarray) -> np.ndarray:
        """uint8 [N,32] pubkeys, [N,M] msgs (equal-length), [N,64] sigs
        -> bool[N]."""
        ...

    def verify_grouped(self, set_key: bytes, val_pubs: np.ndarray,
                       val_idx: np.ndarray, msgs: np.ndarray,
                       sigs: np.ndarray) -> np.ndarray:
        """Verify N signatures made by members of a FIXED key set: lane i
        was signed by val_pubs[val_idx[i]].  set_key identifies the set
        (e.g. the validator-set hash) so device backends can cache
        per-set precomputation (comb tables) across calls — fast-sync
        verifies thousands of commits against the same ~100 keys.
        Semantics identical to verify_batch(val_pubs[val_idx], ...)."""
        ...


def _bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


class PythonBackend:
    """Golden bigint implementation — slow, obviously correct."""
    name = "python"

    def verify_batch(self, pubkeys, msgs, sigs):
        # verification is a pure function of (pub, msg, sig), so lanes
        # share the process-wide memo with the scalar vote path
        # (types/keys.py).  The repeat shape this serves: every node of
        # an in-process rig validates the SAME LastCommit (N sigs x N
        # nodes per height) — first check settles each lane for everyone
        # else.  Chaos/spot-check machinery is unaffected: injection
        # corrupts results at the supervised-rung wrapper, above here.
        from tendermint_tpu.types.keys import _verify_memo
        out = np.zeros(len(pubkeys), dtype=bool)
        # "scalar." prefix -> CAT_SCALAR: this is the scalar-tail time
        # the attribution doctor reports when work falls off the device
        with tracing.span("scalar.verify", lanes=len(pubkeys)):
            for i in range(len(pubkeys)):
                out[i] = _verify_memo(pubkeys[i].tobytes(),
                                      msgs[i].tobytes(),
                                      sigs[i].tobytes())
        REGISTRY.sigs_requested.inc(len(pubkeys))
        REGISTRY.sigs_verified.inc(int(out.sum()))
        return out

    def verify_grouped(self, set_key, val_pubs, val_idx, msgs, sigs):
        return self.verify_batch(val_pubs[val_idx], msgs, sigs)


class TpuBackend:
    """JAX batch kernel (`tendermint_tpu.ops.ed25519`) with shape bucketing.

    Runs on whatever platform jax was given (the test suite pins the CPU
    XLA backend), so "tpu" names the code path; the platform and device
    kind it really got are logged once at construction and kept in
    `platform` / `device_kind`.
    """
    name = "tpu"

    # Comb-table cache is BYTE-bounded, not count-bounded: 10-bit tables
    # are ~2.5 MB per validator (uint8), so a 128-validator set costs
    # ~312 MB while an 8-validator light chain costs ~41 MB — a count
    # cap of 8 evicted small light-chain tables whenever a big fast-sync
    # set was also resident, and the multi-chain streaming loop then
    # paid full table REBUILDS mid-flight (measured: config4 fell from
    # 274k to 116k sigs/s when run after config1+3).  4 GB comfortably
    # holds a validator node's chain plus a light client tracking many
    # chains on a 16 GB chip.
    TABLE_CACHE_BYTES = 4 << 30

    def __init__(self):
        # import lazily so the python backend works without jax configured
        import jax
        import jax.numpy as jnp
        from tendermint_tpu.ops import ed25519 as dev
        enable_compile_cache()
        self._jnp = jnp
        self._dev = dev
        # fixed-base comb table, uploaded once and passed as an ARGUMENT
        # to every jitted entry point — baked in as a graph constant the
        # 8.6 MB literal adds ~5s of XLA compile per executable
        from tendermint_tpu.ops import curve as _curve
        self._base_tbl = jnp.asarray(_curve._base_table())
        # set_key -> (tbl, ok, V, staged key matrix, its padded host copy)
        self._tables: dict[bytes, tuple] = {}
        self._tables_lock = threading.Lock()
        self._builds: dict[bytes, threading.Event] = {}  # in-flight builds
        # (V bucket, joined bucket) -> the two executables that derive a
        # table from a resident one (`_derive_tables`), loaded under the
        # lock; None while `warm_derive`'s thread is on its way to them.
        # Empty, and nothing loaded, until a set changes
        self._derive_programs: dict[tuple, tuple | None] = {}
        self._derive_lock = threading.Lock()
        # (V bucket, message length) -> the (lanes, templates) buckets of
        # the templated verify that have run to an end in this process:
        # the programs a call can be padded into without a compile
        self._warm_templated: dict[tuple, set] = {}
        # multi-chip: shard verify lanes over every visible device (comb
        # tables replicate; no collectives in the hot loop).  Single-chip
        # hosts skip the sharding machinery entirely.
        self._mesh = None
        self._sharded_fns: dict[bytes, object] = {}
        self._base_tbl_mesh = None
        devs = jax.devices()
        n_dev = len(devs)
        self.platform = devs[0].platform
        self.device_kind = devs[0].device_kind
        if n_dev > 1:
            from tendermint_tpu.parallel import sharding
            from jax.sharding import NamedSharding, PartitionSpec
            self._mesh = sharding.make_mesh(n_dev)
            self._base_tbl_mesh = jax.device_put(
                self._base_tbl, NamedSharding(self._mesh, PartitionSpec()))
        metrics.set_build_info(jax_backend=self.platform,
                               device_kind=self.device_kind,
                               local_devices=n_dev)
        log.info("tpu crypto backend up", platform=self.platform,
                 device_kind=self.device_kind, devices=n_dev,
                 cache_dir=compile_cache_dir())

    def tables_cached(self, set_key: bytes) -> bool:
        """True when the comb tables for `set_key` are already resident —
        latency-sensitive callers (the consensus receive loop's vote
        micro-batch) must not trigger a multi-second table build inline."""
        with self._tables_lock:
            return set_key in self._tables

    def verify_batch(self, pubkeys, msgs, sigs):
        n = len(pubkeys)
        if n == 0:
            return np.zeros(0, dtype=bool)
        b = _bucket(n)
        pad = b - n
        if pad:
            pubkeys = np.concatenate([pubkeys, np.repeat(pubkeys[:1], pad, 0)])
            msgs = np.concatenate([msgs, np.repeat(msgs[:1], pad, 0)])
            sigs = np.concatenate([sigs, np.repeat(sigs[:1], pad, 0)])
        jnp = self._jnp
        _h2d(pubkeys, msgs, sigs)
        cold = _note_dispatch("verify_batch", pubkeys, msgs, sigs)
        t0 = time.perf_counter()
        with _firstcall("verify_batch", cold), \
                tracing.span("verify.batch", lanes=n, bucket=b):
            out = self._dev.verify_batch(jnp.asarray(pubkeys),
                                         jnp.asarray(msgs),
                                         jnp.asarray(sigs))
            out = np.asarray(out)
        _d2h(out)
        dt = time.perf_counter() - t0
        # sync call: dispatch and wait are one interval — record it under
        # both summaries so they stay comparable with the async path
        # (which records the wait alone in step, full wall in dispatch)
        REGISTRY.device_step_seconds.observe(dt)
        REGISTRY.device_dispatch_seconds.observe(dt)
        REGISTRY.device_step_hist.observe(dt)
        _count_call(n, b, out)
        return out[:n]

    def _set_tables(self, set_key: bytes, val_pubs: np.ndarray) -> tuple:
        """Build (or fetch) the affine comb tables for a key set.  The
        valset is padded to a power-of-two so a handful of table shapes
        cover any set size with one compile each.  Concurrent first
        requests for the same set wait on one in-flight build instead of
        each paying the multi-second device build."""
        while True:
            with self._tables_lock:
                ent = self._tables.get(set_key)
                if ent is not None:
                    return ent
                pending = self._builds.get(set_key)
                if pending is None:
                    self._builds[set_key] = threading.Event()
                    break                    # we build
            pending.wait()                   # someone else is building
        try:
            with _cold_section():    # build compile + run: minutes cold
                ent = self._build_tables(set_key, val_pubs)
        finally:
            with self._tables_lock:
                self._builds.pop(set_key).set()
        return ent

    # bump when the comb-table layout changes (COMB_WBITS, packing, …):
    # a versioned filename turns stale-format cache files into misses
    TABLE_CACHE_FORMAT = 1
    TABLE_DISK_CACHE_BYTES = 8 << 30     # on-disk cap, oldest-mtime evicted

    @classmethod
    def _table_cache_path(cls, set_key: bytes) -> str | None:
        """Disk location for a set's built comb tables, or None when the
        on-disk cache is disabled (TM_TABLE_CACHE_DIR=\"\").  Tables are
        pure functions of the member pubkeys and set_key digests those,
        so content-addressing by set_key can never serve STALE tables.
        TRUST: the cache dir must be exactly as trusted as the jax
        persistent compile cache — anyone who can write either can
        subvert verification (poisoned executables in the compile cache
        are strictly worse), so by default the tables live INSIDE the
        compile cache's directory (`compile_cache_dir()`): one
        operator-owned directory to protect, place and carry."""
        d = os.environ.get("TM_TABLE_CACHE_DIR",
                           os.path.join(compile_cache_dir(), "tables"))
        if not d:
            return None
        return os.path.join(
            d, f"v{cls.TABLE_CACHE_FORMAT}-{set_key.hex()}.npz")

    def _build_tables(self, set_key: bytes, val_pubs: np.ndarray) -> tuple:
        v = len(val_pubs)
        vb = _bucket(v)
        if vb > v:
            val_pubs = np.concatenate(
                [val_pubs, np.repeat(val_pubs[:1], vb - v, 0)])
        t0 = time.perf_counter()
        path = self._table_cache_path(set_key)
        from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
        want_shape = (COMB_WINDOWS, COMB_DIGITS, vb, 3, 32)
        import hashlib as _hashlib
        pubs_digest = _hashlib.sha256(val_pubs.tobytes()).digest()
        tbl = ok = None
        if path is not None and os.path.exists(path):
            try:
                # loading ~2.5 MB/validator from disk beats the ~12s
                # on-device rebuild a warm node restart would otherwise
                # pay; shape + pubs-digest checks turn format drift or a
                # mislabeled file into a miss (consistency, not a
                # security boundary — see _table_cache_path)
                with np.load(path) as z:
                    if z["pubs_sha256"].tobytes() == pubs_digest:
                        arr = z["tbl"]   # NpzFile re-reads per access:
                        if tuple(arr.shape) == want_shape:  # bind once
                            tbl = self._jnp.asarray(arr)
                            ok = self._jnp.asarray(z["ok"])
            except Exception:
                tbl = ok = None          # corrupt cache file: rebuild
        vp_dev = self._jnp.asarray(val_pubs)   # one upload serves both the
        built = tbl is None                    # build + lane pubkey gathers
        near = self._predecessor(val_pubs) if built else None
        if near is not None:
            t0, tbl, ok = self._derive_tables(v, *near)
        elif built:
            # the program first (traced, then compiled or read from the
            # compile cache: seconds the first time a process meets a V
            # bucket, nothing after), under a record of its own, so that
            # `tables.build` times the device's work alone
            build = _loaded(self._dev.build_neg_comb_jit, vp_dev)
            t1 = time.perf_counter()
            tracing.RECORDER.record(
                "tables.build.load", tracing.perf_to_epoch(t0), t1 - t0,
                {"v": v}, cat=tracing.CAT_NONE)
            t0 = t1
            tbl, ok = build(vp_dev)
        if self._mesh is not None:
            # commit the tables replicated across the mesh at build time:
            # the sharded verify takes them as arguments (one jitted fn
            # per SHAPE, not per set), so evicting the table entry also
            # frees its only replicated device copy
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            repl = NamedSharding(self._mesh, P())
            tbl = jax.device_put(tbl, repl)
            ok = jax.device_put(ok, repl)
            vp_dev = jax.device_put(vp_dev, repl)
        tbl.block_until_ready()
        dt = time.perf_counter() - t0
        if near is not None:
            # one bare record a derived table, inside its set's
            # `tables.build`, which stays the cost of a set
            tracing.RECORDER.record(
                "tables.derive", tracing.perf_to_epoch(t0), dt,
                {"v": v, "joined": len(near[1]), "bytes": int(tbl.size)},
                cat=tracing.CAT_NONE)
            REGISTRY.table_derives.inc()
        # one bare record a table, the device build (whole or derived; or
        # the load from the disk cache) run to its end; bookkeeping
        # (CAT_NONE): it nests under the verify call that first met the set
        tracing.RECORDER.record(
            "tables.build" if built else "tables.load",
            tracing.perf_to_epoch(t0), dt, {"v": v, "bytes": int(tbl.size)},
            cat=tracing.CAT_NONE)
        if built:
            # loads are ~100ms and would drag the build histogram down
            REGISTRY.table_build_seconds.observe(dt)
            REGISTRY.table_builds.inc()
        if built and path is not None:
            tmp = None
            try:                         # persist for the next restart
                d = os.path.dirname(path)
                os.makedirs(d, exist_ok=True)
                tmp = f"{path}.{os.getpid()}.tmp"   # concurrent writers
                with open(tmp, "wb") as f:   # file object: savez must
                    np.savez(f, tbl=np.asarray(tbl),  # not append .npz
                             ok=np.asarray(ok),
                             pubs_sha256=np.frombuffer(pubs_digest,
                                                       np.uint8))
                os.replace(tmp, path)
                self._prune_table_cache(d)
            except Exception:            # cache write is best-effort —
                if tmp is not None:      # but a half-written tmp (full
                    try:                 # disk) must not sit outside
                        os.unlink(tmp)   # the pruner's *.npz scope
                    except OSError:      # forever
                        pass
        # the host copy is what a later set's keys are matched against
        # (`_predecessor`): its own, so that no caller's array is trusted
        # to stay as it was
        ent = (tbl, ok, v, vp_dev, np.array(val_pubs))
        with self._tables_lock:
            new_bytes = tbl.size                    # uint8: size == bytes
            resident = sum(e[0].size for e in self._tables.values())
            while self._tables and \
                    resident + new_bytes > self.TABLE_CACHE_BYTES:
                oldest = next(iter(self._tables))   # FIFO eviction
                gone = int(self._tables.pop(oldest)[0].size)
                resident -= gone
                tracing.instant("tables.evict", bytes=gone)
                REGISTRY.table_evictions.inc()
            self._tables[set_key] = ent
            REGISTRY.tables_resident_bytes.set(resident + new_bytes)
        return ent

    def _predecessor(self, val_pubs: np.ndarray) -> tuple | None:
        """The resident table that the table of `val_pubs` (a set's key
        matrix, padded to its V bucket) can be assembled from: of the
        same V bucket, the one that holds the most of these keys, where
        the keys it lacks fit one small build.  Returns (its entry, the
        keys that joined [k, 32], for each column of the new table the
        column of [that table | the joined keys' table] it is), or None:
        the whole build.

        Keys are matched by their bytes, never by position: a set is
        ordered by address, so one swap moves every column between the
        old and the new position by one, and the padding columns repeat
        the first member, so they change when it does.  What decides is
        what the call was given (these keys, the tables resident now);
        `k` = every key is the whole build."""
        if self._mesh is not None:
            return None     # the replicated table's build is the whole one
        want = [row.tobytes() for row in val_pubs]
        with self._tables_lock:
            resident = [e for e in self._tables.values()
                        if len(e[4]) == len(want)]
        held, best = 0, None
        for ent in resident:
            at = {}
            for j, row in enumerate(ent[4]):
                at.setdefault(row.tobytes(), j)
            n = sum(key in at for key in want)
            if n and n >= held:                  # of equals, the newest
                held, best = n, (ent, at)
        if best is None:
            return None
        ent, at = best
        joined = list(dict.fromkeys(key for key in want if key not in at))
        if len(joined) > MIN_BUCKET:
            return None
        for i, key in enumerate(joined):
            at[key] = len(want) + i
        return (ent,
                np.frombuffer(b"".join(joined), np.uint8).reshape(-1, 32),
                np.array([at[key] for key in want], np.int32))

    def _derive_programs_for(self, vb: int, kb: int, v: int) -> tuple:
        """The build of `kb` columns (`build_neg_comb_jit`, the program a
        set of up to 16 is built by; None where no key joined) and the
        gather over [a table of `vb` columns | those], as executables:
        traced and compiled, or read from the compile cache, by shapes
        (`_loaded`: nothing runs, nothing is allocated), once a process
        and under a record of its own, by whichever comes first: the
        thread `warm_derive` started, or the derive that needs them."""
        with self._derive_lock:
            programs = self._derive_programs.get((vb, kb))
            if programs is not None:
                return programs
            import jax
            from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
            u8, S = np.uint8, jax.ShapeDtypeStruct
            t0 = time.perf_counter()
            widths = (vb, kb) if kb else (vb,)
            with _cold_section():
                build = (_loaded(self._dev.build_neg_comb_jit,
                                 S((kb, 32), u8)) if kb else None)
                gather = _loaded(
                    self._dev.comb_columns_jit,
                    tuple(S((COMB_WINDOWS, COMB_DIGITS, w, 3, 32), u8)
                          for w in widths),
                    tuple(S((w,), np.bool_) for w in widths),
                    S((vb,), np.int32))
            tracing.RECORDER.record(
                "tables.derive.load", tracing.perf_to_epoch(t0),
                time.perf_counter() - t0, {"v": v, "joined": kb},
                cat=tracing.CAT_NONE)
            programs = self._derive_programs[(vb, kb)] = (build, gather)
        return programs

    def warm_derive(self, n_vals: int) -> None:
        """A change of the validator set has been SIGHTED ahead of the
        height the node has applied (the fast-sync window cut at it,
        `BlockchainReactor._prepare_window`): load the derive's two
        programs now, on a thread named `crypto-precompile` as the
        node's own warm-ups are, so that the set's first verify call
        finds them, and whoever waits for the warm-ups by that name
        (`chip_smoke.py`, the benchmark before it opens its window)
        waits for this one too.  Once a V bucket; before a node's first
        set change nothing of the derive exists, this thread included."""
        key = (_bucket(n_vals), MIN_BUCKET)
        with self._tables_lock:
            if self._mesh is not None or key in self._derive_programs:
                return
            self._derive_programs[key] = None
        threading.Thread(target=self._derive_programs_for,
                         args=key + (n_vals,), daemon=True,
                         name="crypto-precompile").start()

    def _derive_tables(self, v: int, near: tuple, joined: np.ndarray,
                       src: np.ndarray) -> tuple:
        """The table of a set from the resident table `near` and the
        `joined` keys' columns, built by the program a 16-key set is
        built by: the whole build's table byte for byte, `ok` included,
        since a column depends on its own key alone.  `near`'s arrays
        are arguments that are not donated: it stays resident as it is,
        for the windows in flight and for the FIFO.  Returns (when the
        programs were dispatched, tbl, ok)."""
        jnp, k = self._jnp, len(joined)
        kb = _bucket(k) if k else 0
        build, gather = self._derive_programs_for(near[0].shape[2], kb, v)
        t0 = time.perf_counter()
        tbls, oks = near[:1], near[1:2]
        if k:
            fresh = build(jnp.asarray(np.concatenate(
                [joined, np.repeat(joined[:1], kb - k, 0)])))
            tbls, oks = tbls + fresh[:1], oks + fresh[1:]
        return (t0,) + tuple(gather(tbls, oks, jnp.asarray(src)))

    @classmethod
    def _prune_table_cache(cls, d: str) -> None:
        """Oldest-mtime eviction past TABLE_DISK_CACHE_BYTES — the disk
        mirror of the in-memory byte bound (validator-set rotation or a
        many-chain light client must not fill the disk)."""
        try:
            entries = []
            for name in os.listdir(d):
                if not name.endswith(".npz"):
                    continue
                p = os.path.join(d, name)
                st = os.stat(p)
                entries.append((st.st_mtime, st.st_size, p))
            total = sum(e[1] for e in entries)
            entries.sort()
            while entries and total > cls.TABLE_DISK_CACHE_BYTES:
                mtime, size, p = entries.pop(0)
                os.unlink(p)
                total -= size
        except OSError:
            pass

    def _warm_verify_if_cold(self, set_key: bytes, n_vals: int,
                             kind: str, shape: tuple):
        """Overlap the verify executable's XLA compile with the comb-table
        build on a COLD set: the compile needs only shapes, so a thread
        of THIS process (one process owns the chip) traces the program
        and compiles it, or reads it from the compile cache, while
        `_set_tables` pays the build's compile and run.  Nothing runs and
        nothing is allocated on the device (a dummy run would need a
        table of zeros beside the one being built, 1.25 GiB at V bucket
        512): the call that follows finds the executable in jit's own
        cache.  Returns the thread (caller joins after tables are
        ready), or None when the set is already cached."""
        if self._mesh is not None:
            return None     # mesh path compiles per-shape sharded fns
        with self._tables_lock:
            if set_key in self._tables:
                return None
        import jax
        from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
        u8, i32, S = np.uint8, np.int32, jax.ShapeDtypeStruct
        vb = _bucket(n_vals)
        tables = (S((COMB_WINDOWS, COMB_DIGITS, vb, 3, 32), u8),
                  S((vb,), np.bool_))
        base = S(self._base_tbl.shape, self._base_tbl.dtype)
        if kind == "templated":
            b, tb, mlen = shape
            fn = self._dev.verify_grouped_templated_jit
            specs = tables + (S((vb, 32), u8), S((b,), i32), S((b,), i32),
                              S((tb, mlen), u8), S((b, 64), u8), base)
        else:
            b, mlen = shape
            # pubkeys here are PER-LANE (challenge-hash input),
            # so the warm shape is the lane bucket, not vb
            fn = self._dev.verify_grouped_jit
            specs = tables + (S((b,), i32), S((b, 32), u8),
                              S((b, mlen), u8), S((b, 64), u8), base)
        t = threading.Thread(target=_loaded, args=(fn,) + specs, daemon=True)
        t.start()
        return t

    def _warm_shape(self, n_vals: int, msg_len: int, lanes: int,
                    templates: int) -> tuple[int, int] | None:
        """The smallest (lanes, templates) bucket of the templated verify
        that has already run here for this set size and fits the call, or
        None.  A fast-sync window cut short by a validator-set change has
        1 to 63 blocks, wherever the chain puts the change: its own
        bucket would be a program nobody warmed (23 s of compile on the
        chip at 100 validators, once a bucket), where padding it into
        the full window's costs one call of that (~50 ms)."""
        with self._tables_lock:
            fits = [s for s in self._warm_templated.get(
                        (_bucket(n_vals), msg_len), ())
                    if s[0] >= lanes and s[1] >= templates]
        return min(fits) if fits else None

    def verify_grouped_templated(self, set_key, val_pubs, val_idx,
                                 tmpl_idx, templates, sigs,
                                 exact_bucket: bool = False):
        """Grouped verify shipping only (sig, val_idx, tmpl_idx) lanes
        plus T message templates; messages and pubkeys assemble on
        device (see ops.ed25519.verify_grouped_templated).

        The call is padded, on the host, into the smallest program that
        has already run and fits (`_warm_shape`), and compiles its own
        power-of-two bucket only where none does or where the caller
        asks for that (`exact_bucket`: the warm-up, which is there to
        compile each bucket).  Padding lanes repeat lane 0 and padding
        templates are zeros, as within a bucket: a real lane's verdict is
        the same in every program that holds it."""
        n = len(val_idx)
        if n == 0:
            return np.zeros(0, dtype=bool)
        b, tb = _bucket(n), _bucket(len(templates))
        mlen = templates.shape[1]
        on_mesh = self._mesh_eligible(b)
        fit = (None if on_mesh or exact_bucket
               else self._warm_shape(len(val_pubs), mlen, b, tb))
        if fit is not None:
            # a program that has run: no compile to hide behind the build
            (b, tb), warm = fit, None
        else:
            warm = self._warm_verify_if_cold(
                set_key, len(val_pubs), "templated", (b, tb, mlen))
        tbl, pub_ok, v, vp_dev, _ = self._set_tables(set_key, val_pubs)
        if warm is not None:
            warm.join()
        if v != len(val_pubs):
            raise ValueError(
                f"set_key reused for a different set size ({v} != "
                f"{len(val_pubs)})")
        if on_mesh:
            # mesh path: assemble messages host-side and ride the
            # sharded kernel (templates are tiny; the win is moot there)
            return self.verify_grouped(set_key, val_pubs,
                                       np.asarray(val_idx),
                                       np.asarray(templates)[
                                           np.asarray(tmpl_idx)],
                                       np.asarray(sigs))
        pad = b - n
        if pad > 0:
            val_idx = np.concatenate([val_idx, np.repeat(val_idx[:1], pad)])
            tmpl_idx = np.concatenate([tmpl_idx,
                                       np.repeat(tmpl_idx[:1], pad)])
            sigs = np.concatenate([sigs, np.repeat(sigs[:1], pad, 0)])
        t = len(templates)
        if tb > t:
            templates = np.concatenate(
                [templates, np.zeros((tb - t, mlen), np.uint8)])
        jnp = self._jnp
        _h2d(val_idx, tmpl_idx, templates, sigs)
        cold = _note_dispatch("verify_grouped_templated", tbl, val_idx,
                              tmpl_idx, templates, sigs)
        t0 = time.perf_counter()
        with _firstcall("verify_grouped_templated", cold), \
                tracing.span("verify.dispatch", lanes=n, bucket=b):
            dev_out = self._dev.verify_grouped_templated_jit(
                tbl, pub_ok, vp_dev, jnp.asarray(val_idx.astype(np.int32)),
                jnp.asarray(tmpl_idx.astype(np.int32)),
                jnp.asarray(templates), jnp.asarray(sigs), self._base_tbl)
        # the step metrics time only the wait for the result: the
        # dispatch above returns once the device step is queued
        t1 = time.perf_counter()
        with tracing.span("verify.collect", lanes=n, bucket=b):
            out = np.asarray(dev_out)
        _d2h(out)
        with self._tables_lock:       # it has run to its end: warm
            self._warm_templated.setdefault(
                (tbl.shape[2], mlen), set()).add((b, tb))
        now = time.perf_counter()
        REGISTRY.device_step_seconds.observe(now - t1)
        REGISTRY.device_dispatch_seconds.observe(now - t0)
        REGISTRY.device_step_hist.observe(now - t1)
        _count_call(n, b, out)
        return out[:n]

    def precompile_for_validators(self, vals, stage: str = "all",
                                  stop=None) -> None:
        """Warm the full crypto plane for a ValidatorSet: THE shared
        derivation of which (lanes, templates) shapes a node produces —
        node boot (`node/node.py _maybe_precompile`) and `cli init
        --warm-crypto` must warm the IDENTICAL set or the "warm first
        boot" guarantee silently regresses when one site changes.

        A node that fast-syncs warms that set in two stages: "catchup",
        at boot, is the one program it runs before the tip (a window's
        templated verify); "live" is the other five, once it has caught
        up.  Each program costs seconds of Python tracing under the GIL
        even where its executable loads from the cache, and during
        catch-up the block download pays for them (PERF.md §6, PR 29).
        "all" is both stages in one call.  `stop`, an Event, ends the
        warm-up before its next program."""
        from tendermint_tpu.blockchain.reactor import DEFAULT_BATCH
        from tendermint_tpu.types import canonical
        v = max(vals.size(), 1)
        # a single gossiped vote, one commit (V lanes / 1 template), and
        # a full fast-sync verify window (DEFAULT_BATCH blocks x V
        # lanes, ~one template per block when commits are unanimous)
        window = (_bucket(DEFAULT_BATCH * v), DEFAULT_BATCH)
        shapes = sorted({(MIN_BUCKET, 1), (_bucket(v), 1), window})
        # the plain path serves VoteSet.add_votes_batched, the templated
        # path verify_commit and fast-sync windows
        programs = [(kind, n, t) for n, t in shapes
                    for kind in ("plain", "templated")]
        catchup = ("templated",) + window
        if stage == "catchup":
            programs = [catchup]
        elif stage == "live":
            programs.remove(catchup)
        elif stage != "all":
            raise ValueError(f"unknown precompile stage {stage!r}")
        self.precompile(vals.set_key(), vals.pubs_matrix(), programs,
                        canonical.SIGN_BYTES_LEN, stop)

    def precompile(self, set_key: bytes, val_pubs: np.ndarray,
                   programs: list[tuple[str, int, int]],
                   msg_len: int, stop=None) -> None:
        """Warm the comb tables for a validator set and the verify
        executables for `programs`, each a (kind, lanes, templates) with
        kind "plain" or "templated" — a cold node joining a net must not
        stall for a minute of XLA compile on its first commit (the
        compiles also land in the persistent cache).  Run it from a
        background thread at boot; every call is harmless dummy work
        through the real entry points.  Template counts must be the
        PRE-bucket values the real workload produces (the jit shape is
        the bucketed count, derived identically here)."""
        n_vals = len(val_pubs)
        for kind, n, t in programs:
            if stop is not None and stop.is_set():
                return
            idx = (np.arange(n) % n_vals).astype(np.int32)
            sigs = np.zeros((n, 64), dtype=np.uint8)
            if kind == "plain":
                self.verify_grouped(set_key, val_pubs, idx,
                                    np.zeros((n, msg_len), dtype=np.uint8),
                                    sigs)
                continue
            t = max(1, t)
            self.verify_grouped_templated(
                set_key, val_pubs, idx,
                (np.arange(n) % t).astype(np.int32),
                np.zeros((t, msg_len), dtype=np.uint8), sigs,
                exact_bucket=True)

    # below this many lanes per device the sharded dispatch overhead
    # beats the parallelism (single gossiped votes stay single-device)
    MIN_LANES_PER_DEVICE = 1024

    def _mesh_eligible(self, bucket: int) -> bool:
        if self._mesh is None:
            return False
        n_dev = self._mesh.devices.size
        return (bucket % n_dev == 0 and
                bucket >= self.MIN_LANES_PER_DEVICE * n_dev)

    def _sharded_fn(self, v_bucket: int, msg_len: int):
        """Jitted mesh verify, one per SHAPE (tables are arguments)."""
        key = (v_bucket, msg_len)
        with self._tables_lock:
            fn = self._sharded_fns.get(key)
        if fn is None:
            from tendermint_tpu.parallel import sharding
            fn = sharding.sharded_grouped_verify_fn(self._mesh)
            with self._tables_lock:
                self._sharded_fns.setdefault(key, fn)
                fn = self._sharded_fns[key]
        return fn

    def verify_grouped(self, set_key, val_pubs, val_idx, msgs, sigs):
        n = len(val_idx)
        if n == 0:
            return np.zeros(0, dtype=bool)
        warm = self._warm_verify_if_cold(
            set_key, len(val_pubs), "plain", (_bucket(n), msgs.shape[-1]))
        tbl, pub_ok, v = self._set_tables(set_key, val_pubs)[:3]
        if warm is not None:
            warm.join()
        if v != len(val_pubs):       # stale key reuse would verify against
            raise ValueError(        # the wrong table — refuse loudly
                f"set_key reused for a different set size ({v} != "
                f"{len(val_pubs)})")
        pubkeys = val_pubs[val_idx]              # challenge hash input
        b = _bucket(n)
        pad = b - n
        if pad:
            val_idx = np.concatenate([val_idx, np.repeat(val_idx[:1], pad)])
            pubkeys = np.concatenate([pubkeys, np.repeat(pubkeys[:1], pad, 0)])
            msgs = np.concatenate([msgs, np.repeat(msgs[:1], pad, 0)])
            sigs = np.concatenate([sigs, np.repeat(sigs[:1], pad, 0)])
        jnp = self._jnp
        _h2d(val_idx, pubkeys, msgs, sigs)
        on_mesh = self._mesh_eligible(b)
        cold = _note_dispatch(
            "verify_grouped_sharded" if on_mesh else "verify_grouped",
            tbl, val_idx, pubkeys, msgs, sigs)
        t0 = time.perf_counter()
        with _firstcall("verify_grouped", cold), \
                tracing.span("verify.grouped", lanes=n, bucket=b):
            if on_mesh:
                fn = self._sharded_fn(tbl.shape[2], msgs.shape[-1])
                out = fn(tbl, pub_ok, val_idx.astype(np.int32), pubkeys,
                         msgs, sigs, self._base_tbl_mesh)
            else:
                out = self._dev.verify_grouped_jit(
                    tbl, pub_ok, jnp.asarray(val_idx.astype(np.int32)),
                    jnp.asarray(pubkeys), jnp.asarray(msgs),
                    jnp.asarray(sigs), self._base_tbl)
            out = np.asarray(out)
        _d2h(out)
        dt = time.perf_counter() - t0
        if on_mesh:
            from tendermint_tpu.parallel import sharding
            sharding.note_sharded_call(self._mesh, dt, n)
        REGISTRY.device_step_seconds.observe(dt)      # sync: step ==
        REGISTRY.device_dispatch_seconds.observe(dt)  # dispatch interval
        REGISTRY.device_step_hist.observe(dt)
        _count_call(n, b, out)
        return out[:n]


# The persistent caches (XLA executables here, comb tables under
# tables/) live where JAX_COMPILATION_CACHE_DIR says.  jax reads that
# variable itself, so when it is set this module sets no directory in
# code; when it is not, everything that compiles — the node,
# chip_smoke.py, the test suite — shares ONE fixed path inside the
# checkout.  The path is part of jax's cache key, so a cache that moves
# never hits; a sealed machine that carries this one directory from run
# to run starts warm.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".tm_cache")


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


_cache_enabled = False
_compile_tls = threading.local()


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache: the ed25519/merkle graphs take
    minutes to compile cold, which would otherwise be paid again on every
    node restart (the restart path JITs during WAL replay)."""
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    from jax import monitoring

    # jax wraps BOTH outcomes of a compile request in the
    # backend_compile_duration event: a real XLA compile and a load from
    # the persistent cache.  The cache_hits event fires first, on the
    # same thread, so a thread-local flag tells them apart: real
    # compiles count in xla_compiles, loads in xla_persistent_cache_hits.
    # Either way a retroactive span lands in the flight recorder so the
    # doctor attributes the interval to `compile`, not device-idle.
    def _on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _compile_tls.hit = True

    def _on_duration(event: str, duration: float, **kw) -> None:
        if "backend_compile" not in event:
            return
        hit = getattr(_compile_tls, "hit", False)
        _compile_tls.hit = False
        if hit:
            REGISTRY.xla_persistent_cache_hits.inc()
        else:
            REGISTRY.xla_compiles.inc()
            REGISTRY.xla_compile_seconds.observe(duration)
        tracing.RECORDER.record(
            "xla.compile", tracing.now_epoch() - duration, duration,
            {"fn": kw.get("fun_name"), "cached": hit})

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def _native_backend():
    from tendermint_tpu.crypto.native import NativeBackend
    return NativeBackend()


def _supervised_backend():
    from tendermint_tpu.crypto.supervised import SupervisedBackend
    return SupervisedBackend.build(
        os.environ.get("TM_CRYPTO_PRIMARY", "tpu"))


_BACKENDS = {
    "python": PythonBackend,
    "tpu": TpuBackend,
    "native": _native_backend,
    "supervised": _supervised_backend,
}

_lock = threading.Lock()
_current: Backend | None = None


def set_backend(name: str) -> Backend:
    global _current
    if name not in _BACKENDS:
        # the name may arrive from TM_CRYPTO_BACKEND or a config file —
        # fail with the valid choices, not a bare KeyError at node boot
        raise ValueError(f"unknown crypto backend {name!r}; "
                         f"known: {sorted(_BACKENDS)}")
    with _lock:
        _current = _BACKENDS[name]()
    metrics.set_build_info(crypto_backend=name)
    return _current


def set_backend_supervised(primary: str = "tpu", **knobs) -> Backend:
    """Install a SupervisedBackend laddered from `primary` down to the
    python floor (see crypto/supervised.py).  `knobs` override the
    breaker/timeout/retry/spot-check defaults; node boot passes the
    `[crypto]` config section through here."""
    global _current
    from tendermint_tpu.crypto.supervised import SupervisedBackend
    with _lock:
        _current = SupervisedBackend.build(primary, **knobs)
    metrics.set_build_info(crypto_backend=f"supervised:{primary}")
    return _current


def get_backend() -> Backend:
    global _current
    with _lock:
        if _current is None:
            name = os.environ.get("TM_CRYPTO_BACKEND", "tpu")
            if name not in _BACKENDS:
                raise ValueError(
                    f"unknown TM_CRYPTO_BACKEND={name!r}; "
                    f"known: {sorted(_BACKENDS)}")
            _current = _BACKENDS[name]()
            metrics.set_build_info(crypto_backend=_current.name)
    return _current


def active_backend_name() -> str:
    """Name of the backend that would answer a call right now: under the
    supervised ladder that is its active rung ("tpu" until a breaker
    demotes it), otherwise the installed backend's own name.  Callers
    that choose between a device path and a host path ask this, so a
    supervised node on a healthy device still takes the device path."""
    be = get_backend()
    active = getattr(be, "active_rung_name", None)
    return (active() or "") if active is not None else be.name


def valset_change_ahead(n_vals: int) -> None:
    """A node that catches up has sighted a validator-set change ahead of
    the height it has applied, in a set of `n_vals`: a backend that
    derives the next set's table (`TpuBackend.warm_derive`) loads the
    programs for it meanwhile; to any other this is nothing."""
    fn = getattr(get_backend(), "warm_derive", None)
    if fn is not None:
        fn(n_vals)


def verify_batch(pubkeys, msgs, sigs) -> np.ndarray:
    return get_backend().verify_batch(pubkeys, msgs, sigs)


def verify_grouped(set_key: bytes, val_pubs, val_idx, msgs,
                   sigs) -> np.ndarray:
    """Fixed-key-set verify (see Backend.verify_grouped).  Backends
    without per-set precomputation fall back to a plain batch."""
    be = get_backend()
    fn = getattr(be, "verify_grouped", None)
    if fn is None:
        return be.verify_batch(val_pubs[val_idx], msgs, sigs)
    return fn(set_key, val_pubs, val_idx, msgs, sigs)


def verify_grouped_templated(set_key: bytes, val_pubs, val_idx, tmpl_idx,
                             templates, sigs) -> np.ndarray:
    """Template form: lane i's message is templates[tmpl_idx[i]].  Device
    backends ship only indices + sigs and assemble on device; others
    gather host-side (one cheap numpy take) and batch normally."""
    be = get_backend()
    fn = getattr(be, "verify_grouped_templated", None)
    if fn is not None:
        return fn(set_key, val_pubs, val_idx, tmpl_idx, templates, sigs)
    return verify_grouped(set_key, val_pubs, val_idx,
                          templates[tmpl_idx], sigs)
