#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user
calls: a `Node` (what `cli node` constructs) with `crypto_backend="tpu"`
and `fast_sync=True` fast-syncs a 100-validator chain served by several
rate-limited source peers over loopback TCP, verifying every commit
signature and re-hashing the full block parts on the device, hands off
to consensus, and answers /status, /block and /validators at the tip.

What comes out is checked against code that is not under test: the
fixture is signed by OpenSSL (`crypto/native.sign_one`), app and tip
hashes are compared with the source's, device part hashes with hashlib,
a chain with one forged commit signature must be refused and its
deliverer banned, and one mixed batch at the widest shape the repo ships
must agree lane by lane with OpenSSL's verdicts.

It refuses to run anywhere but on the chip, lets nothing be caught on
the way (any failed phase, assertion or timeout ends the process
non-zero), starts no process, and prints as the LAST line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The times it prints on the way are facts about one run, not metrics.
One process uses the chip: run it alone.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

# the whole run must stay inspectable: no span may fall off the ring
os.environ.setdefault("TM_FLIGHT_RECORDER_CAP", "1048576")

# device kinds this script knows, per platform it may be asked to expect
# ("cpu" is what the tier-1 test passes in; main() always expects "tpu")
KNOWN_DEVICE_KINDS = {
    "tpu": ("TPU v5 lite", "TPU v5e"),
    "cpu": ("cpu",),
}

# the deployment `BASELINE.json` names, cut in depth only: 100 validators
# (V buckets to 128; a 64-block reactor window is 6,400 lanes -> the
# 8,192-lane x 64-template executable a node warms at boot) and 17 full
# windows of blocks.  The FIRST window's blocks carry >= 64 KiB of txs
# (one full 64 KiB part each -> 64 lockstep part-hash lanes on the
# device), so the warm-up window takes both device paths and every
# compile of the sync belongs to it.  The forged chain (small blocks
# only) carries its forged commit in its third window.
FULL = dict(n_vals=100, n_blocks=17 * 64 + 1, big_first=1, big_count=64,
            n_sources=8, forged_blocks=3 * 64 + 1, forge_at=2 * 64 + 1,
            wide_lanes=65536, wide_templates=1024)

DEADLINE_S = 1150.0              # the driver allows 1200 s, compiles included
BIG_TX_BYTES = 66_000            # > one 64 KiB part


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"[chip_smoke] {key}: {value}", flush=True)


def wait_for(pred, timeout: float, what: str, poll: float = 0.02) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise SmokeFailure(f"timeout after {timeout:.0f}s waiting for "
                               f"{what}")
        time.sleep(poll)


# ---------------------------------------------------------------------------
# fixture: a host-signed chain, made from the seed
# ---------------------------------------------------------------------------

def make_validators(seed: int, n: int):
    """(privs aligned with the set's validator order, ValidatorSet)."""
    from tendermint_tpu.types import PrivKey, Validator, ValidatorSet
    privs = [PrivKey(hashlib.sha256(b"chip-smoke/%d/val/%d" % (seed, i))
                     .digest()) for i in range(n)]
    pubs = {p.seed: p.pub_key for p in privs}
    vs = ValidatorSet([Validator(pubs[p.seed], 10) for p in privs])
    by_addr = {pubs[p.seed].address: p for p in privs}
    return [by_addr[v.address] for v in vs.validators], vs


def build_chain(chain_id: str, privs, vs, n_blocks: int, big_first: int,
                big_count: int, forge_at: int | None = None):
    """[(block, part_set, seen_commit)] for heights 1..n_blocks, each
    block embedding the real +2/3 LastCommit of its predecessor (that is
    what fast-sync verifies).  Every signature is made by the host signer
    (`crypto/native.sign_one`, OpenSSL) over the repo's canonical
    sign-bytes; part sets are hashed by hashlib (no device backend is
    installed yet, and a block has < 16 full parts).  Heights in
    [big_first, big_first + big_count) carry one BIG_TX_BYTES tx.  With
    `forge_at`, one signature of that height's commit is corrupted."""
    from tendermint_tpu.abci.app import create_app
    from tendermint_tpu.crypto import native
    from tendermint_tpu.types import (TYPE_PRECOMMIT, Block, BlockID, Commit,
                                      EMPTY_COMMIT, Vote, ZERO_BLOCK_ID)
    check(native.AVAILABLE, "the OpenSSL host signer is not available")
    app = create_app("kvstore")
    vals_hash = vs.hash()
    addrs = [v.address for v in vs.validators]
    seeds = [p.seed for p in privs]
    out = []
    last_commit, last_block_id, app_hash = EMPTY_COMMIT, ZERO_BLOCK_ID, b""
    for h in range(1, n_blocks + 1):
        txs = [b"k%d=v%d" % (h % 7, h)]
        if big_first <= h < big_first + big_count:
            # one reused key: constant app state, so per-block apply cost
            # is the same at every height
            txs.append(b"big=" + hashlib.sha256(b"%d" % h).digest()
                       * (BIG_TX_BYTES // 32))
        block = Block.make(chain_id=chain_id, height=h,
                           time_ns=1_000_000_000 + h, txs=txs,
                           last_commit=last_commit,
                           last_block_id=last_block_id,
                           validators_hash=vals_hash, app_hash=app_hash)
        ps = block.make_part_set()
        bid = BlockID(block.hash(), ps.header)
        unsigned = [Vote(validator_address=addrs[i], validator_index=i,
                         height=h, round=0, type=TYPE_PRECOMMIT,
                         block_id=bid) for i in range(len(seeds))]
        msg = unsigned[0].sign_bytes(chain_id)   # signer-independent
        sigs = [native.sign_one(s, msg) for s in seeds]
        if forge_at == h:
            bad = bytearray(sigs[len(sigs) // 2])
            bad[7] ^= 0x20
            sigs[len(sigs) // 2] = bytes(bad)
        seen = Commit(block_id=bid, precommits=[
            Vote(**{**v.__dict__, "signature": s})
            for v, s in zip(unsigned, sigs)])
        out.append((block, ps, seen))
        for tx in txs:
            app.deliver_tx(tx)
        app_hash = app.commit().data
        last_commit, last_block_id = seen, bid
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def start_sources(chain_id: str, chain, gen, n: int, tag: str):
    """n dialable source peers serving `chain` from this process (they
    verify nothing and never touch jax), each behind the reference's
    per-peer rate limit exactly as configured (P2PConfig defaults)."""
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.scenarios import harness
    switches = []
    for i in range(n):
        sw, _state, store = harness.fastsync_source(
            chain_id, chain, gen, moniker=f"{tag}-{i}",
            config=P2PConfig(laddr="tcp://127.0.0.1:0", pex=False))
        check(store.height == len(chain), "source did not load the chain")
        sw.start()
        switches.append(sw)
    return switches


def boot_node(home: str, gen, sources):
    """What `cli node --home <home> --crypto-backend tpu --fast-sync`
    constructs: genesis and priv-validator on disk, sqlite stores, RPC
    and p2p on loopback, the supervised ladder off (the default)."""
    from tendermint_tpu.config import Config
    from tendermint_tpu.node.node import Node
    os.makedirs(home, exist_ok=True)
    cfg = Config()
    cfg.base.home = home
    cfg.base.chain_id = gen.chain_id
    cfg.base.moniker = os.path.basename(home)
    cfg.base.crypto_backend = "tpu"
    cfg.base.fast_sync = True
    cfg.crypto.supervised = False
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.p2p.persistent_peers = [str(sw._listener.addr) for sw in sources]
    gen.save(cfg.base.genesis_file())
    return Node(cfg)


def stop_all(node, sources) -> None:
    if node is not None:
        node.stop()
    for sw in sources:
        sw.stop()


def compile_spans(since_epoch: float):
    from tendermint_tpu.utils import tracing
    return [s for s in tracing.RECORDER.snapshot()
            if s["name"] == "xla.compile" and s["ts"] >= since_epoch]


# the jitted entry points of ops/: what a node must never compile again
# once warm.  Everything else jax compiles is a one-op helper
# (`jnp.zeros` of a new shape and the like): well under a second, never
# persisted by jax, and which ones a process needs depends on which
# thread wins the race to load a table — counted and printed, not failed.
KERNELS = frozenset(f"jit({n})" for n in (
    "verify", "verify_grouped", "verify_grouped_templated",
    "build_neg_comb", "leaf_hashes", "roots", "root_from_leaf_hashes"))


def kernel_compiles(spans):
    """Real backend compiles (not loads) of a kernel, or of anything that
    took as long as one."""
    return [s for s in spans if not s["args"]["cached"] and
            (s["args"]["fn"] in KERNELS or s["dur"] >= 1.0)]


def precompile_running() -> bool:
    return any(t.name == "crypto-precompile" and t.is_alive()
               for t in threading.enumerate())


def phase_sync(workdir, chain_id, chain, gen, sizes, facts) -> None:
    """The main path: boot, fast-sync, hand off, answer RPC."""
    from tendermint_tpu.blockchain.reactor import DEFAULT_BATCH
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types.part_set import PART_SIZE
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    n_vals, n_blocks = sizes["n_vals"], sizes["n_blocks"]
    target = n_blocks - 1          # each block needs its successor's commit
    sources = start_sources(chain_id, chain, gen, sizes["n_sources"], "src")
    node = None
    try:
        before = REGISTRY.snapshot()
        spans_before = tracing.RECORDER.total
        t0, t0_epoch = time.monotonic(), tracing.now_epoch()
        node = boot_node(os.path.join(workdir, "node"), gen, sources)
        be = cb.get_backend()
        check(type(be).__name__ == "TpuBackend" and be.name == "tpu",
              f"node installed backend {be!r}, not the device backend")
        check(be.platform == facts["platform"],
              f"backend got platform {be.platform}, jax reports "
              f"{facts['platform']}")
        node.start()
        wait_for(lambda: node.block_store.height >= target, DEADLINE_S / 2,
                 f"fast-sync to height {target} (at "
                 f"{node.block_store.height})")
        sync_wall = time.monotonic() - t0
        t_synced = tracing.now_epoch()
        wait_for(lambda: node.consensus.get_round_state_summary()["height"]
                 == target + 1, 30, "hand-off to consensus")
        wait_for(lambda: not precompile_running(), DEADLINE_S / 2,
                 "the boot precompile thread")
        after = REGISTRY.snapshot()
        spans = [s for s in tracing.RECORDER.snapshot()
                 if s["ts"] >= t0_epoch]

        # -- the device answered every commit lane --------------------
        lanes = target * n_vals
        delta = {k: after[k] - before[k] for k in
                 ("sigs_verified", "sigs_requested", "verify_batches",
                  "h2d_bytes", "blocks_synced", "crypto_fallback_calls")}
        check(delta["blocks_synced"] == target,
              f"blocks_synced moved by {delta['blocks_synced']}, "
              f"expected {target}")
        check(delta["sigs_verified"] >= lanes,
              f"device verified {delta['sigs_verified']} lanes, chain has "
              f"{lanes}")
        windows = -(-target // DEFAULT_BATCH)
        check(delta["verify_batches"] >= windows,
              f"{delta['verify_batches']} device batches for {windows} "
              "windows")
        check(delta["h2d_bytes"] >= lanes * 72,
              f"only {delta['h2d_bytes']} bytes went to the device")
        check(delta["crypto_fallback_calls"] == 0,
              "a call was served below the device rung")
        check(not any(s["name"] == "scalar.verify" for s in spans),
              "a scalar.verify span was recorded: lanes fell off the device")
        check(tracing.RECORDER.total - spans_before <=
              tracing.RECORDER.capacity, "the flight recorder overflowed")
        if be._mesh_eligible(cb._bucket(DEFAULT_BATCH * n_vals)):
            # more than one chip: the backend shards a window's lanes on
            # its own, so every device must have served some
            per_dev = dict(REGISTRY.device_lanes.items())
            check(len(per_dev) == facts["count"] and
                  all(v > 0 for v in per_dev.values()),
                  f"lanes did not reach every device: {per_dev}")
            say("lanes per device", per_dev)

        # -- hashes equal the source's --------------------------------
        tip_block, _ps, _seen = chain[target - 1]
        want_app = chain[target][0].header.app_hash   # state after `target`
        rpc = HTTPClient(node.rpc_server.addr)
        st = rpc.status()
        check(st["latest_block_height"] == target, f"/status height {st}")
        check(st["latest_block_hash"] == tip_block.hash().hex(),
              "/status tip hash differs from the source's")
        check(st["latest_app_hash"] == want_app.hex(),
              "/status app hash differs from the source's")
        check(st["validator_count"] == n_vals, "/status validator count")
        blk = rpc.block(height=target)["block"]
        check(blk["block_hash"] == tip_block.hash().hex() and
              blk["header"]["height"] == target and
              blk["last_commit"]["precommits"] == n_vals,
              "/block at the tip differs from the source's")
        vals = rpc.validators()["validators"]
        check([v["pub_key"] for v in vals] ==
              [gv.pub_key.hex() for gv in gen.validators],
              "/validators differs from genesis")

        # -- compiles: none after the warm-up window ------------------
        first = min((s for s in spans if s["name"] in
                     ("fastsync.verify", "fastsync.lookahead")),
                    key=lambda s: s["ts"] + s["dur"])
        t_first = first["ts"] + first["dur"]
        # ... and before the tip: at the hand-off the node starts warming
        # the live path's programs (`Node._maybe_precompile`), and
        # consensus may ask for one of them first
        late = kernel_compiles(s for s in compile_spans(t_first)
                               if s["thread"] != "crypto-precompile"
                               and s["ts"] < t_synced)
        check(not late, f"kernel compiles after the warm-up window: "
              f"{[(s['args']['fn'], s['thread']) for s in late]}")
        # from here on (boot precompile done too) nothing may compile a
        # kernel or dispatch a new shape, whichever thread it is on
        facts["warm_epoch"] = tracing.now_epoch()
        facts["warm_recompiles"] = after["xla_recompiles"]

        # -- part hashes: device path taken, equal to hashlib ---------
        dev_hash = [s for s in spans if s["name"] == "parthash.device"]
        check(dev_hash, "no window took the device part-hash path")
        big = [chain[h - 1][0] for h in range(
            sizes["big_first"], sizes["big_first"] + sizes["big_count"])]
        from tendermint_tpu.types import part_set as ps_mod
        chunks = [b.encode()[:PART_SIZE] for b in big]
        got = ps_mod._device_full_chunk_hashes(chunks, PART_SIZE)
        check(got is not None, "device part hashing declined")
        check(got == [hashlib.sha256(b"\x00" + c).digest() for c in chunks],
              "device part hashes differ from hashlib")
        for b in big:
            meta = node.block_store.load_block_meta(b.height)
            check(meta.block_id.parts == chain[b.height - 1][1].header,
                  f"stored part-set header at {b.height} differs")

        say("validators", n_vals)
        say("blocks synced", target)
        say("commit lanes answered by the device", delta["sigs_verified"])
        say("device verify batches", delta["verify_batches"])
        say("bytes host->device", delta["h2d_bytes"])
        say("fallback calls", delta["crypto_fallback_calls"])
        say("scalar.verify spans", 0)
        say("device part-hash windows",
            [s["args"]["chunks"] for s in dev_hash])
        say("lookahead windows consumed",
            node.switch.reactor("blockchain").lookahead_hits)
        say("seconds from boot to first verified window",
            round(t_first - t0_epoch, 2))
        say("wall seconds of the sync (boot to tip)", round(sync_wall, 2))
        say("app hash", st["latest_app_hash"])
        say("tip hash", st["latest_block_hash"])
    finally:
        stop_all(node, sources)


def phase_forged(workdir, chain_id, forged, gen, sizes, facts) -> None:
    """A served chain with one forged commit signature is refused at
    that height and whoever delivered it is banned — not synced.  Two
    peers serve the same forged chain, so the refusal has to hold when
    the block is fetched again elsewhere."""
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    forge_at = sizes["forge_at"]
    liars = start_sources(chain_id, forged, gen, 2, "liar")
    liar_ids = [sw.node_info.id for sw in liars]
    node = None
    try:
        t0_epoch = tracing.now_epoch()
        node = boot_node(os.path.join(workdir, "node-forged"), gen, liars)
        node.start()
        wait_for(lambda: all(node.switch.is_banned(i) for i in liar_ids),
                 DEADLINE_S / 4, "both deliverers of the forged commit to "
                 f"be banned (node at {node.block_store.height})")
        # a slow peer is evicted too ("request timeouts"), redials and
        # carries on; only a proven lie bans
        lies = [s["args"] for s in tracing.RECORDER.snapshot()
                if s["name"] == "pool.evict" and s["ts"] >= t0_epoch
                and s["args"]["reason"].startswith("bad block")]
        check(len(lies) == 2 and
              {e["reason"] for e in lies} ==
              {f"bad block at height {forge_at + 1}"},
              f"expected the two deliverers of height {forge_at + 1} to be "
              f"blamed, got {lies}")
        check(sorted(e["peer"] for e in lies) ==
              sorted(i[:12] for i in liar_ids), "blamed a different peer")
        wait_for(lambda: not node.switch.peers(), 10,
                 "the liars to be disconnected")
        time.sleep(1.0)            # nothing more may land after the bans
        height = node.block_store.height
        check(height < forge_at, f"synced to {height}, past the forged "
              f"commit at {forge_at}")
        wait_for(lambda: not precompile_running(), DEADLINE_S / 4,
                 "the boot precompile thread")
        late = compile_spans(facts["warm_epoch"])
        kernels = kernel_compiles(late)
        check(not kernels, f"kernel compiles after warm-up: "
              f"{[(s['args']['fn'], s['thread']) for s in kernels]}")
        drift = REGISTRY.xla_recompiles.value - facts["warm_recompiles"]
        check(drift == 0, f"{drift} new shapes dispatched after warm-up")
        say("forged chain", f"commit {forge_at} refused, both deliverers of "
            f"{forge_at + 1} banned, node stopped at {height}")
        say("after the warm-up window: kernel compiles / shape drift / "
            "one-op helper compiles",
            f"0 / 0 / {sum(not s['args']['cached'] for s in late)}")
    finally:
        stop_all(node, liars)


def phase_wide(seed: int, privs, vs, sizes) -> None:
    """One mixed batch at the widest shape the repo ships, lane by lane
    against OpenSSL (`crypto/native.verify_one`)."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.crypto import native
    from tendermint_tpu.types import canonical
    n, t = sizes["wide_lanes"], sizes["wide_templates"]
    rng = np.random.default_rng(seed)
    seeds = [p.seed for p in privs]
    # the chain's keys plus one that is not on the curve (the bigint
    # reference finds no x for this y)
    from tendermint_tpu.crypto import pure_ed25519 as ref
    off_curve = next(y.to_bytes(32, "little") for y in range(2, 64)
                     if ref.pt_decode(y.to_bytes(32, "little")) is None)
    pubs = np.frombuffer(b"".join(v.pub_key.bytes_ for v in vs.validators)
                         + off_curve, np.uint8).reshape(-1, 32)
    v_off = len(seeds)
    templates = np.stack([np.frombuffer(canonical.sign_bytes(
        "chip-smoke-wide", canonical.TYPE_PRECOMMIT, h + 1, 0,
        block_hash=hashlib.sha256(b"wb%d" % h).digest(),
        parts_hash=hashlib.sha256(b"wp%d" % h).digest(), parts_total=1),
        np.uint8) for h in range(t)])
    val_idx = (np.arange(n) % len(seeds)).astype(np.int32)
    tmpl_idx = (np.arange(n) // 64 % t).astype(np.int32)
    with ThreadPoolExecutor(8) as pool:
        sigs = np.frombuffer(b"".join(pool.map(
            lambda i: native.sign_one(seeds[val_idx[i]],
                                      templates[tmpl_idx[i]].tobytes()),
            range(n), chunksize=1024)), np.uint8).reshape(n, 64).copy()
    k = max(4, n // 64)            # lanes per adversarial class
    bad = rng.choice(n, 7 * k, replace=False).reshape(7, k)
    sigs[bad[0], 3] ^= 0x01                               # forged R
    sigs[bad[1], 40] ^= 0x01                              # forged s
    L = 2**252 + 27742317777372353535851937790883648493
    for i in bad[2]:                                      # s + L: s >= L
        s = int.from_bytes(sigs[i, 32:].tobytes(), "little") + L
        sigs[i, 32:] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
    noncanon = (2**255 - 19 + 1).to_bytes(32, "little")   # y = p + 1
    sigs[bad[3], :32] = np.frombuffer(noncanon, np.uint8)  # non-canonical R
    tmpl_idx[bad[4]] = (tmpl_idx[bad[4]] + 1) % t         # wrong template
    val_idx[bad[5]] = v_off                               # off-curve key
    val_idx[bad[6]] = (val_idx[bad[6]] + 1) % len(seeds)  # wrong signer
    # OpenSSL's verdict for every lane, independent of the code under test
    rows = [(pubs[val_idx[i]].tobytes(), templates[tmpl_idx[i]].tobytes(),
             sigs[i].tobytes()) for i in range(n)]
    with ThreadPoolExecutor(8) as pool:
        want = np.fromiter(pool.map(lambda r: native.verify_one(*r), rows,
                                    chunksize=1024), bool, n)
    expect = np.ones(n, bool)
    expect[bad.ravel()] = False
    check((want == expect).all(), "OpenSSL disagrees with the construction "
          "of the mixed batch")
    t0 = time.monotonic()
    got = cb.verify_grouped_templated(
        hashlib.sha256(pubs.tobytes()).digest(), pubs, val_idx, tmpl_idx,
        templates, sigs)
    wall = time.monotonic() - t0
    check(got.shape == (n,) and got.dtype == bool, "verdict shape")
    diff = np.flatnonzero(got != want)
    check(len(diff) == 0, f"{len(diff)} lanes disagree with OpenSSL, first "
          f"at lane {diff[:1]}")
    say("mixed batch", f"{n} lanes x {t} templates, {int(want.sum())} valid "
        f"/ {int((~want).sum())} invalid in 7 classes: all {n} verdicts "
        "equal OpenSSL's")
    say("wall seconds of the mixed batch (table build and compile included)",
        round(wall, 2))


# ---------------------------------------------------------------------------

def run(*, expect_platform: str, seed: int, n_vals: int, n_blocks: int,
        big_first: int, big_count: int, n_sources: int, forged_blocks: int,
        forge_at: int, wide_lanes: int, wide_templates: int) -> dict:
    """The whole smoke at the given sizes; returns the final JSON object.
    Raises on any failed phase."""
    sizes = dict(n_vals=n_vals, n_blocks=n_blocks, big_first=big_first,
                 big_count=big_count, n_sources=n_sources,
                 forged_blocks=forged_blocks, forge_at=forge_at,
                 wide_lanes=wide_lanes, wide_templates=wide_templates)
    t_start = time.monotonic()
    import importlib.metadata as md
    import jax
    dev = jax.devices()
    platform, kind = dev[0].platform, dev[0].device_kind
    facts = {"platform": platform, "count": len(dev)}
    say("platform", platform)
    say("device_kind", kind)
    say("device count", len(dev))
    say("versions", {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")})
    if (jax.default_backend() != expect_platform or
            platform != expect_platform or
            kind not in KNOWN_DEVICE_KINDS.get(expect_platform, ())):
        raise SystemExit(
            f"chip_smoke: this run needs platform {expect_platform!r} with a "
            f"device kind in {KNOWN_DEVICE_KINDS.get(expect_platform)}; jax "
            f"found platform {platform!r}, device kind {kind!r}. Nothing "
            "was run.")

    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    from tendermint_tpu.utils import nativelib
    from tendermint_tpu.utils.metrics import REGISTRY
    from tendermint_tpu.utils import tracing
    run_epoch = tracing.now_epoch()
    fallbacks_before = REGISTRY.crypto_fallback_calls.value
    cache_dir = cb.compile_cache_dir()
    cached_before = (len(os.listdir(cache_dir))
                     if os.path.isdir(cache_dir) else 0)
    say("cache directory", f"{cache_dir} ({cached_before} entries at start; "
        "JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    nativelib.get()
    say("native/libtmhash.so", nativelib.build_status)

    chain_id = f"chip-smoke-{seed}"
    t0 = time.monotonic()
    privs, vs = make_validators(seed, n_vals)
    gen = GenesisDoc(chain_id=chain_id, genesis_time_ns=1_000_000_000,
                     validators=[GenesisValidator(v.pub_key.bytes_, 10)
                                 for v in vs.validators])
    chain = build_chain(chain_id, privs, vs, n_blocks, big_first, big_count)
    forged = build_chain(chain_id, privs, vs, forged_blocks, 0, 0,
                         forge_at=forge_at)
    say("fixture", f"{n_blocks} + {forged_blocks} blocks x {n_vals} "
        f"validators host-signed by OpenSSL in "
        f"{time.monotonic() - t0:.1f}s, "
        f"{sum(len(c[0].encode()) for c in chain) / 1e6:.1f} MB to sync")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        phase_sync(workdir, chain_id, chain, gen, sizes, facts)
        phase_forged(workdir, chain_id, forged, gen, sizes, facts)
    if wide_lanes:
        phase_wide(seed, privs, vs, sizes)
    else:
        say("mixed batch", "skipped (wide_lanes=0)")

    snap = REGISTRY.snapshot()
    check(snap["crypto_fallback_calls"] == fallbacks_before,
          "fallback calls at exit")
    compiles = compile_spans(run_epoch)
    by_fn: dict[str, list] = {}
    for s in compiles:
        by_fn.setdefault(f"{s['args']['fn']}"
                         f"{' (cache load)' if s['args']['cached'] else ''}",
                         []).append(s["dur"])
    for fn, durs in sorted(by_fn.items(), key=lambda kv: -sum(kv[1])):
        if sum(durs) >= 0.5:
            say(f"compile seconds, {fn}",
                f"{sum(durs):.1f} over {len(durs)} executable(s)")
    # jax persists what took >= 1 s to compile (its default threshold);
    # the rest are one-op helpers (jnp.zeros and the like) it recompiles
    # in every process
    real = [s["dur"] for s in compiles if not s["args"]["cached"]]
    loads = len(compiles) - len(real)
    say("backend compiles of 1 s or more / shorter ones / loads from the "
        "persistent cache",
        f"{sum(d >= 1.0 for d in real)} / {sum(d < 1.0 for d in real)} / "
        f"{loads}")
    say("cache hit", "yes" if loads else "no")
    mem = dev[0].memory_stats() or {}
    say("peak_bytes_in_use", mem.get("peak_bytes_in_use", "not reported"))
    from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
    say(f"comb table logical bytes (one {n_vals}-validator set, V bucket "
        f"{cb._bucket(n_vals)})",
        COMB_WINDOWS * COMB_DIGITS * cb._bucket(n_vals) * 3 * 32)
    say("wall seconds, whole run", round(time.monotonic() - t_start, 1))
    return {"ok": True, "device": {"platform": platform, "kind": kind,
                                   "count": len(dev)}}


def _watchdog() -> None:
    """Nothing here may hang past the driver's limit: past the deadline
    the process ends non-zero, whatever it is stuck in."""
    def fire():
        print(f"chip_smoke: still running after {DEADLINE_S:.0f}s; giving up",
              file=sys.stderr, flush=True)
        os._exit(5)
    t = threading.Timer(DEADLINE_S, fire)
    t.daemon = True
    t.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="every key, block and adversarial lane derives "
                         "from it")
    args = ap.parse_args(argv)
    _watchdog()
    result = run(expect_platform="tpu", seed=args.seed, **FULL)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
