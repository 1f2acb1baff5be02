"""The cell `catchup-1ktx-100v.full-blocks`: its files, what its
per-layer metrics return on a run's slow branch (no look-ahead, no full
part, a quiet pool), and the program against the chain builder on
multi-part, many-tx blocks at a size the CPU holds."""

import json
import os
import time

import pytest

from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain, reducers, source_child

CELL = "catchup-1ktx-100v.full-blocks"
ACCEPTED = ("catchup-100v.empty-blocks", "testnet-4v.empty-blocks")
NEW_SPAN_METRICS = (
    "reactor.lookahead_per_window_ms", "reactor.parthash_device_ms",
    "apply.txs_hash_ms", "pool.boot_rerequests", "pool.boot_evictions",
    "pool.boot_late_blocks")

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def test_the_cell_loads_and_its_chain_is_the_configuration_files():
    cell = cell_mod.load_cell(REPO, CELL)
    cfg = cell["config"]
    plan = cfg["chain"]["full-blocks"]
    assert cell_mod.chain_plan(cell) == plan and plan["headroom"] == 3.0
    n = cell_mod.chain_blocks(cell, BENCH["run_seconds"])
    want = plan["headroom"] * plan["parent_blocks_per_s"] * (
        plan["warmup_s"] + BENCH["run_seconds"])
    assert n % 64 == 1 and 0 <= n - 1 - int(want) < 64
    assert n == 3457
    # the accepted traffic file's own plan is as it was, and not used
    assert cell["traffic"]["chain"]["catchup-100v"] == {
        "parent_blocks_per_s": 11.6, "warmup_s": 50}
    # what the file states for the reader is the traffic that is run
    assert {k: cfg["block"][k] for k in ("txs_per_block", "tx_bytes")} == {
        k: cell["traffic"]["block"][k] for k in ("txs_per_block", "tx_bytes")}


def test_the_configuration_keeps_catchup_100vs_shapes_and_guarantees():
    with open(os.path.join(REPO, "benchmark/configs/catchup-100v.json")) as f:
        base = json.load(f)
    cfg = cell_mod.load_cell(REPO, CELL)["config"]
    for k in ("validators", "source_peers", "app", "part_bytes",
              "peer_rate_bytes_per_s", "max_pending_requests",
              "max_pending_per_peer", "window_blocks", "chips"):
        assert cfg[k] == base[k], k
    assert cfg["guarantees"][:3] == base["guarantees"]
    assert "part-set root and tx Merkle root" in cfg["guarantees"][3]
    cell_mod.stated_as_run(cfg)


@pytest.mark.parametrize("workload", ACCEPTED + (CELL,))
def test_lookahead_ms_is_read_where_a_lookahead_is_sure_to_run(workload):
    """`reactor.lookahead_ms` divides by the look-aheads, so it has
    nothing to say in a run without one; its twin per window reads 0."""
    names = {m["name"] for m in
             cell_mod.load_cell(REPO, workload)["per_layer"]}
    assert ("reactor.lookahead_ms" in names) == (workload in ACCEPTED)
    assert "reactor.lookahead_per_window_ms" in names
    assert ("kernel.parthash_ms" in names) == (workload == CELL)


def slow_branch_ctx() -> dict:
    """What a run holds whose every window was prepared synchronously,
    over blocks with no full part, with a quiet pool: two completions of
    a window and nothing this PR's metrics look for."""
    spans = []
    for i in range(2):
        t = 10.0 + i
        spans += [
            {"name": "fastsync.prepare", "ts": t, "dur": 0.2},
            {"name": "fastsync.verify", "ts": t + 0.2, "dur": 0.1},
            {"name": "verify.dispatch", "ts": t + 0.2, "dur": 0.05},
            {"name": "verify.collect", "ts": t + 0.25, "dur": 0.05},
            {"name": "fastsync.apply", "ts": t + 0.3, "dur": 0.6},
            {"name": "fastsync.window", "ts": t, "dur": 0.9,
             "args": {"window": 1 + 64 * i, "blocks": 64}}]
    return {
        "spans": spans, "boot_spans": [], "notes": [],
        "hists": {"batchplane_wait_seconds": {"fastsync": (2, 0.01)}},
        "harness": {"link_util_pct": 30.0, "rpc_status_p95_ms": 5.0,
                    "hbm_peak_MiB": 700.0, "device_kind": "TPU v5 lite",
                    "bucket_lanes": 8192, "bucket_templates": 64},
        "trace": {"idle_pct": 98.0, "reactor_windows": 2, "kernels": {
            "jit_verify_grouped_templated": (2, 0.07),
            "jit_leaf_hashes": (2, 0.05)}}}


@pytest.mark.parametrize("name", NEW_SPAN_METRICS)
def test_a_new_span_metric_reads_zero_and_not_nothing(name):
    spec = reducers.load_layer(REPO, name)
    ctx = slow_branch_ctx()
    assert reducers.read_metric(spec, ctx) == 0.0
    # and counts what is there: one record of each kind at boot and in
    # the interval
    for span in ("fastsync.lookahead", "parthash.device", "block.txs_hash",
                 "pool.rerequest", "pool.evict", "pool.late_block"):
        rec = {"name": span, "ts": 10.5, "dur": 0.05}
        ctx["spans"].append(rec)
        ctx["boot_spans"].append(rec)
    want = 1.0 if spec["reducer"] == "span_count" else 25.0
    assert reducers.read_metric(spec, ctx) == pytest.approx(want)


@pytest.mark.parametrize(
    "name", [m["name"] for m in BENCH["per_layer"]
             if "workloads" not in m or CELL in m["workloads"]])
def test_every_metric_of_the_cell_has_a_number_on_the_slow_branch(name):
    """Whenever two reactor windows complete, a traced run's line has
    every per-layer metric of the cell: none divides by a span that a
    run on its slow branch lacks."""
    spec = reducers.load_layer(REPO, name)
    assert reducers.read_metric(spec, slow_branch_ctx()) is not None
    if spec["reducer"] == "span_ms_per":
        # what every window has on either branch: itself, its apply, and
        # the device call that verified it
        assert spec["args"]["per"] in ("fastsync.window", "fastsync.apply",
                                       "verify.collect")


def test_parthash_kernel_time_is_per_reactor_window_of_the_trace():
    spec = reducers.load_layer(REPO, "kernel.parthash_ms")
    assert reducers.read_metric(spec, slow_branch_ctx()) == \
        pytest.approx(25.0)
    assert reducers.read_metric(spec, dict(slow_branch_ctx(),
                                           trace=None)) is None


# -- the program against the builder, on full blocks ------------------------

with open(os.path.join(REPO, "benchmark/traffic/full-blocks.json")) as _f:
    FULL = json.load(_f)["block"]        # 1,000 txs x 250 B, as the cell
# with 4 validators' commits a block is ~255 KB: 3 full parts and a tail
# (the cell's 100-vote commits make it 273 KB, 4 full parts and a tail)
N_VALS, N_BLOCKS, SEED, PARTS = 4, 20, 2**31 + 29, 4


@pytest.fixture(scope="module")
def built():
    seeds, vs = chain.make_validators(SEED, N_VALS)
    out = chain.build_chain("bench-full", seeds, vs, N_BLOCKS, FULL, SEED,
                            keep_objects=True)
    out["vs"] = vs
    return out


def test_full_blocks_sync_to_the_builders_hashes_through_the_real_path(built):
    """20 blocks of 1,000 txs x 250 B (4 parts each) from the
    benchmark's own source store, fast-synced through the real pool,
    reactor, look-ahead and `apply_window`: block hash, part-set header
    and app hash at every height are the builder's (OpenSSL, hashlib,
    its own kvstore)."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.p2p import connect_switches, make_switch
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.db import MemDB

    assert all(-(-len(e) // 65536) == PARTS for e in built["encoded"])
    gen = chain.genesis_doc(chain.genesis_dict("bench-full", built["vs"]))
    fast = P2PConfig(laddr="", pex=False, send_rate=64 << 20,
                     recv_rate=64 << 20)
    src = BlockchainReactor(get_state(MemDB(), gen), None,
                            source_child.ServedStore(built["encoded"]),
                            fast_sync=False)
    src_sw = make_switch("bench-full", {"blockchain": src}, config=fast)
    bc = BlockchainReactor(get_state(MemDB(), gen),
                           ClientCreator("kvstore").new_app_conns().consensus,
                           BlockStore(MemDB()), fast_sync=True, batch_size=8)
    sync_sw = make_switch("bench-full", {"blockchain": bc}, config=fast)
    old = cb._current
    cb.set_backend("native")
    t0 = tracing.now_epoch()
    src_sw.start()
    sync_sw.start()
    try:
        connect_switches(sync_sw, src_sw)
        deadline = time.time() + 60
        want = built["app_hash"][N_BLOCKS - 2]
        while ((bc.store.height < N_BLOCKS - 1 or bc.state.app_hash != want)
               and time.time() < deadline):
            time.sleep(0.02)
        assert bc.store.height >= N_BLOCKS - 1, bc.pool.status()
    finally:
        src_sw.stop()
        sync_sw.stop()
        bc.stop()
        if bc._thread is not None:
            bc._thread.join(timeout=10)
        cb._current = old
    for h in range(1, N_BLOCKS):
        block, ps, _seen = built["objects"][h - 1]
        meta = bc.store.load_block_meta(h)
        assert meta.block_id.hash == built["block_hash"][h - 1]
        assert meta.block_id.parts == ps.header and ps.header.total == PARTS
        got = bc.store.load_block(h)
        assert got.encode() == built["encoded"][h - 1]
        assert got.header.data_hash == block.header.data_hash
        if h > 1:             # block h carries the app hash after h - 1
            assert got.header.app_hash == built["app_hash"][h - 2]
    assert bc.state.app_hash == built["app_hash"][N_BLOCKS - 2]
    # one `block.txs_hash` record a block applied, inside apply's validate
    # stage; bookkeeping, so no category
    recs = [s for s in tracing.RECORDER.since(t0) if s["ts"] >= t0]
    roots = [s for s in recs if s["name"] == "block.txs_hash"]
    assert len(roots) == N_BLOCKS - 1
    assert all(s["dur"] > 0 and not s.get("cat") for s in roots)
    stages = [s for s in recs if s["name"] == "fastsync.apply.validate"]
    assert sum(s["dur"] for s in roots) <= sum(s["dur"] for s in stages)


def test_part_sets_of_a_window_device_path_against_hashlib_and_the_builder(
        built, monkeypatch):
    """`from_data_batched` on a window of full blocks with the device
    path taken (60 full chunks >= DEVICE_MIN_CHUNKS, the backend named
    tpu, jax on the CPU here) against the host path and the builder's
    part sets: equal, part for part."""
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.types import merkle, part_set
    from tendermint_tpu.utils import tracing

    datas = built["encoded"]
    n_full = sum(len(d) // part_set.PART_SIZE for d in datas)
    assert n_full == (PARTS - 1) * N_BLOCKS >= part_set.DEVICE_MIN_CHUNKS
    host = [part_set.PartSet._assemble(
        chunks, [merkle.leaf_hash(c) for c in chunks])
        for chunks in ([d[i:i + part_set.PART_SIZE]
                        for i in range(0, len(d), part_set.PART_SIZE)]
                       for d in datas)]
    monkeypatch.setattr(cb, "active_backend_name", lambda: "tpu")
    t0 = tracing.now_epoch()
    dev = part_set.from_data_batched(datas)
    calls = [s for s in tracing.RECORDER.since(t0)
             if s["ts"] >= t0 and s["name"] == "parthash.device"]
    assert [(s["args"]["chunks"], s["args"]["bucket"]) for s in calls] == [
        (n_full, 64)]
    for d, h, (_block, ps, _seen) in zip(dev, host, built["objects"]):
        assert d.header == h.header == ps.header and d.total == PARTS
        for i in range(PARTS):
            got, want = d.get_part(i), ps.get_part(i)
            assert got.bytes_ == want.bytes_ == h.get_part(i).bytes_
            assert got.proof == want.proof == h.get_part(i).proof
            assert got.verify(ps.header)
    # a flipped byte in one full chunk moves that block's root, and only it
    bad = bytearray(datas[3])
    bad[70_000] ^= 1
    again = part_set.from_data_batched([bytes(bad)] + datas[4:])
    assert again[0].header != built["objects"][3][1].header
    assert [p.header for p in again[1:]] == [
        o[1].header for o in built["objects"][4:]]
