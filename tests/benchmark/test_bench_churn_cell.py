"""The cell `catchup-churn-100v.valset-churn` as files, and one rehearsal
of it on the CPU: `benchmark/run.py`'s run at 4 validators and 2 peers,
the program's own `valset_kvstore` named by the rehearsal's configuration
and the validator-set plan READ FROM `benchmark/traffic/valset-churn.json`
(a change every 200 blocks: every set ends in a window cut to 8 blocks).

A comb table builds 22-60 s a set on the CPU backend, and between two
builds the node syncs 200 heights in a second or two, so a measured window
of any length closes while some set's table builds, and the close then
waits for that build (the harness gives it 120 s).  The rehearsal warms up
over four windows (256 heights: the second set's table is built by then),
so that its window opens on windows that run (265-328, 329-392 and the cut
one, 393-400), and measures for 45 s: the close then falls late in the
third build or in the fourth, and a machine 2.5 times slower than this one
at its slowest still stops in time.  Two to three minutes, under a time
limit of its own."""

import json
import os
import types

import pytest

import benchutil
from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import reducers

CELL = "catchup-churn-100v.valset-churn"
NEW_LAYERS = ["tables.build_ms", "tables.builds", "tables.evictions",
              "reactor.valset_cuts", "kernel.tablebuild_ms"]
NAMES = ["refused", "wrong_hash", "tip_hash_differs", "app_hash_differs",
         "rpc_answers_differ", "fallback_calls", "scalar_verify_spans",
         "sigs_verified", "kernel_programs_in_window", "ring_overflowed",
         "probe_errors", "control_lanes_differ", "control_programs"]
with open(os.path.join(REPO, "benchmark", "traffic",
                       "valset-churn.json")) as _f:
    MIX = json.load(_f)
WARM_4 = """
from benchmark.lib import cell as _cell
_cell.WARM_WINDOWS = 4
"""


def test_the_cell_loads_and_serves_the_plain_cells_chain():
    cell = cell_mod.load_cell(REPO, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["valset"] == {
        "change_every_blocks": 200, "swap": 1}
    plan = cell_mod.chain_plan(cell)
    assert plan == {"parent_blocks_per_s": 30, "warmup_s": 22,
                    "headroom": 9.0}
    assert cell["traffic"]["chain"]["default"] == \
        cell["traffic"]["chain"]["catchup-churn-100v"]
    # 18,113 heights = 91 sets: the chain catchup-100v.empty-blocks serves
    assert cell_mod.chain_blocks(cell, 45) == 18113 == cell_mod.chain_blocks(
        cell_mod.load_cell(REPO, "catchup-100v.empty-blocks"), 45)
    assert cell["traffic"]["block"] == cell_mod.load_cell(
        REPO, "catchup-100v.empty-blocks")["traffic"]["block"]
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= set(NEW_LAYERS) | {"kernel.verify_ms",
                                       "verify_grouped_templated_roofline",
                                       "device.idle_pct"}
    assert "kernel.parthash_ms" not in names


def test_the_configuration_is_catchup_100vs_with_an_app_the_program_has():
    from tendermint_tpu.abci.app import create_app
    with open(os.path.join(REPO, "benchmark", "configs",
                           "catchup-100v.json")) as f:
        plain = json.load(f)
    cfg = cell_mod.load_cell(REPO, CELL)["config"]
    for key in ("validators", "source_peers", "part_bytes",
                "peer_rate_bytes_per_s", "max_pending_requests",
                "max_pending_per_peer", "window_blocks", "chips",
                "upstream_chain_blocks", "reduced"):
        assert cfg[key] == plain[key], key
    assert cfg["guarantees"][:3] == plain["guarantees"]
    assert len(cfg["guarantees"]) == 4 and "/validators" in cfg[
        "guarantees"][3]
    assert {"change_every_blocks", "swap"} <= set(cfg["assumed"])
    assert cfg["app"] == "valset_kvstore" != plain["app"]
    assert type(create_app(cfg["app"])).__name__ == "ValsetKVStoreApp"
    node_cfg = types.SimpleNamespace(
        base=types.SimpleNamespace(proxy_app=cfg["app"]))
    cell_mod.stated_as_run(cfg, node_cfg)


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIGS = {c["name"]: c for c in BENCH["configs"]}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_configuration_states_an_app_the_program_has(config):
    """Every configuration of `BENCHMARK.json` is held to the harness's
    own rule, by behaviour and not by name
    (`benchutil.config_states_an_app_that_fits`: the app is in the
    program's registry, returns `val:` txs as `EndBlock` diffs exactly
    where the mix of a cell that runs it states a `valset` or a `powers`
    plan, a node booted on it is the deployment as stated, and a node on
    any other app is an error); an ACCEPTED configuration also keeps the
    app it was accepted with (`benchutil.ACCEPTED_APPS`), and a name that
    is not in that record is held to the rule alone.
    `test_bench_valset.py::test_every_accepted_configuration_names_an_app_the_program_boots`
    overlaps: it holds `create_app` and `stated_as_run` on the stated
    app, without the rule, the record or the refusal of another app."""
    benchutil.config_states_an_app_that_fits(REPO, BENCH, CONFIGS[config])


def test_every_new_layer_file_loads_and_reads_its_span():
    specs = {n: reducers.load_layer(REPO, n) for n in NEW_LAYERS}
    assert {s["moves"] for s in specs.values()} == {"sync_blocks_per_s"}
    spans = [{"name": "fastsync.window", "ts": 0.0, "dur": 1.0},
             {"name": "fastsync.window", "ts": 1.0, "dur": 6.0},
             {"name": "tables.build", "ts": 1.0, "dur": 5.0,
              "args": {"v": 100, "bytes": 327155712}},
             {"name": "tables.evict", "ts": 6.0, "dur": 0.0,
              "args": {"bytes": 327155712}},
             {"name": "fastsync.valset_cut", "ts": 0.5, "dur": 0.0,
              "args": {"height": 201, "blocks": 8}}]
    ctx = {"spans": spans, "boot_spans": [], "hists": {}, "harness": {},
           "trace": None, "notes": []}
    read = {n: reducers.read_metric(s, ctx) for n, s in specs.items()}
    assert read == {"tables.build_ms": 2500.0, "tables.builds": 1.0,
                    "tables.evictions": 1.0, "reactor.valset_cuts": 1.0,
                    "kernel.tablebuild_ms": None}
    # a program without the spans (the parent) reads 0 and nothing, and
    # does not raise
    ctx["spans"] = spans[:2]
    assert [reducers.read_metric(specs[n], ctx) for n in NEW_LAYERS] == [
        0.0, 0.0, 0.0, 0.0, None]
    ctx["trace"] = {"kernels": {"jit_build_neg_comb": (3, 16.5)},
                    "reactor_windows": 11}
    assert reducers.read_metric(specs["kernel.tablebuild_ms"],
                                ctx) == 1500.0


def test_the_cell_rehearsed_comes_out_correct_by_the_same_13_checks():
    result, out = benchutil.rehearse(
        seed=2**31 + 361, trace=False, seconds=45,
        config={"app": "valset_kvstore"},
        traffic={"valset": MIX["valset"]},
        chain={"parent_blocks_per_s": 30, "warmup_s": 4},
        prelude=WARM_4, timeout=1200)
    checks = result["checks"]
    assert list(checks) == NAMES
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    assert all(c["ok"] for c in checks.values())
    assert checks["kernel_programs_in_window"] == {"value": 0, "at_most": 0,
                                                   "ok": True}
    assert checks["rpc_answers_differ"]["value"] == 0
    assert checks["control_lanes_differ"]["value"] == 0
    # at least two windows ended inside the window, one of them cut: a
    # set's 200 heights are three windows of 64 and one of 8
    line = next(ln for ln in out.splitlines()
                if ln.startswith("[bench] interval:")).split()
    windows, heights = int(line[2]), int(line[6])
    assert windows >= 2 and heights >= 72 and heights % 64, line
    held = next(ln for ln in out.splitlines() if ln.startswith(
        "[bench] validators: the builder's set")).split()
    assert int(held[5]) >= 3 and int(held[11]) > 400, held
    assert checks["sigs_verified"]["value"] >= 4 * 400
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(out))
