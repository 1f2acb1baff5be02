"""The cell `catchup-300v.empty-blocks` as files: a configuration that is
`catchup-100v`'s but for the set's size and what follows from it, a chain
plan of its own in the configuration file, and one per-layer metric as a
data file (`tables.boot_build_s`).  No rehearsal of `run_cell` here: a
window of any set wider than 16 is 2,048 lanes and more, which the CPU
backend's grouped convolution takes minutes over; the run at 4
validators in `test_bench_rehearsal.py` reads the new metric, since it
has no `workloads` list, and the program's side of a wide set is
`tests/test_wide_set_host.py` and `tests/test_wide_set_device.py`."""

import json
import os
import types

import pytest

from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import reducers, roofline

CELL = "catchup-300v.empty-blocks"
# what differs from catchup-100v's file, by key; every other key is its
OWN_KEYS = {"name", "source", "deployment", "validators", "on_device",
            "assumed", "reduced_to", "chain"}


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


PLAIN, WIDE = _config("catchup-100v"), _config("catchup-300v")


@pytest.mark.parametrize("key", sorted(set(PLAIN) | set(WIDE)))
def test_the_configuration_is_catchup_100vs_key_for_key(key):
    if key in OWN_KEYS:
        assert key in WIDE and WIDE[key] != PLAIN.get(key), key
    else:
        assert WIDE[key] == PLAIN[key], key


def test_the_set_is_not_cut_and_only_the_depth_is():
    assert WIDE["validators"] == 300 and WIDE["chips"] == 1
    assert WIDE["app"] == "kvstore" and WIDE["source_peers"] == 16
    assert WIDE["reduced"] == ["upstream_chain_blocks"]
    assert WIDE["upstream_chain_blocks"] == 100000
    assert WIDE["guarantees"] == PLAIN["guarantees"]
    assert set(WIDE["assumed"]) >= set(PLAIN["assumed"]) | {"validators"}
    assert "V bucket 512" in WIDE["on_device"]
    assert "1,308,622,848 B" in WIDE["on_device"]


def test_the_shapes_stated_are_the_programs_defaults():
    from tendermint_tpu.config import Config
    cell_mod.stated_as_run(WIDE, Config())
    other = types.SimpleNamespace(base=types.SimpleNamespace(
        proxy_app="counter"))
    with pytest.raises(RuntimeError, match="'app'"):
        cell_mod.stated_as_run(WIDE, other)


def test_the_cell_loads_with_the_plain_cells_traffic_and_metrics():
    cell = cell_mod.load_cell(REPO, CELL)
    plain = cell_mod.load_cell(REPO, "catchup-100v.empty-blocks")
    assert cell["chips"] == 1 and cell["traffic"] == plain["traffic"]
    assert "valset" not in cell["traffic"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "sync_blocks_per_s", "boot_to_first_window_s", "setup_s"}
    # every per-layer metric without a `workloads` list, and none of the
    # listed ones: the cell adds itself to no list
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in cell["per_layer"]] == [
        m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert not any(CELL in m.get("workloads", ())
                   for m in bench["per_layer"] + bench["end_to_end"])
    assert "tables.boot_build_s" in {m["name"] for m in cell["per_layer"]}


@pytest.mark.parametrize("seconds", [5, 45, 51])
def test_the_chain_is_whole_windows_plus_one_from_the_files_own_plan(
        seconds):
    cell = cell_mod.load_cell(REPO, CELL)
    plan = cell_mod.chain_plan(cell)
    # the configuration file's plan, not the traffic file's default
    assert plan == WIDE["chain"]["empty-blocks"]
    assert plan["headroom"] == 3.0 and "note" in plan
    assert plan != cell["traffic"]["chain"]["default"]
    n = cell_mod.chain_blocks(cell, seconds)
    assert n % 64 == 1
    want = plan["headroom"] * plan["parent_blocks_per_s"] * (
        plan["warmup_s"] + seconds)
    assert want <= n - 1 < want + 64
    # 56 KB a block: the chain fits the source child (under 1.5 GB)
    assert n * 188 * WIDE["validators"] < 1.5e9


def test_the_roofline_counts_the_window_s_own_bucket():
    """The harness derives the verify program's shape from the set's size
    (`cell.run_cell`: `bucket_lanes`), so the accepted roofline share
    counts the (32,768, 64) call: four times cell 1's bytes."""
    from tendermint_tpu.crypto import backend as cb
    lanes = cb._bucket(cell_mod.WINDOW_BLOCKS * WIDE["validators"])
    assert lanes == 32768 and cb._bucket(cell_mod.WINDOW_BLOCKS) == 64
    flops, nbytes = roofline.verify_ops_bytes(lanes, 64)
    f1, b1 = roofline.verify_ops_bytes(8192, 64)
    assert flops == 4 * f1 and nbytes - 64 * 128 == 4 * (b1 - 64 * 128)
    _t, bound = roofline.least_time_s("TPU v5 lite", flops, nbytes)
    assert bound == "memory"


def test_the_boot_build_metric_reads_the_boot_s_table_builds_only():
    spec = reducers.load_layer(REPO, "tables.boot_build_s")
    assert spec["moves"] == "boot_to_first_window_s"
    assert spec["reducer"] == "span_sum_s" and "workloads" not in spec
    build = {"name": "tables.build", "ts": 3.0, "dur": 22.0,
             "args": {"v": 300, "bytes": 1308622848}}
    later = dict(build, ts=90.0, dur=5.5)
    load = {"name": "tables.load", "ts": 1.0, "dur": 0.4,
            "args": {"v": 300, "bytes": 1308622848}}
    # the build program's own trace and load, which the program records
    # apart since PR 39: not the build
    program = {"name": "tables.build.load", "ts": 1.4, "dur": 1.6,
               "args": {"v": 300}}
    ctx = {"spans": [later], "boot_spans": [load, program, build],
           "hists": {}, "harness": {}, "trace": None, "notes": []}
    assert reducers.read_metric(spec, ctx) == 22.0
    ctx["boot_spans"] = [load, build, dict(build, ts=30.0, dur=5.0)]
    assert reducers.read_metric(spec, ctx) == 27.0
    # a program without the span (before PR 36), or a boot that loaded
    # its table from disk: 0.0, never nothing, and no raise
    ctx["boot_spans"] = [load]
    assert reducers.read_metric(spec, ctx) == 0.0
