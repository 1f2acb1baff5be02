"""BENCHMARK.json against the contract it was written to, and against
the files it names."""

import json
import os
import re

import pytest

import benchutil
from benchutil import NAME, REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import reducers, roofline

UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(PATH.match(p) and os.path.isdir(os.path.join(REPO, p))
               for p in BENCH["paths"])
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_files_under_paths_are_named_from_allowed_characters():
    for p in BENCH["paths"]:
        for d, _dirs, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PATH.match(rel), rel


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(c):
    benchutil.config_entry_and_file_hold(REPO, BENCH, c)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_files(w):
    benchutil.workload_entry_and_files_hold(REPO, BENCH, w)


def _cell_of(config: str, traffic: str, plan=None) -> dict:
    """The parts of a cell `chain_blocks` reads, for a pair that need not
    be a workload; `plan` stands in the configuration file's override."""
    with open(os.path.join(REPO, "benchmark", "traffic",
                           traffic + ".json")) as f:
        mix = json.load(f)
    cfg = {"chain": {traffic: plan}} if plan else {}
    return {"config": cfg, "config_name": config, "traffic": mix,
            "traffic_name": traffic}


PARENT_100V = {"parent_blocks_per_s": 90, "warmup_s": 22}
PARENT_4V = {"parent_blocks_per_s": 355, "warmup_s": 15}


@pytest.mark.parametrize("config,traffic,plan,headroom,blocks", [
    # a plan that names no headroom reads as before PR 26: twice
    ("catchup-100v", "empty-blocks", PARENT_100V, 2.0, 12097),
    ("testnet-4v", "empty-blocks", PARENT_4V, 2.0, 42625),
    ("testnet-4v", "empty-blocks", dict(PARENT_4V, headroom=2.6), 2.6, 55425),
    # the traffic file's own plans, and its default
    ("catchup-100v", "empty-blocks", None, 3.0, 18113),
    ("testnet-4v", "empty-blocks", None, 2.6, 55425),
    ("some-other", "empty-blocks", None, 2.0, 12097),
    # full-blocks is as long as it was
    ("catchup-100v", "full-blocks", None, 2.0, 2241),
    ("some-other", "full-blocks", None, 2.0, 2241),
])
def test_chain_length_follows_the_plans_headroom(config, traffic, plan,
                                                 headroom, blocks):
    cell = _cell_of(config, traffic, plan)
    assert cell_mod.chain_plan(cell)["headroom"] == headroom
    assert cell_mod.chain_blocks(cell, 45) == blocks


def test_metrics_follow_the_contract():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "sync_blocks_per_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_is_a_file_with_a_known_reducer(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    spec = reducers.load_layer(REPO, m["name"])
    for k in ("unit", "better", "source", "layer", "moves"):
        assert spec[k] == m[k], (m["name"], k)
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_no_layer_file_is_left_out_of_benchmark_json():
    have = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark",
                                                    "layers"))}
    assert have == {m["name"] for m in BENCH["per_layer"]}


def test_peaks_and_the_kernels_least_time():
    peaks = roofline.load_peaks()
    assert "TPU v5 lite" in peaks
    flops, nbytes = roofline.verify_ops_bytes(8192, 64)
    assert flops == 8192 * 350 * 2048
    assert nbytes == 8192 * (48 * 96 + 73) + 64 * 128
    t, bound = roofline.least_time_s("TPU v5 lite", flops, nbytes)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    pct, _ = roofline.roofline_pct("TPU v5 lite", 8192, 64, 10 * t)
    assert pct == pytest.approx(10.0)
    with pytest.raises(KeyError):
        roofline.least_time_s("TPU v9", flops, nbytes)


def test_reducers_read_spans_and_return_nothing_when_nothing_is_there():
    spans = [{"name": "fastsync.window", "ts": 0.0, "dur": 0.2},
             {"name": "fastsync.window", "ts": 0.2, "dur": 0.4},
             {"name": "fastsync.prepare", "ts": 0.2, "dur": 0.1},
             {"name": "xla.compile", "ts": 0.0, "dur": 2.0,
              "args": {"fn": "jit(verify)", "cached": True}},
             {"name": "xla.compile", "ts": 0.0, "dur": 0.5,
              "args": {"fn": "jit(zeros)", "cached": False}}]
    ctx = {"spans": spans, "boot_spans": spans, "trace": None, "notes": [],
           "hists": {"batchplane_wait_seconds": {"fastsync": (4, 0.1)}},
           "harness": {"hbm_peak_MiB": None}}
    assert reducers.span_ms_per(ctx, ["fastsync.window"],
                                "fastsync.window") == pytest.approx(300.0)
    assert reducers.span_ms_per(ctx, ["fastsync.prepare"],
                                "fastsync.window") == pytest.approx(50.0)
    assert reducers.span_ms_per(ctx, ["x"], "fastsync.apply") is None
    assert reducers.span_hit_pct(ctx, "fastsync.prepare",
                                 "fastsync.window") == pytest.approx(50.0)
    assert reducers.span_sum_s(ctx, "xla.compile", {"cached": True},
                               "boot") == pytest.approx(2.0)
    assert reducers.hist_mean_ms(ctx, "batchplane_wait_seconds",
                                 "fastsync") == pytest.approx(25.0)
    assert reducers.hist_mean_ms(ctx, "batchplane_wait_seconds",
                                 "light") is None
    assert reducers.harness(ctx, "hbm_peak_MiB") is None
    assert reducers.trace_idle_pct(ctx) is None
    assert reducers.trace_kernel_ms_per_window(
        ctx, "verify_grouped_templated") is None
