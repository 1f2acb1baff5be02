"""The reduction from the profiler's trace to device metrics
(`benchmark/lib/devtrace.py`): on hand-made events whose answer is known,
and on a small trace recorded on the chip (`data/trace_small.json.gz`:
0.7 s of `testnet-4v.empty-blocks` on a TPU v5 lite, outermost
operations only, with the program spans of the same second)."""

import gzip
import json
import os

import pytest

import benchutil  # noqa: F401
from benchmark.lib import devtrace, reducers

DEV = "/device:TPU:0"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE


def ev(name, start, dur, line=OPS, plane=DEV):
    return (plane, line, name, start, dur)


HAND = [
    ev("jit_verify_grouped_templated(123)", 1.0, 0.30, MODS),
    ev("while.1", 1.0, 0.20), ev("fusion.2", 1.05, 0.05),   # nested
    ev("copy.3", 1.25, 0.05),
    ev("jit_verify_grouped_templated(123)", 3.0, 0.30, MODS),
    ev("while.1", 3.0, 0.20), ev("copy.3", 3.25, 0.05),
    ("/host:CPU", "python3", devtrace.ANCHOR, 0.5, 0.001),
]
SPANS = [
    {"name": "fastsync.window", "ts": 100.0, "dur": 4.0},
    {"name": "fastsync.apply", "ts": 100.8, "dur": 1.9},
    {"name": "fastsync.lookahead", "ts": 100.9, "dur": 0.2},
    {"name": "verify.collect", "ts": 103.0, "dur": 0.3},
]


def test_union_merges_overlaps():
    assert devtrace.union([(0, 1), (0.5, 2), (3, 4), (4, 5)]) == \
        [(0, 2), (3, 5)]


def test_short_name_keeps_the_operation_not_its_hlo_line():
    assert devtrace.short_name(
        "%while.811 = (s32[]{:T(128)}, s32[8192,32]) while(...)") == \
        "while.811"
    assert devtrace.short_name("jit_verify(1)") == "jit_verify(1)"


def test_hand_made_trace_reduces_to_known_numbers():
    offset = devtrace.clock_offset(HAND, anchor_epoch=100.0)
    assert offset == pytest.approx(99.5)
    r = devtrace.reduce(HAND, 0.5, 4.5, spans=SPANS, offset=offset)
    assert r["planes"] == [DEV] and r["window_s"] == pytest.approx(4.0)
    # busy: [1.0,1.2] u [1.25,1.3] and the same at 3.0 -> 0.5 s; the
    # nested fusion adds nothing
    assert r["busy_s"] == pytest.approx(0.5)
    assert r["idle_pct"] == pytest.approx(87.5)
    assert r["kernels"] == {"jit_verify_grouped_templated":
                            (2, pytest.approx(0.6))}
    assert r["device_ops"][0] == ["while.1", pytest.approx(0.4)]
    # the longest gap, 1.3 -> 3.0, has its middle at 2.15 (101.65 on the
    # recorder's clock): inside fastsync.apply, not in the look-ahead,
    # and the all-covering window span does not count
    assert r["idle_gaps"][0] == ["fastsync.apply", pytest.approx(1.7)]
    assert [g[0] for g in r["idle_gaps"]].count("no span") >= 1
    assert devtrace.kernel(r, "verify_grouped_templated") == \
        (2, pytest.approx(0.6))
    assert devtrace.kernel(r, "leaf_hashes") is None


def test_busy_is_averaged_over_the_device_planes():
    two = HAND + [ev("while.9", 1.0, 1.0, plane="/device:TPU:1")]
    r = devtrace.reduce(two, 0.5, 4.5)
    assert r["busy_s"] == pytest.approx((0.5 + 1.0) / 2)
    assert r["idle_gaps"][0][0] == "clock not tied"


def test_a_trace_with_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce([e for e in HAND if e[0] != DEV], 0.5, 4.5)


def test_trace_reducers_give_per_window_time_and_roofline_share():
    r = devtrace.reduce(HAND, 0.5, 4.5)
    r["reactor_windows"] = 2
    ctx = {"trace": r, "notes": [], "harness": {
        "device_kind": "TPU v5 lite", "bucket_lanes": 8192,
        "bucket_templates": 64}}
    assert reducers.trace_kernel_ms_per_window(
        ctx, "verify_grouped_templated") == pytest.approx(300.0)
    pct = reducers.trace_kernel_roofline_pct(ctx, "verify_grouped_templated")
    # 8192 lanes x 4,681 B + templates over 819 GB/s, against 0.3 s a call
    assert pct == pytest.approx(100 * (8192 * 4681 + 8192) / 819e9 / 0.3)
    assert ctx["notes"] == ["verify_grouped_templated_roofline bound by "
                            "memory"]
    assert reducers.trace_idle_pct(ctx) == pytest.approx(87.5)


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_small.json.gz")


def test_recorded_trace_from_the_chip_reduces_sanely():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    events = [(*rec["lines"][li], rec["names"][ni], t / 1e9, d / 1e9)
              for li, ni, t, d in rec["rows"]]
    r = devtrace.reduce(events, 0.0, 0.7, spans=rec["spans"], offset=0.0)
    assert r["planes"] == ["/device:TPU:0"]
    assert set(r["lines"]) >= {"XLA Modules", "XLA Ops"}
    calls, secs = devtrace.kernel(r, "verify_grouped_templated")
    # four 256-lane windows in 0.7 s, 7.7 ms of device time each
    assert calls == 4 and secs / calls == pytest.approx(0.0077, rel=0.05)
    # the chip is idle most of a host-bound sync, and busy no longer
    # than its programs ran
    assert 0.02 < r["busy_s"] <= secs * 1.01
    assert 90.0 < r["idle_pct"] < 100.0
    assert 1 <= len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(len(n) <= 80 and " = " not in n for n, _s in r["device_ops"])
    # the long gaps of this cell are the apply of a window
    assert r["idle_gaps"][0][0] == "fastsync.apply"
    assert 0.1 < r["idle_gaps"][0][1] < 0.25
    assert sum(g[1] for g in r["idle_gaps"]) <= 0.7 - r["busy_s"] + 1e-6
