"""The spans inside a fast-sync window, as the reactor records them on a
short chain synced in process (CPU, python backend, sqlite stores), and
every per-layer metric file that reads them (`benchmark/layers/`), read
with the benchmark's own reducers."""

import os
import sys

import pytest

from chainutil import fast_sync_in_process
from tendermint_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

APPLY_STAGES = ["store_save", "validate", "abci_exec", "save_responses",
                "update_state", "abci_commit", "state_save", "advance"]
SPAN_MS_METRICS = (
    [f"apply.{st}_ms" for st in APPLY_STAGES] +
    ["stores.write_ms", "reactor.decode_ms", "reactor.encode_ms",
     "reactor.parts_ms", "reactor.block_ids_ms", "reactor.commit_lanes_ms",
     "reactor.commit_tally_ms"])
COUNT_METRICS = ["pool.rerequests", "pool.late_blocks"]
N_BLOCKS, BATCH = 40, 8
CLOCK = 2e-6       # an epoch timestamp holds a quarter of a microsecond


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    t_start = tracing.now_epoch()
    fast_sync_in_process("stage-spans-chain", N_BLOCKS, BATCH,
                         sqlite_dir=str(tmp_path_factory.mktemp("sync")))
    return [s for s in tracing.RECORDER.since(t_start) if s["ts"] >= t_start]


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] - CLOCK <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + CLOCK)


def test_stage_records_nest_in_time_under_the_apply_span(spans):
    applies = _named(spans, "fastsync.apply")
    assert applies and sum(a["args"]["blocks"] for a in applies) >= \
        N_BLOCKS - 1
    for a in applies:
        mine = [s for s in spans if s["name"].startswith("fastsync.apply.")
                and s["tid"] == a["tid"] and _inside(s, a)]
        # every stage once a block, in order, and nothing but stages
        assert [s["name"].rsplit(".", 1)[1] for s in mine] == \
            APPLY_STAGES * a["args"]["blocks"]
        # no step of a block is outside a stage
        assert sum(s["dur"] for s in mine) <= a["dur"] + CLOCK
        assert mine[-1]["ts"] + mine[-1]["dur"] - mine[0]["ts"] == \
            pytest.approx(sum(s["dur"] for s in mine), abs=CLOCK * len(mine))
    # and no stage record is outside an apply span
    n_stage = sum(s["name"].startswith("fastsync.apply.") for s in spans)
    assert n_stage == 8 * sum(a["args"]["blocks"] for a in applies)


def test_apply_and_lookahead_leave_their_threads_cpu_in_the_ledger(spans):
    applies = _named(spans, "fastsync.apply")
    waits = _named(spans, "offcpu.apply")
    assert applies and len(waits) == len(applies)
    for a, q in zip(applies, waits):
        # what apply was off the CPU for ends with its span (the record
        # is written inside it) and cannot pass its wall
        assert q["ph"] == tracing.PH_COUNTER and q["tid"] == a["tid"]
        assert a["ts"] <= q["ts"] + q["dur"] <= a["ts"] + a["dur"] + CLOCK
        assert 0.0 <= q["dur"] <= a["dur"], (q, a)
    # the look-aheads' own CPU, as each said it at its exit, cannot pass
    # their wall (clock grain aside); the first window's reading is the
    # baseline, so what ended before it is in no record
    aheads = _named(spans, "fastsync.lookahead")
    cpu = _named(spans, "cpu.lookahead")
    assert aheads and cpu
    assert len(cpu) == len(_named(spans, "fastsync.window")) - 1
    assert 0.0 < sum(q["dur"] for q in cpu) <= \
        sum(s["dur"] + 0.02 for s in aheads)
    assert not any("args" in s for s in applies + aheads
                   if "cpu_s" in s.get("args", {}))


def test_prepare_and_commit_phases_nest_under_their_window_phase(spans):
    outers = [s for s in spans if s["name"] in (
        "fastsync.lookahead", "fastsync.prepare", "fastsync.verify")]
    for name in ("fastsync.prepare.encode", "fastsync.prepare.parts",
                 "fastsync.prepare.block_ids", "fastsync.commit.lanes",
                 "fastsync.commit.tally"):
        found = _named(spans, name)
        assert found, name
        for s in found:
            assert s["cat"] == tracing.CAT_PREP
            assert any(o["tid"] == s["tid"] and _inside(s, o)
                       for o in outers), s
    # one decode a delivered block, in the p2p thread, uncategorized
    decodes = _named(spans, "fastsync.decode")
    assert len(decodes) >= N_BLOCKS - 1
    assert all("cat" not in d and "args" not in d for d in decodes)
    assert not {d["tid"] for d in decodes} & \
        {a["tid"] for a in _named(spans, "fastsync.apply")}


def _read(spans, name):
    """(layer file, its value over `spans`) by the benchmark's reducers."""
    from benchmark.lib import reducers
    spec = reducers.load_layer(REPO, name)
    ctx = {"spans": spans, "boot_spans": [], "hists": {}, "harness": {},
           "trace": None, "notes": []}
    return spec, reducers.read_metric(spec, ctx)


@pytest.mark.parametrize("name", SPAN_MS_METRICS + COUNT_METRICS)
def test_every_new_layer_file_reads_the_recorded_spans(spans, name):
    spec, value = _read(spans, name)
    if name in COUNT_METRICS:
        assert spec["reducer"] == "span_count" and value == 0.0
    else:
        assert spec["reducer"] == "span_ms_per" and value > 0.0
        assert spec["args"]["per"] in ("fastsync.apply", "fastsync.window")


def test_the_eight_stages_are_the_apply_span(spans):
    stages = sum(_read(spans, f"apply.{st}_ms")[1] for st in APPLY_STAGES)
    whole = _read(spans, "apply.window_ms")[1]
    # on the chip they agree within 2 % (PERF.md); here a window is a
    # few ms on a shared CPU, so only: nothing is counted twice, and
    # the stages are most of the span
    assert 0.5 * whole < stages <= whole * (1 + 1e-6)
    assert _read(spans, "stores.write_ms")[1] < stages
