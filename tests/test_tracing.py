"""Flight recorder tests: ring semantics, span/instant recording, and
the Chrome trace-event export schema (utils/tracing.py)."""

import json
import os
import threading

import pytest

from tendermint_tpu.utils.tracing import (CAT_APPLY, CAT_NONE, PH_INSTANT,
                                          PH_SPAN, FlightRecorder)


def test_ring_overflow_keeps_newest_in_order():
    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.record(f"ev{i}", ts_s=float(i), dur_s=0.1)
    snap = rec.snapshot()
    assert [s["name"] for s in snap] == ["ev2", "ev3", "ev4", "ev5"]
    assert rec.total == 6
    assert rec.dropped == 2


def test_snapshot_before_wrap_is_oldest_first():
    rec = FlightRecorder(capacity=8)
    for i in range(3):
        rec.record(f"ev{i}", ts_s=float(i), dur_s=0.0)
    assert [s["name"] for s in rec.snapshot()] == ["ev0", "ev1", "ev2"]
    assert rec.dropped == 0


def test_span_records_duration_and_args():
    rec = FlightRecorder(capacity=8)
    with rec.span("work", height=7):
        pass
    (s,) = rec.snapshot()
    assert s["name"] == "work"
    assert s["ph"] == PH_SPAN
    assert s["dur"] >= 0.0
    assert s["args"] == {"height": 7}


def test_span_recorded_on_exception_with_error_arg():
    rec = FlightRecorder(capacity=8)
    with pytest.raises(ValueError):
        with rec.span("boom", height=1):
            raise ValueError("x")
    (s,) = rec.snapshot()
    assert s["args"] == {"height": 1, "error": "ValueError"}


def test_span_yields_its_args_for_what_is_known_only_at_the_end():
    rec = FlightRecorder(capacity=8)
    with rec.span("fastsync.apply", blocks=64) as args:
        args["cpu_s"] = 0.25
    with pytest.raises(ValueError):
        with rec.span("fastsync.apply") as args:
            args["cpu_s"] = 0.5
            raise ValueError("x")
    a, b = rec.snapshot()
    assert a["args"] == {"blocks": 64, "cpu_s": 0.25}
    assert b["args"] == {"cpu_s": 0.5, "error": "ValueError"}


@pytest.mark.parametrize("n_records", [2, 4, 6, 20],
                         ids=["before-wrap", "full", "wrapped", "wrapped-5x"])
@pytest.mark.parametrize("since_ts", [-1.0, 0.0, 2.05, 3.85, 18.95, 100.0])
def test_since_equals_the_filtered_snapshot(n_records, since_ts):
    """since(ts) is what snapshot() holds of the records that ended at
    or after ts, oldest first, before and after the ring wraps; a span
    that began before ts and ended after it is in, and does not end the
    walk."""
    rec = FlightRecorder(capacity=8)
    for i in range(n_records):
        # recorded in order of ends, as a running program records:
        # every third is a long span that started well before the rest
        dur = 2.5 if i % 3 == 0 else 0.1
        rec.record(f"ev{i}", ts_s=float(i) + 0.9 - dur, dur_s=dur,
                   args={"i": i} if i % 2 else None,
                   cat=CAT_APPLY if i % 4 else None)
        rec.record(f"bookkeeping{i}", float(i) + 0.85, 0.1, cat=CAT_NONE)
    want = [s for s in rec.snapshot() if s["ts"] + s["dur"] >= since_ts]
    assert rec.since(since_ts) == want
    # the attribution's read: the same walk, categorized records only
    assert rec.since(since_ts, categorized=True) == \
        [s for s in want if "cat" in s]
    if since_ts <= 0.0:
        assert len(want) == min(2 * n_records, 8)  # the whole ring, once
    assert rec.since(float(n_records) + 10.0) == []


def test_since_on_an_empty_and_a_cleared_ring():
    rec = FlightRecorder(capacity=4)
    assert rec.since(0.0) == []
    rec.record("a", 1.0, 0.5)
    rec.clear()
    assert rec.since(0.0) == []


def test_instants_and_spans_share_the_ring_in_order():
    rec = FlightRecorder(capacity=8)
    rec.instant("tick", n=1)
    with rec.span("fixture"):
        pass
    rec.instant("tick", n=2)
    snap = rec.snapshot()
    assert [(s["name"], s.get("args")) for s in snap] == [
        ("tick", {"n": 1}), ("fixture", None), ("tick", {"n": 2})]
    assert [s["ph"] == PH_INSTANT for s in snap] == [True, False, True]


def test_clear_resets_ring():
    rec = FlightRecorder(capacity=4)
    for i in range(9):
        rec.record(f"ev{i}", ts_s=0.0, dur_s=0.0)
    rec.clear()
    assert rec.snapshot() == []
    assert rec.total == 0 and rec.dropped == 0


def test_concurrent_records_all_counted():
    rec = FlightRecorder(capacity=4096)

    def worker(k):
        for i in range(200):
            rec.record(f"t{k}", ts_s=0.0, dur_s=0.0)

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert rec.total == 800
    assert len(rec.snapshot()) == 800


def test_chrome_trace_schema():
    """The export must be loadable by Perfetto/chrome://tracing: X events
    carry microsecond ts+dur, instants carry a scope, and every thread
    gets an M thread_name metadata event."""
    rec = FlightRecorder(capacity=16)
    with rec.span("verify.dispatch", lanes=64):
        pass
    rec.instant("pool.evict", peer="ab")
    doc = rec.to_chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["recorder_total"] == 2
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ins = [e for e in evs if e["ph"] == "i"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(xs) == 1 and len(ins) == 1 and len(metas) >= 1
    x = xs[0]
    assert x["name"] == "verify.dispatch"
    assert {"pid", "tid", "ts", "dur"} <= set(x)
    # ts is microseconds of a wall-clock anchor: must be a huge number,
    # not raw seconds
    assert x["ts"] > 1e12
    assert x["args"] == {"lanes": 64}
    assert ins[0]["s"] == "t"
    assert metas[0]["name"] == "thread_name"
    assert metas[0]["args"]["name"]
    json.dumps(doc)                       # serializable end to end


def test_dump_atomic_write(tmp_path):
    rec = FlightRecorder(capacity=8)
    with rec.span("a"):
        pass
    path = os.path.join(str(tmp_path), "sub", "trace.json")
    assert rec.dump(path) == path
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    assert not os.path.exists(path + ".tmp")


def test_capacity_validation():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_category_inference_longest_prefix():
    from tendermint_tpu.utils import tracing
    assert tracing.default_category("xla.compile") == tracing.CAT_COMPILE
    assert tracing.default_category("transfer.h2d") == tracing.CAT_TRANSFER
    assert tracing.default_category("scalar.verify") == tracing.CAT_SCALAR
    assert tracing.default_category("verify.batch") == tracing.CAT_DEVICE
    assert tracing.default_category("verify.dispatch") == \
        tracing.CAT_DISPATCH
    assert tracing.default_category("fastsync.prepare") == tracing.CAT_PREP
    assert tracing.default_category("fastsync.apply") == tracing.CAT_APPLY
    # window-boundary and unknown names stay uncategorized
    assert tracing.default_category("fastsync.window") is None
    assert tracing.default_category("wal.write") is None


def test_span_cat_and_lane_in_snapshot():
    """cat/lane are reserved span() keywords: they land as top-level
    snapshot fields, never in args (the args contract above must hold)."""
    rec = FlightRecorder(capacity=8)
    with rec.span("verify.batch", lanes=4):
        pass
    with rec.span("custom.op", cat="scalar", lane="worker-3", n=1):
        pass
    a, b = rec.snapshot()
    assert a["cat"] == "device"               # derived from name
    assert a["lane"]                          # defaults to thread name
    assert a["args"] == {"lanes": 4}
    assert b["cat"] == "scalar"               # explicit override
    assert b["lane"] == "worker-3"
    assert b["args"] == {"n": 1}


def test_chrome_trace_carries_cat():
    rec = FlightRecorder(capacity=8)
    with rec.span("xla.compile", entry="verify_batch"):
        pass
    with rec.span("uncategorized.op"):
        pass
    evs = rec.to_chrome_trace()["traceEvents"]
    x = next(e for e in evs if e.get("name") == "xla.compile")
    assert x["cat"] == "compile"
    u = next(e for e in evs if e.get("name") == "uncategorized.op")
    assert "cat" not in u


def test_perf_to_epoch_aligns_with_span_clock():
    import time
    from tendermint_tpu.utils import tracing
    p = time.perf_counter()
    w = time.time()
    assert abs(tracing.perf_to_epoch(p) - w) < 1.0


def test_grown_timeout_zero_base_no_crash():
    """Regression: `_grown` divided timeout_max by the base timeout; a
    config with base 0 (skip a step instantly) crashed with
    ZeroDivisionError the moment growth was enabled."""
    from tendermint_tpu.config import ConsensusConfig
    c = ConsensusConfig()
    c.timeout_round_growth, c.timeout_max = 1.5, 8.0
    c.timeout_propose, c.timeout_propose_delta = 0.0, 0.2
    t = c.propose_timeout(10)
    assert 0.0 < t <= c.timeout_max
