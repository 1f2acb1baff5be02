"""Attribution profiler tests (utils/attribution.py): interval algebra,
the priority partition (components must sum to window wall clock), window
discovery from span args, overlap accounting on nested and cross-thread
span sets, the doctor report schema, and the Chrome-trace round trip."""

import json

from tendermint_tpu.utils import attribution as at
from tendermint_tpu.utils import tracing


def _span(name, ts, dur, cat=None, tid=1, **args):
    s = {"name": name, "ph": tracing.PH_SPAN, "ts": ts, "dur": dur,
         "tid": tid, "thread": f"t{tid}", "lane": f"t{tid}"}
    if cat:
        s["cat"] = cat
    if args:
        s["args"] = args
    return s


# -- interval algebra --------------------------------------------------------

def test_merge_overlapping_and_adjacent():
    assert at.merge([(0, 2), (1, 3), (3, 4), (6, 7)]) == [(0, 4), (6, 7)]
    assert at.merge([(5, 5), (2, 1)]) == []          # empty/inverted drop


def test_clip_and_total():
    ivs = at.merge([(0, 4), (6, 10)])
    assert at.clip(ivs, 2, 8) == [(2, 4), (6, 8)]
    assert at.total(at.clip(ivs, 2, 8)) == 4


def test_subtract_and_intersect():
    a = [(0, 10)]
    b = [(2, 4), (6, 8)]
    assert at.subtract(a, b) == [(0, 2), (4, 6), (8, 10)]
    assert at.intersect(a, b) == [(2, 4), (6, 8)]
    assert at.intersect(b, [(3, 7)]) == [(3, 4), (6, 7)]
    assert at.subtract(b, a) == []


def test_covered_by_at_least_two():
    lists = [[(0, 4)], [(2, 6)], [(3, 8)]]
    assert at.covered_by_at_least(lists, 2) == [(2, 6)]
    assert at.covered_by_at_least(lists, 3) == [(3, 4)]
    assert at.covered_by_at_least(lists, 1) == [(0, 8)]
    assert at.covered_by_at_least([], 2) == []


# -- partition ---------------------------------------------------------------

def test_partition_sums_to_wall_exactly():
    """Priority partition: every instant attributed once, idle is the
    remainder, so components sum to wall by construction."""
    cat_ivs = {
        tracing.CAT_COMPILE: [(1, 3)],
        tracing.CAT_DEVICE: [(2, 6)],       # 2..3 shadowed by compile
        tracing.CAT_SCALAR: [(5, 9)],       # 5..6 shadowed by device
        tracing.CAT_TRANSFER: [(0.5, 1.5)],  # 1..1.5 shadowed by compile
    }
    out = at.attribute_interval(cat_ivs, 0, 10)
    assert out["wall"] == 10
    assert out["compile"] == 2              # 1..3
    assert out["transfer"] == 0.5           # 0.5..1
    assert out["device_busy"] == 3          # 3..6
    assert out["scalar_tail"] == 3          # 6..9
    parts = (out["compile"] + out["transfer"] + out["device_busy"]
             + out["scalar_tail"] + out["device_idle"])
    assert abs(parts - out["wall"]) < 1e-9


def test_partition_priority_compile_shadows_device():
    cat_ivs = {tracing.CAT_COMPILE: [(0, 10)],
               tracing.CAT_DEVICE: [(0, 10)]}
    out = at.attribute_interval(cat_ivs, 0, 10)
    assert out["compile"] == 10
    assert out["device_busy"] == 0
    assert out["device_idle"] == 0


def test_overlap_fraction_pipelined_vs_serial():
    # serial: prep then device then apply — no two stages concurrent
    serial = {tracing.CAT_PREP: [(0, 2)], tracing.CAT_DEVICE: [(2, 4)],
              tracing.CAT_APPLY: [(4, 6)]}
    assert at.attribute_interval(serial, 0, 6)["overlap_fraction"] == 0.0
    # pipelined: prep of window N+1 under device of window N
    piped = {tracing.CAT_PREP: [(0, 2), (2, 4)],
             tracing.CAT_DEVICE: [(2, 4)], tracing.CAT_APPLY: [(4, 6)]}
    out = at.attribute_interval(piped, 0, 6)
    assert abs(out["overlap_fraction"] - 2 / 6) < 1e-9


# -- spans -> categories / windows -------------------------------------------

def test_spans_by_category_explicit_and_derived():
    spans = [
        _span("xla.compile", 0, 1),                  # derived: compile
        _span("custom.thing", 2, 1, cat="device"),   # explicit wins
        _span("scalar.verify", 4, 1),                # derived: scalar
        _span("unknown.name", 6, 1),                 # uncategorized: out
        _span("xla.compile", 10, 0),                 # zero dur: out
    ]
    ivs = at.spans_by_category(spans)
    assert ivs[tracing.CAT_COMPILE] == [(0, 1)]
    assert ivs[tracing.CAT_DEVICE] == [(2, 3)]
    assert ivs[tracing.CAT_SCALAR] == [(4, 5)]
    assert "unknown" not in "".join(ivs)


def test_find_windows_sorted_and_extended():
    spans = [
        _span("fastsync.prepare", 10, 1, window=2),
        _span("fastsync.apply", 12, 2, window=2),
        _span("fastsync.prepare", 0, 1, window=1),
        _span("fastsync.apply", 3, 1, window=1),
        _span("xla.compile", 5, 1),                  # no key: no window
    ]
    wins = at.find_windows(spans)
    assert list(wins) == [1, 2]                      # sorted by start
    assert wins[1] == (0, 4)
    assert wins[2] == (10, 14)


def test_window_attribution_cross_thread_spans():
    """Category intervals come from ALL spans: a compile span on another
    thread (no window arg) still attributes to the window it overlaps."""
    spans = [
        _span("fastsync.prepare", 0, 1, tid=1, window=0),
        _span("fastsync.apply", 8, 2, tid=1, window=0),
        _span("xla.compile", 2, 3, tid=2),           # worker thread
        _span("verify.batch", 5, 3, tid=2),
    ]
    (row,) = at.window_attribution(spans)
    assert row["window"] == 0
    assert row["wall"] == 10
    assert row["compile"] == 3
    assert row["device_busy"] == 3
    parts = (row["compile"] + row["transfer"] + row["device_busy"]
             + row["scalar_tail"] + row["device_idle"])
    assert abs(parts - row["wall"]) < 1e-9


def test_nested_spans_do_not_double_count():
    """A device span nested inside a scalar span (or overlapping same-
    category spans) must not attribute the same instant twice."""
    spans = [
        _span("fastsync.prepare", 0, 1, window=0),
        _span("scalar.verify", 1, 8, window=0),
        _span("verify.batch", 3, 2),                 # nested inside scalar
        _span("scalar.verify", 2, 4),                # overlaps first scalar
        _span("fastsync.apply", 9, 1, window=0),
    ]
    (row,) = at.window_attribution(spans)
    assert row["device_busy"] == 2                   # 3..5 wins over scalar
    assert row["scalar_tail"] == 6                   # 1..3 + 5..9
    parts = (row["compile"] + row["transfer"] + row["device_busy"]
             + row["scalar_tail"] + row["device_idle"])
    assert abs(parts - row["wall"]) < 1e-9


# -- doctor report -----------------------------------------------------------

def test_doctor_report_schema_and_thief():
    spans = [
        _span("fastsync.prepare", 0, 1, window=0),
        _span("scalar.verify", 1, 7),
        _span("fastsync.apply", 8, 2, window=0),
    ]
    rep = at.doctor_report(spans)
    assert rep["schema"] == at.DOCTOR_SCHEMA
    assert rep["window_count"] == 1
    assert rep["largest_thief"] == "scalar_tail"
    gap = rep["headline_gap"]
    assert set(gap) == {"wall", "compile", "transfer", "device_busy",
                        "scalar_tail", "device_idle"}
    parts = sum(gap[k] for k in gap if k != "wall")
    assert abs(parts - gap["wall"]) <= 0.1 * gap["wall"]
    json.dumps(rep)                                  # machine-readable


def test_doctor_report_no_windows_falls_back_to_extent():
    spans = [_span("xla.compile", 0, 2), _span("verify.batch", 2, 2)]
    rep = at.doctor_report(spans)
    assert rep["window_count"] == 0
    assert rep["headline_gap"]["wall"] == 4
    assert rep["headline_gap"]["compile"] == 2
    assert rep["largest_thief"] == "compile"


def test_doctor_report_empty_and_regressions_folded():
    rep = at.doctor_report([])
    assert rep["largest_thief"] is None
    assert rep["headline_gap"]["wall"] == 0.0
    regs = {"config0": {"rate": 10.0, "unit": "blocks_per_sec",
                        "best_prior": 20.0, "delta_frac": -0.5,
                        "regression": True}}
    rep = at.doctor_report([], regressions=regs)
    assert rep["regressions"] == regs
    text = at.render_report(rep)
    assert "REGRESSION config0" in text
    assert "-50.0%" in text


def test_render_report_names_largest_thief():
    spans = [
        _span("fastsync.prepare", 0, 1, window=0),
        _span("scalar.verify", 1, 8),
        _span("fastsync.apply", 9, 1, window=0),
    ]
    text = at.render_report(at.doctor_report(spans))
    assert text.startswith("largest thief: scalar_tail")
    assert "partition:" in text
    assert "overlap fraction" in text


def _plane_metrics(occ_mean, flushes=10, mixed=4):
    """REGISTRY.snapshot()-shaped batch-plane slice at a given mean
    flush occupancy."""
    return {
        "batchplane_flushes": flushes,
        "batchplane_mixed_batches": mixed,
        "batchplane_occupancy": {"count": flushes,
                                 "sum": occ_mean * flushes,
                                 "p50": occ_mean},
        "batchplane_flush_reason": {"deadline": 6, "full": 4},
        "batchplane_lanes": {"consensus": 64, "light": 32},
        "batchplane_wait_seconds": {},
    }


def test_doctor_half_full_batches_named_thief():
    spans = [
        _span("fastsync.prepare", 0, 0.5, window=0),
        _span("verify.batch", 0.5, 9, window=0),
        _span("fastsync.apply", 9.5, 0.5, window=0),
    ]
    rep = at.doctor_report(spans, metrics=_plane_metrics(0.25))
    plane = rep["batchplane"]
    assert plane["flushes"] == 10 and plane["mixed_batches"] == 4
    # ~9s device_busy at 25% occupancy -> ~6.75s burned verifying
    # padding lanes, larger than every partition component
    assert plane["half_full_stolen_seconds"] > 6
    assert rep["largest_thief"] == "half_full_batches"
    text = at.render_report(rep)
    assert text.startswith("largest thief: half_full_batches")
    assert "batch plane: 10 flushes (4 mixed-producer)" in text
    json.dumps(rep)


def test_doctor_full_batches_do_not_steal():
    spans = [_span("verify.batch", 0, 9, window=0),
             _span("scalar.verify", 9, 1, window=0)]
    rep = at.doctor_report(spans, metrics=_plane_metrics(1.0))
    assert rep["batchplane"]["half_full_stolen_seconds"] == 0
    assert rep["largest_thief"] != "half_full_batches"


def test_doctor_quiet_plane_reports_no_section():
    rep = at.doctor_report([], metrics={"batchplane_flushes": 0})
    assert "batchplane" not in rep
    assert at.batchplane_summary({}) is None


# -- chrome round trip -------------------------------------------------------

def test_spans_from_chrome_round_trip():
    rec = tracing.FlightRecorder(capacity=16)
    rec.record("scalar.verify", ts_s=100.0, dur_s=2.0,
               args={"window": 3})
    rec.record("xla.compile", ts_s=101.0, dur_s=0.5)
    rec.instant("pool.evict")
    spans = at.spans_from_chrome(rec.to_chrome_trace())
    names = [s["name"] for s in spans]
    assert "scalar.verify" in names and "xla.compile" in names
    assert "thread_name" not in names                # metadata skipped
    sv = next(s for s in spans if s["name"] == "scalar.verify")
    assert abs(sv["ts"] - 100.0) < 1e-6
    assert abs(sv["dur"] - 2.0) < 1e-6
    assert sv["cat"] == tracing.CAT_SCALAR
    assert sv["args"] == {"window": 3}
    # a report computed from the round-tripped spans matches one from
    # the original snapshot
    direct = at.doctor_report(rec.snapshot())
    via_chrome = at.doctor_report(spans)
    assert direct["headline_gap"] == via_chrome["headline_gap"]


def test_observe_window_metrics_feeds_registry():
    from tendermint_tpu.utils.metrics import REGISTRY
    before = REGISTRY.window_scalar_seconds.snapshot()["count"]
    at.observe_window_metrics({"wall": 2.0, "overlap_fraction": 0.5,
                               "device_busy": 1.0, "device_idle": 0.5,
                               "scalar_tail": 0.5})
    after = REGISTRY.window_scalar_seconds.snapshot()["count"]
    assert after == before + 1
    at.observe_window_metrics({"wall": 0.0})         # no-op, no crash


def test_reactor_observes_a_window_from_its_own_records_only(monkeypatch,
                                                             tmp_path):
    """The reactor's four window histograms are observed once a window
    from `RECORDER.since(window start)`: never a snapshot() of the ring,
    and a read as long as the window's own records whatever the ring
    holds (here 50,000 older records: the ring full and wrapped)."""
    from chainutil import fast_sync_in_process
    from tendermint_tpu.utils.metrics import REGISTRY
    rec = tracing.RECORDER
    old = tracing.now_epoch() - 3600.0
    for i in range(50_000):
        rec.record("unrelated", old + i * 1e-3, 5e-4, None,
                   cat=tracing.CAT_DEVICE)
    assert len(rec.since(0.0)) == min(50_000, rec.capacity)

    def no_snapshot():
        raise AssertionError("the sync loop read the whole ring")
    reads = []
    real_since = rec.since

    def counted_since(ts, categorized=False):
        out = real_since(ts, categorized)
        reads.append(len(out))
        return out
    monkeypatch.setattr(rec, "snapshot", no_snapshot)
    monkeypatch.setattr(rec, "since", counted_since)
    hists = (REGISTRY.window_overlap_frac_hist,
             REGISTRY.window_device_busy_frac_hist,
             REGISTRY.window_device_idle_frac_hist,
             REGISTRY.window_scalar_seconds)
    before = [h.snapshot()["count"] for h in hists]
    t_start = tracing.now_epoch()
    fast_sync_in_process("window-hist-chain", 40, 8, sqlite_dir=str(tmp_path))
    windows = [s for s in real_since(t_start)
               if s["name"] == "fastsync.window"]
    assert len(windows) >= 4
    assert [h.snapshot()["count"] - b for h, b in zip(hists, before)] == \
        [len(windows)] * 4
    assert len(reads) == len(windows)
    # a window's categorized records: its phases and the look-ahead's
    # beside it, not the 8 x 13 per-block records, not the ring's 16,384
    assert 3 <= max(reads) < 8 * 13 < rec.capacity
