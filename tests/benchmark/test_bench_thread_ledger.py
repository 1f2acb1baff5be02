"""The thread ledger's ten per-layer metrics (PR 41), each read from its
layer file by the reducer it names, on a span list made by hand: two
reactor windows in the interval, records shaped as the program's
(`utils/threadledger.py`): a quantity record holds seconds in its `dur`
and ends 1 us before the span it belongs to."""

import json
import os

import pytest

from benchutil import REPO
from benchmark.lib import accounting, reducers

US = 1e-6


def _quantity(name, value_s, end):
    return {"name": name, "ph": "C", "ts": end - US - value_s,
            "dur": value_s}


def _span(name, ts, dur):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur}


# window k ends at W[k]; its apply ends 2 ms before it.  Window 0 is the
# one whose completion opens the interval: nothing of it is kept
W = [100.0, 100.5, 100.9]
CPU = {                      # seconds a role used in windows 0, 1, 2
    "apply": (9.0, 0.125, 0.175), "lookahead": (9.0, 0.25, 0.15),
    "recv": (9.0, 0.75, 0.25), "send": (9.0, 0.003, 0.001),
    "other": (9.0, 0.02, 0.04), "process": (9.0, 1.5, 0.9)}
OFF_APPLY = (9.0, 0.3, 0.2)
OFF_WRITE = (9.0, 0.25, 0.15)
LAGS = [(99.95, 0.04), (100.05, 0.001), (100.10, 0.002), (100.45, 0.012),
        (100.85, 0.005), (100.899, 0.004)]       # the last ends after W[2]


def _records():
    spans = []
    for k, hi in enumerate(W):
        a_end = hi - 0.002
        spans.append(_span("fastsync.apply", a_end - 0.35, 0.35))
        spans.append(_quantity("offcpu.apply", OFF_APPLY[k], a_end))
        spans.append(_quantity("offcpu.db_write", OFF_WRITE[k], a_end))
        spans.append(_span("fastsync.window", hi - 0.4, 0.4))
        for role, used in CPU.items():
            spans.append(_quantity("cpu." + role, used[k], hi))
    spans += [_span("gil.lag", ts, dur) for ts, dur in LAGS]
    return spans


@pytest.fixture(scope="module")
def ctx():
    spans = _records()
    t_first, t_last, windows = accounting.measured_interval(
        spans, W[0] - 1.0, W[2] + 1.0)
    assert (t_first, t_last, len(windows)) == (W[0], W[2], 2)
    return {"spans": accounting.in_interval(spans, t_first, t_last),
            "boot_spans": [], "hists": {}, "harness": {}, "trace": None,
            "notes": []}


@pytest.mark.parametrize("name,by_hand", [
    # a role's CPU of windows 1 and 2 over two windows; a recv role of
    # 16 threads uses more than a window is long, and is kept whole
    ("threads.apply_cpu_ms", 1e3 * (0.125 + 0.175) / 2),
    ("threads.lookahead_cpu_ms", 1e3 * (0.25 + 0.15) / 2),
    ("threads.recv_cpu_ms", 1e3 * (0.75 + 0.25) / 2),
    ("threads.send_cpu_ms", 1e3 * (0.003 + 0.001) / 2),
    ("threads.other_cpu_ms", 1e3 * (0.02 + 0.04) / 2),
    ("threads.process_cpu_ms", 1e3 * (1.5 + 0.9) / 2),
    # per apply, of which two ended in the interval
    ("apply.offcpu_ms", 1e3 * (0.3 + 0.2) / 2),
    ("stores.write_offcpu_ms", 1e3 * (0.25 + 0.15) / 2),
    # the four wakes that ended inside (W[0], W[2]]: the first ended
    # before the interval began, the last after it ended; their lag
    # summed, over the two windows
    ("gil.lag_ms", 1e3 * (0.001 + 0.002 + 0.012 + 0.005) / 2),
    ("gil.lag_samples", 4.0),
])
def test_a_ledger_metric_reads_its_records_as_its_layer_file_says(
        ctx, name, by_hand):
    spec = reducers.load_layer(REPO, name)
    assert reducers.read_metric(spec, ctx) == pytest.approx(by_hand,
                                                            rel=1e-9)


def test_every_ledger_record_is_read_by_a_layer_file():
    """No record that nothing reads: sqlite on the CPU is
    `stores.write_ms` less `stores.write_offcpu_ms`, and the lag a wake
    is `gil.lag_ms` over `gil.lag_samples` a window."""
    layers = os.path.join(REPO, "benchmark", "layers")
    read = set()
    for f in os.listdir(layers):
        with open(os.path.join(layers, f)) as fh:
            args = json.load(fh).get("args", {})
        read.update(args.get("total", []), [args.get("span")])
    written = {"offcpu.db_write", "offcpu.apply", "gil.lag"} | \
        {"cpu." + r for r in CPU}
    assert written <= read
    assert {n for n in read if n and n.startswith(
        ("cpu.", "offcpu.", "oncpu.", "gil."))} == written


def test_on_a_program_without_the_ledger_the_readers_find_nothing(ctx):
    """The parent's records: the same windows and applies, no quantity
    and no probe.  A `span_ms_per` over the windows reads 0.0 (an
    accepted test, `test_bench_full_blocks.py`, holds every metric of a
    cell to a number wherever two windows completed, so the lag is
    read per window and not per wake), the count 0: no reader
    raises."""
    bare = dict(ctx, spans=[s for s in ctx["spans"]
                            if s["name"].startswith("fastsync.")])
    got = {n: reducers.read_metric(reducers.load_layer(REPO, n), bare)
           for n in ("threads.recv_cpu_ms", "apply.offcpu_ms",
                     "stores.write_offcpu_ms", "gil.lag_ms",
                     "gil.lag_samples")}
    assert got == {"threads.recv_cpu_ms": 0.0, "apply.offcpu_ms": 0.0,
                   "stores.write_offcpu_ms": 0.0, "gil.lag_ms": 0.0,
                   "gil.lag_samples": 0.0}
