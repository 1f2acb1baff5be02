"""The peer link's two per-layer metrics (PR 42): how many complete
messages each receive loop assembled in the interval.  Each is a layer
file, equal to its `per_layer` entry, a `span_count` over the one record
its loop writes a message (`MConnection._recv_routine_native` /
`_recv_routine`), and 0.0, a number, on a ring that holds neither: the
parent's, which runs the Python loop and writes no record."""

import json
import os

import pytest

from benchutil import REPO
from benchmark.lib import accounting, reducers

RECORD = {"p2p.native_msgs": "link.recv.native",
          "p2p.python_msgs": "link.recv.python"}
BETTER = {"p2p.native_msgs": "higher", "p2p.python_msgs": "lower"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


def _span(name, ts, dur=0.0, ph="X", **args):
    return {"name": name, "ph": ph, "ts": ts, "dur": dur, "args": args}


# three reactor windows end at 100.0, 100.5, 100.9: the interval is
# (100.0, 100.9].  Messages: one before it, five native and two Python
# inside it, one after it
WINDOWS = [100.0, 100.5, 100.9]
NATIVE_AT = [99.99, 100.01, 100.2, 100.49, 100.5, 100.9, 100.95]
PYTHON_AT = [100.3, 100.7, 101.0]


def _ctx(spans):
    t_first, t_last, windows = accounting.measured_interval(
        spans, WINDOWS[0] - 1.0, WINDOWS[-1] + 1.0)
    assert (t_first, t_last, len(windows)) == (WINDOWS[0], WINDOWS[-1], 2)
    return {"spans": accounting.in_interval(spans, t_first, t_last),
            "boot_spans": [], "hists": {}, "harness": {}, "trace": None,
            "notes": []}


def _windows():
    return [_span("fastsync.window", hi - 0.4, 0.4) for hi in WINDOWS]


@pytest.mark.parametrize("name", list(RECORD))
def test_the_layer_file_is_its_per_layer_entry(name):
    spec = reducers.load_layer(REPO, name)
    entry = PER_LAYER[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves"}
    assert {k: spec[k] for k in entry} == entry
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("p2p switch", "count", BETTER[name],
                                 "sync_blocks_per_s", "program_span")
    assert (spec["reducer"], spec["args"]) == ("span_count",
                                               {"span": RECORD[name]})


@pytest.mark.parametrize("name,by_hand", [("p2p.native_msgs", 5.0),
                                          ("p2p.python_msgs", 2.0)])
def test_a_loops_messages_in_the_interval_are_counted(name, by_hand):
    spans = _windows() + \
        [_span("link.recv.native", t, ph="i", ch=0x40, bytes=18_900)
         for t in NATIVE_AT] + \
        [_span("link.recv.python", t, ph="i", ch=0x40, bytes=18_900)
         for t in PYTHON_AT] + \
        [_span("fastsync.decode", t, 0.001) for t in NATIVE_AT]
    got = reducers.read_metric(reducers.load_layer(REPO, name), _ctx(spans))
    assert got == by_hand and isinstance(got, float)


@pytest.mark.parametrize("name", list(RECORD))
def test_on_a_ring_with_neither_record_both_read_zero(name):
    """A number and not nothing: an accepted test
    (`test_bench_full_blocks.py`) holds every metric of a cell to a
    number wherever two windows completed, on the parent too."""
    spans = _windows() + [_span("fastsync.decode", t, 0.001)
                          for t in NATIVE_AT]
    got = reducers.read_metric(reducers.load_layer(REPO, name), _ctx(spans))
    assert got == 0.0 and isinstance(got, float)

