"""`reactor.commit_wire_absent` (owed since PR 46): how many commits the
program left in their served wire bytes WITH nil entries in the interval.
A layer file, equal to its `per_layer` entry, a `span_count` over the one
instant `Commit.decode` writes for such a commit (`commit.wire_absent`),
and 0.0, a number, on a ring that holds none: a chain of full commits, or
a program from before PR 46."""

import json
import os

from benchutil import REPO
from benchmark.lib import accounting, reducers

NAME, RECORD = "reactor.commit_wire_absent", "commit.wire_absent"

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


def _span(name, ts, dur=0.0, ph="X", **args):
    return {"name": name, "ph": ph, "ts": ts, "dur": dur, "args": args}


# three reactor windows end at 100.0, 100.5, 100.9: the interval is
# (100.0, 100.9].  Commits decoded: one before it, four with nil entries
# inside it (and two that went vote by vote), one after it
WINDOWS = [100.0, 100.5, 100.9]
WIRE_AT = [99.99, 100.01, 100.5, 100.77, 100.9, 100.95]
VOTES_AT = [100.3, 100.6]


def _ctx(spans):
    t_first, t_last, windows = accounting.measured_interval(
        spans, WINDOWS[0] - 1.0, WINDOWS[-1] + 1.0)
    assert (t_first, t_last, len(windows)) == (WINDOWS[0], WINDOWS[-1], 2)
    return {"spans": accounting.in_interval(spans, t_first, t_last),
            "boot_spans": [], "hists": {}, "harness": {}, "trace": None,
            "notes": []}


def _windows():
    return [_span("fastsync.window", hi - 0.4, 0.4) for hi in WINDOWS]


def test_the_layer_file_is_its_per_layer_entry():
    spec = reducers.load_layer(REPO, NAME)
    entry = PER_LAYER[NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves"}
    assert {k: spec[k] for k in entry} == entry
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == ("reactor", "count", "higher",
                                 "sync_blocks_per_s", "program_span")
    assert (spec["reducer"], spec["args"]) == ("span_count",
                                               {"span": RECORD})
    # the layer's name is the one the accepted `reactor` metrics give
    assert entry["layer"] == PER_LAYER["reactor.commit_absent_form"]["layer"]


def test_the_program_writes_the_instant_the_file_names():
    """One bare instant a commit that kept its wire bytes through a nil
    entry, none for a full commit: read back from the program's own
    recorder, through the reducer."""
    from tendermint_tpu.types import (TYPE_PRECOMMIT, BlockID, Commit,
                                      PartSetHeader, Vote)
    from tendermint_tpu.types.codec import Reader
    from tendermint_tpu.utils import tracing
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    votes = [Vote(validator_address=bytes([i]) * 20, validator_index=i,
                  height=7, round=0, type=TYPE_PRECOMMIT, block_id=bid,
                  signature=bytes([i]) * 64) for i in range(4)]
    t0 = tracing.now_epoch()
    for precommits in (votes, [votes[0], None, votes[2], votes[3]],
                       [None, votes[1], votes[2], None]):
        Commit.decode(Reader(Commit(block_id=bid,
                                    precommits=precommits).encode()))
    mine = [s for s in tracing.RECORDER.snapshot()
            if s["ts"] >= t0 and s["name"] in (RECORD, "commit.object_form")]
    wire = [s for s in mine if s["name"] == RECORD]
    # the program as it stands keeps both in their bytes; one that sent
    # them vote by vote would say `commit.object_form` twice: either way
    # two commits with nil entries were decoded, the full one wrote none
    assert len(mine) == 2
    assert all(s["args"]["height"] == 7 for s in wire)
    assert {s["args"]["absent"] for s in wire} <= {1, 2}
    ctx = {"spans": mine, "boot_spans": [], "hists": {}, "harness": {},
           "trace": None, "notes": []}
    assert reducers.read_metric(reducers.load_layer(REPO, NAME), ctx) == \
        float(len(wire))


def test_the_commits_kept_in_their_bytes_in_the_interval_are_counted():
    spans = _windows() + \
        [_span(RECORD, t, ph="i", height=9, absent=4) for t in WIRE_AT] + \
        [_span("commit.object_form", t, ph="i", height=9, reason="absent")
         for t in VOTES_AT]
    ctx = _ctx(spans)
    got = reducers.read_metric(reducers.load_layer(REPO, NAME), ctx)
    assert got == 4.0 and isinstance(got, float)
    # the share of the commits with a nil entry that the wire form held
    went = reducers.read_metric(
        reducers.load_layer(REPO, "reactor.commit_absent_form"), ctx)
    assert (got, went) == (4.0, 2.0)


def test_on_a_ring_without_the_instant_it_reads_zero():
    """A number and not nothing: an accepted test
    (`test_bench_full_blocks.py`) holds every metric of a cell to a
    number wherever two windows completed, on the parent too."""
    spans = _windows() + [_span("fastsync.decode", t, 0.001)
                          for t in WIRE_AT]
    got = reducers.read_metric(reducers.load_layer(REPO, NAME), _ctx(spans))
    assert got == 0.0 and isinstance(got, float)
