"""`stores.commit_aliased` (PR 50): how many `C:h` rows the block store
wrote in the interval as the marker for the bytes of `SC:h-1`, in place
of a third copy of the commit.  A layer file, equal to its `per_layer`
entry, a `span_count` over the one bare instant `BlockStore.save_block`
writes for such a row (`store.commit_alias`), read here off the ring of a
fast-sync through the real pool, reactor and `apply_window`, and 0.0, a
number, on a ring that holds none: a program from before PR 50."""

import json
import os

import benchutil
from benchutil import REPO
from benchmark.lib import accounting, chain, reducers

NAME, RECORD = "stores.commit_aliased", "store.commit_alias"

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


def _span(name, ts, dur=0.0, ph="X", **args):
    rec = {"name": name, "ph": ph, "ts": ts, "dur": dur}
    return dict(rec, args=args) if args else rec


# three reactor windows end at 100.0, 100.5, 100.9: the interval is
# (100.0, 100.9].  Marker rows written: one before it, four inside it,
# one after it
WINDOWS = [100.0, 100.5, 100.9]
ALIAS_AT = [99.99, 100.01, 100.5, 100.77, 100.9, 100.95]


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
            entry["source"]) == ("apply and stores", "count", "higher",
                                 "sync_blocks_per_s", "program_span")
    assert (spec["reducer"], spec["args"]) == ("span_count",
                                               {"span": RECORD})
    # the layer's name is the one the accepted metrics of the stores give
    assert entry["layer"] == PER_LAYER["stores.write_ms"]["layer"] == \
        PER_LAYER["apply.store_save_ms"]["layer"]


def test_a_recorded_fast_sync_counts_a_marker_a_height_less_the_first():
    """65 served blocks of 4 validators through the real pool, reactor,
    look-ahead and `apply_window`: 64 heights stored, the first with its
    `C:` row whole (no seen commit came before it), every other with the
    marker, one bare instant each; the node holds and serves every
    commit as the builder signed it."""
    from tendermint_tpu.utils import tracing
    n_blocks, n_vals, seed = 65, 4, 2**31 + 50
    seeds, vs = chain.valset_at(seed, n_vals, None, 1)
    with chain.Signers(seeds, 0) as sg:
        built = chain.build_chain(
            "bench-alias", seeds, vs, n_blocks,
            {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}, seed, sg)
    t0 = tracing.now_epoch()
    bc = benchutil.fast_sync(built, "bench-alias", "kvstore", n_blocks - 1)
    stored = bc.store.height
    assert stored in (n_blocks - 1, n_blocks)
    mine = [s for s in tracing.RECORDER.since(t0)
            if s["ts"] >= t0 and s["name"] in (RECORD, "fastsync.apply")]
    wrote = [s for s in mine if s["name"] == RECORD]
    assert all(s["ph"] == "i" and "args" not in s for s in wrote)
    ctx = {"spans": mine, "boot_spans": [], "hists": {}, "harness": {},
           "trace": None, "notes": []}
    got = reducers.read_metric(reducers.load_layer(REPO, NAME), ctx)
    assert got == float(len(wrote)) == float(stored - 1)
    assert got in (63.0, 64.0)
    rows = dict(bc.store.db.iterate_prefix(b"C:"))
    assert sum(1 for v in rows.values() if v == b"") == stored - 1
    for h in range(1, stored):
        served = built["encoded"][h]          # block h + 1 ends with it
        for commit in (bc.store.load_block_commit(h),
                       bc.store.load_seen_commit(h)):
            assert commit.num_sigs() == built["signed"][h - 1] == n_vals
            assert served.endswith(commit.encode())


def test_the_marker_rows_written_in_the_interval_are_counted():
    spans = _windows() + [_span(RECORD, t, ph="i") for t in ALIAS_AT] + \
        [_span("db.write", t, 0.001) for t in ALIAS_AT]
    got = reducers.read_metric(reducers.load_layer(REPO, NAME), _ctx(spans))
    assert got == 4.0 and isinstance(got, float)


def test_on_a_ring_without_the_instant_it_reads_zero():
    """A number and not nothing: an accepted test
    (`test_bench_full_blocks.py`) holds every metric of a cell to a
    number wherever two windows completed, on the parent too."""
    spans = _windows() + [_span("db.write", t, 0.001) for t in ALIAS_AT]
    got = reducers.read_metric(reducers.load_layer(REPO, NAME), _ctx(spans))
    assert got == 0.0 and isinstance(got, float)
