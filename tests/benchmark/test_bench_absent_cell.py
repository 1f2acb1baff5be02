"""The cell `catchup-absent-100v.absent-precommits` as files: a
configuration that is `catchup-100v`'s but for what the nil entries
change, a traffic mix that is `empty-blocks`' block under PR 43's plan of
absent precommits with a chain plan of its own, and six per-layer metrics
as data files.  No rehearsal of `run_cell` at 100 validators here (a
window is 6,144 lanes, minutes on the CPU backend): the harness's run
over an absent chain is `test_bench_absent_rehearsal.py`, the program
against the plain reference on this mix's own plan is
`test_bench_absent_reference.py`, and the records the new metrics read
are `tests/test_absent_records.py`."""

import json
import os

import pytest

from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain, reducers

CELL = "catchup-absent-100v.absent-precommits"
PLAN = {"late_per_1000": 20, "down": 2, "down_for_blocks": 1000}
# what differs from catchup-100v's file, by key; every other key is its
OWN_KEYS = {"name", "source", "deployment", "on_device", "guarantees",
            "assumed", "reduced_to"}
NEW_METRICS = ("reactor.commit_absent_form", "reactor.vote_decode_ms",
               "reactor.lane_windows_per_block",
               "reactor.lane_windows_vectorised", "rpc.handle_ms",
               "rpc.requests")
SEED = 2**31 + 441


def _json(*path) -> dict:
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


PLAIN = _json("benchmark", "configs", "catchup-100v.json")
OURS = _json("benchmark", "configs", "catchup-absent-100v.json")
MIX = _json("benchmark", "traffic", "absent-precommits.json")
BENCH = _json("BENCHMARK.json")


@pytest.mark.parametrize("key", sorted(set(PLAIN) | set(OURS)))
def test_the_configuration_is_catchup_100vs_key_for_key(key):
    if key in OWN_KEYS:
        assert key in OURS and OURS[key] != PLAIN.get(key), key
    else:
        assert OURS[key] == PLAIN[key], key


def test_no_shape_rate_or_limit_is_cut_and_a_fourth_guarantee_is_stated():
    assert OURS["validators"] == 100 and OURS["chips"] == 1
    assert OURS["reduced"] == ["upstream_chain_blocks"]
    assert OURS["upstream_chain_blocks"] == 100000
    assert OURS["guarantees"][:3] == PLAIN["guarantees"]
    assert len(OURS["guarantees"]) == 4
    fourth = OURS["guarantees"][3]
    assert "neither verified nor tallied" in fourth
    assert "WHOLE power" in fourth and "nil entries" in fourth
    assert set(OURS["assumed"]) == set(PLAIN["assumed"]) | {"absent"}
    assert all(OURS["assumed"][k] == v for k, v in PLAIN["assumed"].items())
    said = OURS["assumed"]["absent"]
    for word in ("stated from memory", "ValidatorTimeoutWindow",
                 "signed_blocks_window", "min_signed_per_window",
                 "2 of 100", "20 in 1,000", "1,000 heights"):
        assert word in said, word
    assert "327,155,712 B" in OURS["on_device"]
    from tendermint_tpu.config import Config
    cell_mod.stated_as_run(OURS, Config())
    entry = next(c for c in BENCH["configs"] if c["name"] == OURS["name"])
    assert "types/block.go:307-354" in entry["source"]
    assert "types/block.go:307-354" in OURS["source"]


def test_the_mix_is_empty_blocks_block_under_the_plan_pr_43_measured():
    empty = _json("benchmark", "traffic", "empty-blocks.json")
    assert MIX["name"] == "absent-precommits"
    assert MIX["block"] == empty["block"] == {
        "txs_per_block": 1, "tx_bytes": 16, "keys": 7}
    assert MIX["absent"] == PLAN and "valset" not in MIX
    assert "closed loop" in MIX["why"] and "Who sends it" in MIX["why"]
    want = {"parent_blocks_per_s": 148, "warmup_s": 22, "headroom": 3.0}
    assert MIX["chain"]["default"] == want
    assert MIX["chain"]["catchup-absent-100v"] == want


def lists_hold(root: str, bench: dict) -> None:
    """What this file holds of `bench`'s three lists, read with the
    files under `root`: the cell is in `workloads` once and its
    configuration in `configs` once, wherever; PR 44's six metrics are in
    `per_layer` once each and in PR 44's order RELATIVE TO EACH OTHER;
    and a cell's metrics come in the list's order.  Nothing here holds
    the END of a list: a later PR appends its cell, its configuration and
    its metrics after these, and a pin of the tail would close the list
    to it (as `per_layer[-6:]` closed it to PR 46's metric)."""
    cell = cell_mod.load_cell(root, CELL)
    names = [m["name"] for m in cell["per_layer"]]
    assert names == [m["name"] for m in bench["per_layer"]
                     if "workloads" not in m]
    assert set(NEW_METRICS) <= set(names)
    assert not any(CELL in m.get("workloads", ())
                   for m in bench["per_layer"] + bench["end_to_end"])
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [c["name"] for c in bench["configs"]].count(
        "catchup-absent-100v") == 1
    listed = [m["name"] for m in bench["per_layer"]]
    assert all(listed.count(n) == 1 for n in NEW_METRICS)
    at = [listed.index(n) for n in NEW_METRICS]
    assert at == sorted(at)


def test_the_cell_loads_with_its_traffic_and_every_unlisted_metric():
    cell = cell_mod.load_cell(REPO, CELL)
    assert cell["chips"] == 1 and cell["config"] == OURS
    assert cell["traffic"] == MIX and cell["traffic_name"] == MIX["name"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "sync_blocks_per_s", "boot_to_first_window_s", "setup_s"}
    # the accepted kernel's metrics read this cell as they read cell 1
    assert {"kernel.verify_ms", "verify_grouped_templated_roofline",
            "device.idle_pct", "device.hbm_peak_MiB",
            "rpc.status_p95_ms"} <= {m["name"] for m in cell["per_layer"]}
    lists_hold(REPO, BENCH)


def _write(path: str, obj: dict) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def test_a_seventh_cell_configuration_and_metric_appended_break_no_order(
        tmp_path):
    """A copy of `BENCHMARK.json` with a made-up cell, configuration and
    per-layer metric appended, each with its files, as a `model_config`
    PR would bring them: every assertion under `tests/benchmark/` on the
    ORDER of the three lists still holds of the copy (this file's, above;
    and that a cell's metrics come in the list's order, which
    `test_bench_wide_set.py` holds too), all seven cells load, and the
    six accepted cells read the new unlisted metric after all they had."""
    import copy
    import shutil
    root = str(tmp_path)
    for part in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(REPO, "benchmark", part),
                        os.path.join(root, "benchmark", part))
    _write(os.path.join(root, "benchmark", "configs", "made-up-7v.json"),
           dict(PLAIN, name="made-up-7v", validators=7))
    _write(os.path.join(root, "benchmark", "traffic", "made-up-mix.json"),
           dict(_json("benchmark", "traffic", "empty-blocks.json"),
                name="made-up-mix"))
    _write(os.path.join(root, "benchmark", "layers", "reactor.made_up.json"),
           dict(reducers.load_layer(REPO, "reactor.commit_absent_form"),
                name="reactor.made_up"))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "made-up-7v", "source": "none: a test's",
        "file": "benchmark/configs/made-up-7v.json", "reduced": [],
        "why": "a seventh configuration, appended"})
    bench["workloads"].append({
        "name": "made-up-7v.made-up-mix", "config": "made-up-7v",
        "traffic": "made-up-mix", "chips": 1,
        "why": "a seventh cell, appended"})
    bench["per_layer"].append(dict(
        next(m for m in BENCH["per_layer"]
             if m["name"] == "reactor.commit_absent_form"),
        name="reactor.made_up"))
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    assert bench["workloads"][-1]["name"] != CELL      # the tails moved
    assert bench["configs"][-1]["name"] != "catchup-absent-100v"
    assert bench["per_layer"][-1]["name"] not in NEW_METRICS
    lists_hold(root, bench)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == len(BENCH["workloads"]) + 1 == len(set(cells))
    for name in cells:
        got = cell_mod.load_cell(root, name)
        assert [m["name"] for m in got["per_layer"]] == [
            m["name"] for m in bench["per_layer"]
            if "workloads" not in m or name in m["workloads"]]
        assert got["per_layer"][-1]["name"] == "reactor.made_up"
        if name != cells[-1]:
            before = cell_mod.load_cell(REPO, name)
            assert got["per_layer"][:-1] == before["per_layer"]
            assert got["config"] == before["config"]
            assert got["traffic"] == before["traffic"]
    made_up = cell_mod.load_cell(root, cells[-1])
    assert made_up["config"]["validators"] == 7
    assert made_up["traffic"]["name"] == "made-up-mix"
    # and of the tree as it stands, which this PR appended one metric to
    lists_hold(REPO, BENCH)
    assert BENCH["per_layer"][-1]["name"] not in NEW_METRICS


# the cell PR 48 prepared, kept as data of THIS test: nothing under
# `benchmark/` and no entry of `BENCHMARK.json` names these files until a
# `model_config` PR copies them to `benchmark/configs/` and
# `benchmark/traffic/` and appends the two entries
CANDIDATE = "catchup-powers-100v.power-drift"
DATA = os.path.join(REPO, "tests", "benchmark", "data")


def with_the_candidate(tmp_path, **config_edits) -> tuple[str, dict]:
    """(root, bench) of a copy of the benchmark's data files under
    `tmp_path` with the candidate's configuration and mix put where a PR
    would put them and its two entries LAST in `configs` and `workloads`
    (an entry of the same name that the tree already lists is replaced:
    the guard judges the data files, whatever the tree has taken since)."""
    import copy
    import shutil
    root = str(tmp_path)
    for part in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(REPO, "benchmark", part),
                        os.path.join(root, "benchmark", part))
    _write(os.path.join(root, "benchmark", "configs",
                        "catchup-powers-100v.json"),
           dict(_json(DATA, "catchup-powers-100v.json"), **config_edits))
    shutil.copy(os.path.join(DATA, "power-drift.json"),
                os.path.join(root, "benchmark", "traffic"))
    bench = copy.deepcopy(BENCH)
    for key, entries in _json(DATA,
                              "catchup-powers-100v.entries.json").items():
        names = {e["name"] for e in entries}
        bench[key] = [e for e in bench[key]
                      if e["name"] not in names] + entries
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root, bench


def test_the_prepared_seventh_cell_appended_is_held_by_every_rule(tmp_path):
    """What the guard above cannot see (its seventh cell is a clone of
    `catchup-100v` under a clone of `empty-blocks`, judged by the order
    rules alone): a REAL candidate, one that differs from the accepted
    cells in its app, its plan and its chain plan, appended to a copy
    with its files, is held by every rule that holds an accepted entry
    (`benchutil.ENTRY_RULES`: the functions the parametrised tests of
    `test_bench_files.py` and `test_bench_churn_cell.py` call on the
    tree), by `lists_hold` and by the order `test_bench_wide_set.py`
    holds; every cell loads, the others as they load from the tree."""
    import benchutil
    root, bench = with_the_candidate(tmp_path)
    assert bench["workloads"][-1]["name"] == CANDIDATE
    assert bench["configs"][-1]["name"] == "catchup-powers-100v"
    assert benchutil.every_entry_holds(root, bench) == \
        2 * len(bench["configs"]) + len(bench["workloads"])
    lists_hold(root, bench)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == len(set(cells)) >= 7
    unlisted = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    for name in cells:
        got = cell_mod.load_cell(root, name)
        assert [m["name"] for m in got["per_layer"]] == [
            m["name"] for m in bench["per_layer"]
            if "workloads" not in m or name in m["workloads"]]
        if name != CANDIDATE:
            assert got == cell_mod.load_cell(REPO, name)
    # as `test_bench_wide_set.py` holds of its cell: every unlisted
    # metric, in the list's order, and the cell adds itself to no list
    wide = cell_mod.load_cell(root, "catchup-300v.empty-blocks")
    assert [m["name"] for m in wide["per_layer"]] == unlisted
    cand = cell_mod.load_cell(root, CANDIDATE)
    assert [m["name"] for m in cand["per_layer"]] == unlisted
    assert not any(CANDIDATE in m.get("workloads", ())
                   for m in bench["per_layer"] + bench["end_to_end"])
    assert cand["config"]["app"] == "valset_kvstore"
    assert cand["traffic"]["powers"] == {
        "change_every_blocks": 1, "members": 3, "min": 1, "max": 100}
    assert cell_mod.chain_plan(cand) == {
        "parent_blocks_per_s": 15, "warmup_s": 22, "headroom": 20.0}
    assert cell_mod.chain_blocks(cand, 45) == 20161
    chain.check_plans(SEED, 100, cand["traffic"].get("valset"),
                      cand["traffic"].get("absent"),
                      cand["traffic"]["powers"])
    cell_mod.app_fits_plans(cand["config"], cand["traffic"])
    from tendermint_tpu.config import Config
    booted = Config()
    booted.base.proxy_app = cand["config"]["app"]
    cell_mod.stated_as_run(cand["config"], booted)


def test_the_rules_refuse_the_candidate_on_an_app_its_plan_cannot_use(
        tmp_path):
    """And the other way round, so that the rule bites through the same
    functions: the candidate stating the default app is refused by name
    of its plan and of the app, before anything could build its chain."""
    import benchutil
    root, bench = with_the_candidate(tmp_path, app="kvstore")
    with pytest.raises(ValueError, match="'catchup-powers-100v'.*'kvstore'"
                       ".*'power-drift'.*a powers plan.*returns none"):
        benchutil.every_entry_holds(root, bench)
    # all but the app rule hold of it still
    for key, rules in benchutil.ENTRY_RULES.items():
        for rule in rules:
            if rule is not benchutil.config_states_an_app_that_fits:
                rule(root, bench, bench[key][-1])


def test_the_candidates_files_are_the_churn_cells_but_for_what_powers_change():
    """`catchup-churn-100v.json` key for key in its shapes, rates and
    limits; `empty-blocks`' block; entries within the contract's 200
    characters; depth the only cut."""
    cfg = _json(DATA, "catchup-powers-100v.json")
    churn = _json("benchmark", "configs", "catchup-churn-100v.json")
    own = {"name", "source", "deployment", "voting_power", "on_device",
           "guarantees", "assumed", "reduced_to"}
    assert list(cfg) == list(churn)
    assert {k for k in cfg if cfg[k] != churn[k]} == own
    assert cfg["guarantees"][:3] == PLAIN["guarantees"]
    assert len(cfg["guarantees"]) == 4
    assert "KEYS AND POWERS" in cfg["guarantees"][3]
    assert "/validators" in cfg["guarantees"][3]
    assert set(cfg["assumed"]) >= {"change_every_blocks", "members", "min",
                                   "max", "genesis", "source_peers"}
    assert cfg["reduced"] == ["upstream_chain_blocks"]
    assert "20,161" in cfg["reduced_to"] and "ONE comb table" in cfg[
        "on_device"] and "327,155,712 B" in cfg["on_device"]
    mix = _json(DATA, "power-drift.json")
    assert mix["block"] == _json("benchmark", "traffic",
                                 "empty-blocks.json")["block"]
    assert not {"valset", "absent"} & set(mix)
    assert mix["chain"]["default"] == mix["chain"]["catchup-powers-100v"]
    assert "0.8 x headroom = 16 (240 blocks/s)" in mix["chain"]["note"]
    # data of a test: neither BENCHMARK.json nor a file under
    # `benchmark/` names them
    paths = [os.path.join(REPO, "BENCHMARK.json")] + [
        os.path.join(d, f)
        for d, _dirs, files in os.walk(os.path.join(REPO, "benchmark"))
        for f in files if f.endswith((".json", ".md", ".py"))]
    for path in paths:
        with open(path) as f:
            text = f.read()
        assert not any(word in text for word in (
            "data/catchup-powers-100v", "data/power-drift")), path


@pytest.mark.parametrize("seconds,blocks", [(5, 12033), (45, 29761),
                                            (51, 32449)])
def test_the_chain_is_whole_windows_plus_one_from_the_mixs_own_plan(
        seconds, blocks):
    cell = cell_mod.load_cell(REPO, CELL)
    plan = cell_mod.chain_plan(cell)
    assert plan == MIX["chain"]["catchup-absent-100v"]
    assert "chain" not in OURS          # the traffic file's, not an override
    n = cell_mod.chain_blocks(cell, seconds)
    want = 3.0 * 148 * (22 + seconds)
    assert n == blocks and n % 64 == 1 and want <= n - 1 < want + 64
    # ~18.5 KB a block: the chain fits the source child
    assert n * 188 * 100 < 1.5e9


def test_the_plan_passes_the_builders_checks_at_100_validators():
    """`absent_at` takes the plan as it stands in the file; at every
    height sampled over the chain's 29,761 (each epoch's ends and a
    stride between) the two that are down are silent, some are late, and
    never a third of the power: more than 2/3 signs every commit."""
    plan = MIX["absent"]
    heights = sorted({h for e in range(30) for h in (
        e * 1000 + 1, e * 1000 + 500, e * 1000 + 1000) if h <= 29761}
        | set(range(1, 29762, 97)))
    counts, down_sets = [], set()
    for h in heights:
        silent = chain.absent_at(SEED, 100, None, plan, h)
        down = chain.absent_at(SEED, 100, None, {
            "down": 2, "down_for_blocks": 1000}, h)
        assert len(down) == 2 and set(down) <= set(silent)
        assert 2 <= len(silent) <= 33
        assert 3 * (100 - len(silent)) * chain.POWER > 2 * 100 * chain.POWER
        counts.append(len(silent))
        down_sets.add(down)
    # ~2 late of the 98 others a height: about 4 nil entries a commit
    assert 3.5 <= sum(counts) / len(counts) <= 4.5
    assert len(down_sets) >= 25        # another pair every 1,000 heights
    # 96 % of the lanes signed: the window rides cell 1's program
    from tendermint_tpu.crypto import backend as cb
    signed_lanes = 64 * (100 - sum(counts) / len(counts))
    assert cb._bucket(int(signed_lanes)) == cb._bucket(64 * 100) == 8192


# -- the six metrics ---------------------------------------------------------------

def _ctx(spans):
    return {"spans": spans, "boot_spans": [], "hists": {}, "harness": {},
            "trace": None, "notes": []}


WINDOWS = [{"name": "fastsync.window", "ts": 0.5 * i, "dur": 0.5}
           for i in range(4)]
# a synthetic ring: 4 windows; 5 commits decoded vote by vote, 3 for a nil
# entry; 3 windows per block, 1 vectorised; 7 requests
RING = WINDOWS + [
    {"name": "commit.object_form", "ts": 0.1, "dur": 0.0, "ph": "i",
     "args": {"height": 9, "reason": "absent"}},
    {"name": "commit.object_form", "ts": 0.2, "dur": 0.0, "ph": "i",
     "args": {"height": 10, "reason": "absent"}},
    {"name": "commit.object_form", "ts": 0.3, "dur": 0.0, "ph": "i",
     "args": {"height": 11, "reason": "length"}},
    {"name": "commit.object_form", "ts": 0.4, "dur": 0.0, "ph": "i",
     "args": {"height": 12, "reason": "votes"}},
    {"name": "commit.object_form", "ts": 0.5, "dur": 0.0, "ph": "i",
     "args": {"height": 13, "reason": "absent"}},
] + [{"name": "commit.decode.votes", "ts": 0.1 * i, "dur": 0.002}
     for i in range(5)] + [
    {"name": "fastsync.lanes.per_block", "ts": 0.1 * i, "dur": 0.0,
     "args": {"blocks": 64, "object_commits": 64}} for i in range(3)] + [
    {"name": "fastsync.lanes.vectorised", "ts": 1.9, "dur": 0.0,
     "args": {"blocks": 64, "object_commits": 0}}] + [
    {"name": "rpc.request", "ts": 0.2 * i, "dur": 0.004,
     "args": {"method": "status"}} for i in range(7)]
WANT = {"reactor.commit_absent_form": 3.0,
        "reactor.vote_decode_ms": 1e3 * 5 * 0.002 / 4,
        "reactor.lane_windows_per_block": 3.0,
        "reactor.lane_windows_vectorised": 1.0,
        "rpc.handle_ms": 1e3 * 7 * 0.004 / 4,
        "rpc.requests": 7.0}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_its_span_and_zero_where_there_is_none(name):
    spec = reducers.load_layer(REPO, name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert "workloads" not in entry and "workloads" not in spec
    assert spec["source"] == "program_span"
    assert spec["moves"] == "sync_blocks_per_s"
    assert spec["layer"] == name.split(".")[0]
    assert spec["reducer"] in ("span_count", "span_ms_per")
    assert spec["better"] == ("higher" if name in (
        "reactor.lane_windows_vectorised", "rpc.requests") else "lower")
    assert reducers.read_metric(spec, _ctx(RING)) == pytest.approx(WANT[name])
    # a ring without the span (the five accepted cells for the first
    # four; the parent's program for all six): 0.0 and not nothing
    got = reducers.read_metric(spec, _ctx(WINDOWS))
    assert got == 0.0 and isinstance(got, float)


def test_the_absent_count_selects_by_the_instants_reason():
    spec = reducers.load_layer(REPO, "reactor.commit_absent_form")
    assert spec["args"] == {"span": "commit.object_form",
                            "where": {"reason": "absent"}}
    whole = reducers.load_layer(REPO, "reactor.commit_object_form")
    assert reducers.read_metric(whole, _ctx(RING)) == 5.0
    # the parent's word for a commit with a nil entry: counted by the
    # accepted metric, not by this one
    old = [dict(s, args=dict(s["args"], reason="length"))
           if s["name"] == "commit.object_form" else s for s in RING]
    assert reducers.read_metric(spec, _ctx(old)) == 0.0
    assert reducers.read_metric(whole, _ctx(old)) == 5.0


def test_the_mean_service_time_of_a_request_follows_from_the_pair():
    ctx = _ctx(RING)
    handle, requests = (reducers.read_metric(
        reducers.load_layer(REPO, n), ctx)
        for n in ("rpc.handle_ms", "rpc.requests"))
    assert handle * len(WINDOWS) / requests == pytest.approx(4.0)


# -- the six metrics through `run_cell`, rehearsed on the CPU ------------------------

def test_a_traced_rehearsal_over_an_absent_chain_reads_all_six():
    """`run_cell` at 4 validators with one down and some late (every
    commit holds a nil entry): the run is `correct`, each new metric is
    in the line and finite, and the prober's requests were handled in
    less than they waited.

    Which decoder and which lane builder the program took is NOT held
    here: a live run's path is the program's to change (a wire form that
    survives a nil entry reads `reactor.commit_absent_form` 0,
    `reactor.vote_decode_ms` 0.0 and every window vectorised, where PR
    44's program reads the interval's heights, a time, and every window
    per block), and an accepted benchmark file that pinned it could be
    lifted by no PR that changes the program.  Two relations hold
    whatever the path: this chain has no irregularity but its nil
    entries, so every object-form commit is an absent-form one, at most
    one a height; and every window is counted once by one of the two
    builders, twice where a look-ahead was dropped.  That the four
    reducers read the right records is pinned by the synthetic ring
    above (`WANT`)."""
    import math

    import benchutil
    result, out = benchutil.rehearse(
        seed=SEED + 4, trace=True, timeout=600,
        traffic={"absent": {"late_per_1000": 150, "down": 1,
                            "down_for_blocks": 96}})
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(m)
    assert all(math.isfinite(m[name]) for name in NEW_METRICS)
    heights = result["attempted"]
    windows = heights // 64
    absent = m["reactor.commit_absent_form"]
    assert m["reactor.commit_object_form"] == absent <= heights + 64
    # a commit of this chain that did not go vote by vote stayed in its
    # wire bytes with its nil entry: each decoded commit is one of the
    # two (counted where it is DECODED, up to the pool's 300 requests
    # ahead of the height applied)
    wire = m["reactor.commit_wire_absent"]
    assert wire >= 0.0 and abs(wire + absent - heights) <= 400
    assert wire + absent > 0
    # a window the look-ahead verified and the main loop verified again
    # (a dropped look-ahead) counts twice
    assert windows - 1 <= (m["reactor.lane_windows_per_block"]
                           + m["reactor.lane_windows_vectorised"]) \
        <= 2 * windows
    # the per-vote loop took time exactly where a commit went through it
    assert m["reactor.vote_decode_ms"] >= 0.0
    assert (m["reactor.vote_decode_ms"] > 0.0) == (absent > 0)
    assert 10 <= m["rpc.requests"] <= 70          # 10 a second for 6 s
    mean_ms = m["rpc.handle_ms"] * windows / m["rpc.requests"]
    assert 0.0 < mean_ms < 1e3
