"""A chain whose validator set changes (`valset` of a traffic mix) and a
configuration that names its app: what the builder serves without a plan
is byte for byte the parent's; with a plan every height is signed by ITS
set, the reference app returns the diffs, and the program follows the
chain to the builder's sets."""

import hashlib
import json
import os
import types

import pytest

import benchutil
from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain
from tendermint_tpu.abci.app import create_app
from tendermint_tpu.crypto import native
from tendermint_tpu.types import Block

EMPTY = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
FULL = {"txs_per_block": 40, "tx_bytes": 250, "keys": 40}
PIN_SEED = 2**31 + 35
# sha256 over the joined `encoded`, `block_hash` and `app_hash` lists of
# build_chain("bench-pin", ..., seed PIN_SEED) as the PARENT tree gives them
# (computed on `git archive 849ac74`): (block spec, validators, blocks)
PARENT = {
    "empty": (EMPTY, 5, 12, (
        "0eedd35f6469bbb38b26a3fb5455b06f357af1f8e15aecdeec66962dd159b0a7",
        "057c27f50bcef9701e446ff2d7b3d4702632d381bef726daef977c06f3c41c9e",
        "14bc749b0838715f4e912307ebdd404ae336fae54bd89d524663093121c07f07")),
    "full": (FULL, 33, 7, (
        "830f1f07cffa90e377a1a5effcf2ea63f27fa883e7367be211a13a398ef5eeab",
        "42867477993d3f62502895354a5fe477b23150059623daf4393407fcd95d466d",
        "379a9272620c7e3f92d6d74ac393668265039582994cf506e84771c47926bf07")),
}
# the churn chain of these tests: 5 validators, one swapped every 3 blocks
N_VALS, N_BLOCKS, SEED = 5, 14, 2**31 + 36
PLAN = {"change_every_blocks": 3, "swap": 1}
# the program's kvstore that returns `val:` txs as `EndBlock` diffs
VALSET_APP = "valset_kvstore"


def _digest(parts) -> str:
    return hashlib.sha256(b"".join(parts)).hexdigest()


@pytest.mark.parametrize("workers", [0, 2, None],
                         ids=["in-process", "workers", "as-the-child"])
@pytest.mark.parametrize("mix", sorted(PARENT))
def test_without_a_plan_the_chain_is_the_parents_byte_for_byte(mix, workers):
    spec, n_vals, n_blocks, want = PARENT[mix]
    seeds, vs = chain.valset_at(PIN_SEED, n_vals, None, 1)
    assert (seeds, vs.hash()) == (
        chain.make_validators(PIN_SEED, n_vals)[0],
        chain.make_validators(PIN_SEED, n_vals)[1].hash())
    with chain.Signers(seeds, workers) as sg:
        built = chain.build_chain("bench-pin", seeds, vs, n_blocks, spec,
                                  PIN_SEED, sg)
    assert tuple(_digest(built[k]) for k in
                 ("encoded", "block_hash", "app_hash")) == want
    assert [(h, s.hash()) for h, s in built["valsets"]] == [(1, vs.hash())]


def _build(workers=0, seed=SEED, plan=PLAN, keep_objects=False):
    seeds, vs = chain.valset_at(seed, N_VALS, plan, 1)
    with chain.Signers(seeds, workers) as sg:
        return chain.build_chain("bench-churn", seeds, vs, N_BLOCKS, EMPTY,
                                 seed, sg, keep_objects=keep_objects,
                                 valset=plan)


@pytest.fixture(scope="module")
def churn():
    return _build(keep_objects=True)


def test_the_set_of_a_height_is_a_pure_function_of_seed_plan_and_height():
    sets = [chain.valset_members(SEED, N_VALS, PLAN, h)
            for h in range(1, N_BLOCKS + 2)]
    assert sets[0] == tuple(range(N_VALS))
    for h in range(1, N_BLOCKS + 1):
        old, new = set(sets[h - 1]), set(sets[h])
        if h % 3 == 0:        # the diffs of h make the set of h + 1
            assert len(old - new) == len(new - old) == 1
            # the key that joins was never used before
            assert (new - old) == {N_VALS + h // 3 - 1}
        else:
            assert old == new
        assert len(new) == N_VALS
    # asked out of order, and again: the same
    assert chain.valset_members(SEED, N_VALS, PLAN, 7) == sets[6]
    assert chain.valset_members(SEED + 1, N_VALS, PLAN, 7) != sets[6]
    assert chain.valset_members(SEED, N_VALS, None, 700) == sets[0]
    seeds, vs = chain.valset_at(SEED, N_VALS, PLAN, 7)
    assert [chain.pub_of(s) for s in seeds] == [
        v.pub_key.bytes_ for v in vs.validators]
    assert {v.voting_power for v in vs.validators} == {chain.POWER}


@pytest.mark.parametrize("plan", [
    {"change_every_blocks": 0, "swap": 1}, {"change_every_blocks": 3,
                                            "swap": 0},
    {"change_every_blocks": 3, "swap": N_VALS + 1}])
def test_a_plan_that_cannot_be_run_is_refused(plan):
    with pytest.raises(ValueError, match="valset plan"):
        chain.valset_members(SEED, N_VALS, plan, 1)


def test_val_txs_come_where_the_plan_says_after_the_kvstore_txs(churn):
    for h in range(1, N_BLOCKS + 1):
        txs = Block.decode_bytes(churn["encoded"][h - 1]).txs
        assert txs[:1] == chain.block_txs(EMPTY, SEED, h)
        vals = [chain.parse_val_tx(t) for t in txs[1:]]
        assert txs[1:] == chain.valset_txs(SEED, N_VALS, PLAN, h)
        if h % 3:
            assert vals == []
            continue
        old = {v.pub_key.bytes_ for v in
               chain.valset_at(SEED, N_VALS, PLAN, h)[1].validators}
        new = {v.pub_key.bytes_ for v in
               chain.valset_at(SEED, N_VALS, PLAN, h + 1)[1].validators}
        assert vals == [(next(iter(old - new)), 0),
                        (next(iter(new - old)), chain.POWER)]
    with pytest.raises(ValueError, match="malformed validator tx"):
        chain.parse_val_tx(b"val:abcd/10")


@pytest.mark.parametrize("workers", [0, 2], ids=["in-process", "workers"])
def test_churn_chain_is_signed_height_by_height_by_its_own_set(churn,
                                                               workers):
    """Every header holds the hash of ITS height's set, every commit is
    +2/3 (here: all) of that set by OpenSSL over the program's canonical
    sign-bytes, and the set moves exactly where the plan says."""
    built = churn if workers == 0 else _build(workers)
    assert built["encoded"] == churn["encoded"]            # repeatable
    assert built["encoded"] != _build(seed=SEED + 1)["encoded"]
    assert [h for h, _ in built["valsets"]] == [1, 4, 7, 10, 13]
    assert len({s.hash() for _, s in built["valsets"]}) == 5
    blocks = [Block.decode_bytes(e) for e in built["encoded"]]
    for h, block in enumerate(blocks, 1):
        _seeds, vs = chain.valset_at(SEED, N_VALS, PLAN, h)
        assert block.header.validators_hash == vs.hash()
        assert vs.size() == N_VALS
        assert vs.hash() == [s for f, s in built["valsets"]
                             if f <= h][-1].hash()
        block.validate_basic()
        if h == 1:
            continue
        # block h embeds the commit of h - 1: the set of h - 1 signed it
        _seeds, signed_by = chain.valset_at(SEED, N_VALS, PLAN, h - 1)
        votes = block.last_commit.precommits
        assert [v.validator_address for v in votes] == [
            v.address for v in signed_by.validators]
        power = sum(val.voting_power for v, val in
                    zip(votes, signed_by.validators)
                    if native.verify_one(val.pub_key.bytes_,
                                         v.sign_bytes("bench-churn"),
                                         v.signature))
        assert 3 * power > 2 * signed_by.total_voting_power()
        assert power == N_VALS * chain.POWER


def test_reference_app_hashes_over_val_txs_are_the_programs_kvstores(churn):
    """A `val:` tx has no `=`: key = value = the tx, in the reference as
    in the program's kvstore of today; the reference keeps a block's
    diffs until its commit."""
    ref, app = chain.RefKVStore(), create_app("kvstore")
    for h, enc in enumerate(churn["encoded"], 1):
        txs = Block.decode_bytes(enc).txs
        for tx in txs:
            ref.deliver_tx(tx)
            app.deliver_tx(tx)
        assert ref.diffs == [chain.parse_val_tx(t) for t in txs[1:]]
        assert len(ref.diffs) == (0 if h % 3 else 2)
        assert ref.commit() == app.commit().data == churn["app_hash"][h - 1]
        assert ref.diffs == []


def test_signers_keep_the_union_and_sign_with_the_set_in_use():
    a, _ = chain.valset_at(SEED, N_VALS, PLAN, 1)
    b, _ = chain.valset_at(SEED, N_VALS, PLAN, 4)
    for workers in (0, 2):
        with chain.Signers(a, workers) as sg:
            first = sg.sign_all(b"m")
            sg.use(b)
            assert sg.sign_all(b"m") != first
            sg.use(a)
            assert sg.sign_all(b"m") == first         # ed25519: no nonce
            with pytest.raises(ValueError, match="a set of 4 keys"):
                sg.use(a[:4])


# -- the program against the builder, on the churn chain --------------------

def _genesis(built):
    return chain.genesis_doc(chain.genesis_dict("bench-churn",
                                                built["valsets"][0][1]))


def _set_hash_after(built, h: int) -> bytes:
    return [s for f, s in built["valsets"] if f <= h + 1][-1].hash()


def test_apply_block_follows_the_churn_chain_to_the_builders_sets(churn):
    """`apply_block` over the built blocks, last commits checked, with an
    app that returns the `val:` txs as `EndBlock` diffs: after every
    height the state's set is the one the builder signs the next with."""
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    state = get_state(MemDB(), _genesis(churn))
    conns = ClientCreator(VALSET_APP).new_app_conns()
    old = cb._current
    cb.set_backend("native")
    try:
        for h, (block, ps, _seen) in enumerate(churn["objects"], 1):
            execution.apply_block(state, None, conns.consensus, block,
                                  ps.header, execution.MockMempool())
            assert state.validators.hash() == _set_hash_after(churn, h)
            assert state.validators.size() == N_VALS
            assert state.app_hash == churn["app_hash"][h - 1]
    finally:
        cb._current = old
    # the set that signed the last height, which the next block's
    # LastCommit will be checked against
    assert state.last_validators.hash() == _set_hash_after(churn,
                                                           N_BLOCKS - 1)


def test_fast_sync_follows_the_churn_chain_through_its_window_cuts(churn):
    """The churn chain from the benchmark's own source store through the
    real pool, reactor, look-ahead and `apply_window` (8-block windows,
    a set change every 3): the node stores the builder's blocks and ends
    on the builder's set and app hash."""
    tip = N_BLOCKS - 1                # the last block's commit is not served
    bc = benchutil.fast_sync(churn, "bench-churn", VALSET_APP, tip)
    for h in range(1, tip + 1):
        assert bc.store.load_block_meta(h).block_id.hash == \
            churn["block_hash"][h - 1]
    assert bc.state.app_hash == churn["app_hash"][tip - 1]
    assert bc.state.validators.hash() == _set_hash_after(churn, tip)
    assert bc.state.validators.hash() != churn["valsets"][0][1].hash()


# -- the source child's index, and the configuration's app ------------------

@pytest.mark.parametrize("plan", [None, PLAN], ids=["no-plan", "plan"])
def test_source_child_index_gains_the_sets_and_keeps_its_keys(tmp_path,
                                                              plan):
    spec = {"seed": SEED, "chain_id": "bench-churn", "n_vals": N_VALS,
            "n_blocks": N_BLOCKS, "n_sources": 1, "traffic": EMPTY}
    if plan:
        spec["valset"] = plan
    ready, index = benchutil.child_index(tmp_path, spec)
    assert set(ready) == {"ready", "genesis", "addrs", "build_s", "n_blocks",
                          "bytes"}
    assert set(index) == {"block_hash", "app_hash", "size", "signed",
                          "valsets"}
    assert index["signed"] == [N_VALS] * N_BLOCKS     # no `absent` plan
    built = _build(plan=plan)
    assert index["block_hash"] == [b.hex() for b in built["block_hash"]]
    assert index["app_hash"] == [b.hex() for b in built["app_hash"]]
    assert [s["from_height"] for s in index["valsets"]] == (
        [1, 4, 7, 10, 13] if plan else [1])
    for s in index["valsets"]:
        _seeds, vs = chain.valset_at(SEED, N_VALS, plan, s["from_height"])
        assert s["hash"] == vs.hash().hex()
        assert s["validators"] == [v.pub_key.bytes_.hex()
                                   for v in vs.validators]
    assert ready["genesis"]["validators"] == index["valsets"][0]["validators"]


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CONFIG_FILES = {c["name"]: c["file"] for c in json.load(_f)["configs"]}


@pytest.mark.parametrize("config", sorted(CONFIG_FILES))
def test_every_accepted_configuration_names_an_app_the_program_boots(config):
    """A configuration's `app` is a name in the program's registry of
    in-process apps, and `stated_as_run` accepts the node booted with it
    (`boot_node` hands it to the node's Config as `proxy_app`).
    `test_bench_churn_cell.py::test_every_configuration_states_an_app_the_program_has`
    overlaps: it holds the same two and, beside them, the harness's rule
    of which app goes with which mix (`cell.app_fits_plans`), the record
    of the apps the accepted configurations were accepted with, and the
    error on a node booted on another app."""
    from tendermint_tpu.config import Config
    with open(os.path.join(REPO, CONFIG_FILES[config])) as f:
        cfg = json.load(f)
    create_app(cfg["app"])
    booted = Config()
    booted.base.proxy_app = cfg["app"]
    cell_mod.stated_as_run(cfg, booted)


def test_a_node_whose_app_is_not_the_one_stated_is_an_error():
    from tendermint_tpu.config import Config
    with open(os.path.join(REPO, CONFIG_FILES["catchup-100v"])) as f:
        cfg = dict(json.load(f), app=VALSET_APP)
    with pytest.raises(RuntimeError, match="'app': \\('valset_kvstore', "
                       "'kvstore'\\)"):
        cell_mod.stated_as_run(cfg, Config())
    booted = Config()
    booted.base.proxy_app = VALSET_APP
    cell_mod.stated_as_run(cfg, booted)
    # a file that names no app is held to none, as before
    cell_mod.stated_as_run({k: v for k, v in cfg.items() if k != "app"},
                           Config())


def test_boot_node_hands_the_configurations_app_to_the_node(monkeypatch,
                                                           tmp_path):
    """`boot_node` is `cli node --proxy-app <app>`: the Node is built
    from a Config whose `proxy_app` is the file's, and from the
    program's default where the file names none."""
    from tendermint_tpu.node import node as node_mod
    seen = []
    monkeypatch.setattr(node_mod, "Node", lambda cfg: seen.append(
        cfg.base.proxy_app) or types.SimpleNamespace(config=cfg))
    gen = _genesis(_build())
    for i, app in enumerate((VALSET_APP, None)):
        home = str(tmp_path / f"node{i}")
        _node, cfg = cell_mod.boot_node(home, gen, [], app)
        assert os.path.exists(cfg.base.genesis_file())
    assert seen == [VALSET_APP, "kvstore"]
