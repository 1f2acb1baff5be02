"""Which app goes with which mix: `cell.app_fits_plans`, the harness's
own rule (PR 49).  A mix that states a `valset` or a `powers` plan puts
`val:<pubkey>/<power>` txs into its blocks, and the chain it builds moves
its sets by them; an app that stores them and returns no diff leaves the
node on the genesis set, and block 2's header names another.  So the
configuration's app has to return such a tx as an `EndBlock` diff exactly
where the mix states such a plan, which the harness asks of the APP (it
delivers one and reads the answer) and of no name: not the app's, not the
configuration's, not the cell's.  `run_cell` asks before anything is
started."""

import json
import os
import time

import pytest

from benchutil import REPO
from benchmark.lib import cell as cell_mod
from benchmark.lib import chain
from tendermint_tpu.abci import app as app_mod
from tendermint_tpu.abci.apps import counter, kvstore  # noqa: F401 - register
from tendermint_tpu.abci.types import ResponseEndBlock, Validator

DATA = os.path.join(REPO, "tests", "benchmark", "data")
VALSET = {"change_every_blocks": 200, "swap": 1}
POWERS = {"change_every_blocks": 1, "members": 3, "min": 1, "max": 100}
ABSENT = {"late_per_1000": 20}


def _json(*path) -> dict:
    with open(os.path.join(*path)) as f:
        return json.load(f)


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("name", sorted(app_mod._REGISTRY))
def test_the_probe_asks_each_app_of_the_registry(name, monkeypatch,
                                                 tmp_path):
    """One app of the program's returns a `val:` tx as a diff today; the
    persistent kvstore is the plain one on disk and returns none (and the
    probe, which commits nothing, writes nothing)."""
    db = tmp_path / "kvstore_app.json"
    monkeypatch.setenv("TM_KVSTORE_PATH", str(db))
    app = app_mod.create_app(name)
    assert cell_mod.returns_val_diffs(app) is (name == "valset_kvstore")
    assert not db.exists()
    # asked again of the same app, which has handed its diffs out
    assert cell_mod.returns_val_diffs(app) is (name == "valset_kvstore")


class _Diffs(app_mod.Application):
    """Not a kvstore and under no known name: returns what it is sent."""

    def __init__(self):
        self.seen = []

    def deliver_tx(self, tx):
        self.seen.append(chain.parse_val_tx(tx))
        return super().deliver_tx(tx)

    def end_block(self, height):
        seen, self.seen = self.seen, []
        return ResponseEndBlock(diffs=[Validator(*d) for d in seen])


class _WrongPower(_Diffs):
    def end_block(self, height):
        res = super().end_block(height)
        return ResponseEndBlock(diffs=[Validator(d.pub_key, d.power + 1)
                                       for d in res.diffs])


def test_the_rule_is_the_apps_behaviour_and_not_its_name(monkeypatch):
    monkeypatch.setitem(app_mod._REGISTRY, "made_up_diffs", _Diffs)
    monkeypatch.setitem(app_mod._REGISTRY, "valset_kvstore_2",
                        kvstore.KVStoreApp)
    monkeypatch.setitem(app_mod._REGISTRY, "made_up_wrong", _WrongPower)
    mix = {"name": "m", "powers": POWERS}
    cell_mod.app_fits_plans({"name": "c", "app": "made_up_diffs"}, mix)
    with pytest.raises(ValueError, match="'made_up_diffs' returns `val:`"):
        cell_mod.app_fits_plans({"name": "c", "app": "made_up_diffs"},
                                {"name": "m"})
    # a name that sounds right over an app that returns none, and an app
    # that returns another power than the tx's: neither is the diff
    for app in ("valset_kvstore_2", "made_up_wrong"):
        with pytest.raises(ValueError, match=f"{app!r} returns none"):
            cell_mod.app_fits_plans({"name": "c", "app": app}, mix)
        cell_mod.app_fits_plans({"name": "c", "app": app}, {"name": "m"})


@pytest.mark.parametrize("cfg,mix,match", [
    ({"app": "kvstore"}, {"powers": POWERS},
     "'c' states the app 'kvstore' under the mix 'm', which states a "
     "powers plan: .* 'kvstore' returns none"),
    ({"app": "kvstore"}, {"valset": VALSET},
     "'kvstore' .* a valset plan: .* 'kvstore' returns none"),
    # a file that names no app is held to the program's default
    ({}, {"valset": VALSET, "powers": POWERS},
     "'kvstore' .* a valset and a powers plan: .* returns none"),
    ({"app": "persistent_kvstore"}, {"powers": POWERS},
     "'persistent_kvstore' .* a powers plan: .* returns none"),
    # an app the mix does not need is another deployment
    ({"app": "valset_kvstore"}, {},
     "'valset_kvstore' under the mix 'm', which states no valset or "
     "powers plan: 'valset_kvstore' returns `val:` txs"),
    ({"app": "valset_kvstore"}, {"absent": ABSENT},
     "no valset or powers plan: 'valset_kvstore' returns"),
    ({"app": "no_such_app"}, {"powers": POWERS},
     "'no_such_app' .* a powers plan: unknown in-proc app 'no_such_app'"),
    ({"app": "tcp://127.0.0.1:46658"}, {},
     "no valset or powers plan: unknown in-proc app"),
], ids=["powers-on-kvstore", "valset-on-kvstore", "both-on-the-default",
        "powers-on-persistent-kvstore", "no-plan-on-valset-kvstore",
        "absent-alone-on-valset-kvstore", "not-in-the-registry",
        "not-in-process"])
def test_a_pairing_that_cannot_be_the_deployment_is_refused(cfg, mix, match,
                                                            monkeypatch):
    with pytest.raises(ValueError, match=match):
        cell_mod.app_fits_plans(dict(cfg, name="c"), dict(mix, name="m"))
    # and `run_cell` refuses it before a child is started: the chain is
    # built in the source child, 40-140 s at a cell's size
    monkeypatch.setattr(cell_mod.children_mod, "Children", lambda root: 1 / 0)
    monkeypatch.setattr(cell_mod, "chain_blocks", lambda *a: 1 / 0)
    cell = {"config": dict(cfg, name="c", validators=4, source_peers=1),
            "traffic": dict(mix, name="m", block={
                "txs_per_block": 1, "tx_bytes": 16, "keys": 7})}
    with pytest.raises(ValueError, match=match):
        cell_mod.run_cell(REPO, cell, 2**31 + 491, 1.0, False,
                          time.monotonic())


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_of_the_tree_is_a_pairing_the_rule_takes(name):
    cell = cell_mod.load_cell(REPO, name)
    cell_mod.app_fits_plans(cell["config"], cell["traffic"])


@pytest.mark.parametrize("cfg,mix", [
    (_json(DATA, "catchup-powers-100v.json"), _json(DATA, "power-drift.json")),
    ({"app": "valset_kvstore"}, {"valset": VALSET, "powers": POWERS}),
    ({"app": "valset_kvstore"}, {"valset": VALSET, "absent": ABSENT}),
    ({}, {"absent": ABSENT}),
    ({}, {}),
    # a plan that is there and empty is no plan
    ({}, {"valset": None, "powers": {}}),
], ids=["the-candidate", "both-plans", "valset-and-absent", "absent-alone",
        "no-app-no-plan", "empty-plans"])
def test_a_pairing_the_rule_takes(cfg, mix):
    cell_mod.app_fits_plans(cfg, mix)


class _Reached(Exception):
    pass


class _Kids:
    """Stands in `children.Children`: the first child to be started is
    the source child, which builds the chain."""

    def __init__(self, root):
        pass

    def start(self, module, *args):
        raise _Reached(module)

    def stop_all(self):
        pass


def test_run_cell_checks_the_plans_first_and_the_app_before_the_chain(
        monkeypatch):
    """A malformed plan is named as such whatever the app; a sound
    pairing gets as far as the child that builds the chain."""
    import types
    monkeypatch.setattr(cell_mod.children_mod, "Children", _Kids)
    monkeypatch.setattr(cell_mod, "start_watchdog",
                        lambda kids: types.SimpleNamespace(cancel=lambda: 0))
    block = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
    chain_plan = {"default": {"parent_blocks_per_s": 10, "warmup_s": 1}}

    def run(cfg, mix):
        cell = {"config": dict(cfg, validators=4, source_peers=1),
                "config_name": "c", "traffic_name": "m",
                "traffic": dict(mix, block=block, chain=chain_plan)}
        cell_mod.run_cell(REPO, cell, 2**31 + 492, 1.0, False,
                          time.monotonic())

    with pytest.raises(ValueError, match="powers plan"):
        run({"app": "kvstore"}, {"powers": dict(POWERS, members=0)})
    with pytest.raises(ValueError, match="returns none"):
        run({"app": "kvstore"}, {"powers": POWERS})
    for cfg, mix in (({"app": "valset_kvstore"}, {"powers": POWERS}),
                     ({}, {})):
        with pytest.raises(_Reached, match="source_child"):
            run(cfg, mix)
