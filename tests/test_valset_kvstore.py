"""`valset_kvstore`: the kvstore whose validator set the chain's own txs
change (`abci/apps/kvstore.py::ValsetKVStoreApp`), against the plain
reference the benchmark builds its chains with
(`benchmark/lib/chain.py`: `valset_txs`, `RefKVStore`; no code shared
with the program)."""

import os
import sys

import pytest

from tendermint_tpu.abci.app import create_app
from tendermint_tpu.abci.apps.kvstore import KVStoreApp, ValsetKVStoreApp
from tendermint_tpu.abci.types import OK

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.lib import chain  # noqa: E402

BLOCK = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
PUB = bytes(range(32))


def test_the_registry_has_it_beside_the_plain_kvstore():
    app = create_app("valset_kvstore")
    assert type(app) is ValsetKVStoreApp
    assert type(create_app("kvstore")) is KVStoreApp


def test_diffs_come_once_at_their_blocks_end_block():
    app = create_app("valset_kvstore")
    assert app.deliver_tx(b"k1=v1").code == OK
    assert app.end_block(1).diffs == []
    app.commit()
    gone, new = PUB.hex().encode(), bytes(reversed(PUB)).hex().encode()
    assert app.deliver_tx(b"val:" + gone + b"/0").code == OK
    assert app.deliver_tx(b"k2=v2").code == OK
    assert app.deliver_tx(b"val:" + new.upper() + b"/10").code == OK
    diffs = app.end_block(2).diffs
    assert [(d.pub_key, d.power) for d in diffs] == [
        (PUB, 0), (bytes(reversed(PUB)), 10)]
    app.commit()
    # returned once: the next block, which carries none, answers none
    assert app.end_block(3).diffs == []


@pytest.mark.parametrize("tx", [
    b"val:",                                         # nothing
    b"val:" + PUB.hex().encode(),                    # no power
    b"val:" + PUB.hex().encode() + b"/",             # empty power
    b"val:" + PUB.hex().encode() + b"/-1",           # a sign
    b"val:" + PUB.hex().encode() + b"/1_0",          # what int() takes
    b"val:" + PUB.hex().encode() + b"/ 10",
    b"val:" + PUB.hex().encode()[:62] + b"/10",      # 31 bytes
    b"val:" + PUB.hex().encode() + b"00/10",         # 33 bytes
    b"val:" + b"zz" + PUB.hex().encode()[2:] + b"/10",   # not hex
    b"val:" + b" " + PUB.hex().encode()[1:] + b"/10",    # fromhex takes it
])
def test_a_malformed_val_tx_is_a_result_code_and_no_diff(tx):
    app, plain = create_app("valset_kvstore"), create_app("kvstore")
    assert app.deliver_tx(b"k=v").code == OK and plain.deliver_tx(
        b"k=v").code == OK
    res = app.deliver_tx(tx)
    assert res.code != OK and "val:" in res.log
    assert app.end_block(1).diffs == []
    # and it stored nothing: the app hash is that of the block without it
    assert app.commit().data == plain.commit().data


@pytest.mark.parametrize("seed", [7, 2**31 + 36])
def test_app_hashes_over_a_seeded_churn_chain_are_the_references(seed):
    """The txs of 10 heights of a chain whose set changes every third
    block (two members of five): the diffs `EndBlock` returns are the
    reference's, height for height, and so is every app hash, because a
    `val:` tx is stored as any tx without `=`."""
    plan = {"change_every_blocks": 3, "swap": 2}
    app, ref = create_app("valset_kvstore"), chain.RefKVStore()
    changes = 0
    for h in range(1, 11):
        txs = chain.block_txs(BLOCK, seed, h) + chain.valset_txs(
            seed, 5, plan, h)
        for tx in txs:
            assert app.deliver_tx(tx).code == OK
            ref.deliver_tx(tx)
        diffs = [(d.pub_key, d.power) for d in app.end_block(h).diffs]
        assert diffs == ref.diffs
        assert len(diffs) == (4 if h % 3 == 0 else 0)
        changes += bool(diffs)
        assert app.commit().data == ref.commit()
    assert changes == 3
