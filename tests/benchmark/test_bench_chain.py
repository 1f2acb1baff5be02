"""The chain builder (`benchmark/lib/chain.py`): what it serves is a
chain the program accepts, made from the seed, with app hashes from a
reference that agrees with the program's kvstore."""

import hashlib

import numpy as np
import pytest

import benchutil  # noqa: F401
from benchmark.lib import chain
from tendermint_tpu.abci.app import create_app
from tendermint_tpu.crypto import native
from tendermint_tpu.types import Block

EMPTY = {"txs_per_block": 1, "tx_bytes": 16, "keys": 7}
FULL = {"txs_per_block": 40, "tx_bytes": 250, "keys": 40}


@pytest.mark.parametrize("block", [EMPTY, FULL], ids=["empty", "full"])
def test_txs_have_the_size_and_key_reuse_the_mix_states(block):
    txs = chain.block_txs(block, seed=2**31 + 3, h=12345)
    assert len(txs) == block["txs_per_block"]
    assert all(len(t) == block["tx_bytes"] for t in txs)
    keys = {t.split(b"=")[0] for h in range(1, 30)
            for t in chain.block_txs(block, 5, h)}
    assert len(keys) == block["keys"]
    assert txs == chain.block_txs(block, seed=2**31 + 3, h=12345)
    assert txs != chain.block_txs(block, seed=2**31 + 4, h=12345)


def test_reference_kvstore_gives_the_programs_app_hashes():
    ref, app = chain.RefKVStore(), create_app("kvstore")
    for h in range(1, 6):
        for tx in chain.block_txs(FULL, 9, h) + [b"lonely-value"]:
            ref.deliver_tx(tx)
            app.deliver_tx(tx)
        assert ref.commit() == app.commit().data


@pytest.mark.parametrize("workers", [0, 2], ids=["in-process", "workers"])
def test_built_chain_is_linked_signed_by_openssl_and_repeatable(workers):
    seeds, vs = chain.make_validators(21, 5)
    with chain.Signers(seeds, workers) as sg:
        built = chain.build_chain("bench-t", seeds, vs, 6, FULL, 21, sg)
    with chain.Signers(seeds, 0) as sg:
        again = chain.build_chain("bench-t", seeds, vs, 6, FULL, 21, sg)
    assert built["encoded"] == again["encoded"]
    assert built["app_hash"] == again["app_hash"]
    app = create_app("kvstore")
    prev = None
    for i, enc in enumerate(built["encoded"]):
        block = Block.decode_bytes(enc)
        assert block.height == i + 1
        assert block.hash() == built["block_hash"][i]
        block.validate_basic()
        if prev is not None:
            # the block embeds its predecessor's +2/3, every signature
            # valid to OpenSSL over the program's canonical sign-bytes
            assert block.header.last_block_id.hash == prev.hash()
            assert block.header.app_hash == built["app_hash"][i - 1]
            votes = block.last_commit.precommits
            assert len(votes) == 5
            for v, val in zip(votes, vs.validators):
                assert native.verify_one(val.pub_key.bytes_,
                                         v.sign_bytes("bench-t"),
                                         v.signature)
        for tx in block.txs:
            app.deliver_tx(tx)
        assert app.commit().data == built["app_hash"][i]
        prev = block


def test_genesis_round_trips_and_orders_validators_as_the_set_does():
    seeds, vs = chain.make_validators(3, 7)
    g = chain.genesis_dict("c", vs)
    doc = chain.genesis_doc(g)
    assert doc.validator_set().hash() == vs.hash()
    assert [hashlib.sha256(s).digest() for s in seeds] != []
    pubs = np.frombuffer(b"".join(bytes.fromhex(p) for p in g["validators"]),
                         np.uint8).reshape(7, 32)
    assert (pubs == vs.pubs_matrix()).all()
