"""What a `C:` row of the block store holds (`blockchain/store.py`): the
marker `b""` wherever block h's `last_commit` is byte for byte the seen
commit the store wrote for h-1 one call before, the whole encoding
wherever it is not, and `load_block_commit` gives the same commit back
either way: across a reopened store, a bootstrap, a prune, a failed
write, and a store whose every row is whole (the format before the
marker)."""

from types import SimpleNamespace

import pytest

from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.types import EMPTY_COMMIT, Block, Commit
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.db import MemDB, SQLiteDB
from tendermint_tpu.utils.metrics import REGISTRY

from chainutil import (build_chain, kvstore_app_hashes, make_genesis,
                       make_validators)

CHAIN, N_BLOCKS = "block-store-chain", 7
MARKER = b""


@pytest.fixture(scope="module")
def chains():
    """4 and 100 validators: [(block, part set, seen commit)], one tx a
    block, each seen commit the next block's `last_commit`."""
    out = {}
    for n in (4, 100):
        privs, vs = make_validators(n)
        out[n] = build_chain(privs, vs, CHAIN, N_BLOCKS, txs_per_block=1,
                             app_hashes=kvstore_app_hashes(N_BLOCKS, 1))
    return out


@pytest.fixture(params=["memdb", "sqlite"])
def new_db(request, tmp_path):
    """A factory: a call with the same name opens the same database."""
    opened = {}

    def make(name="blocks"):
        if request.param == "sqlite":
            return SQLiteDB(str(tmp_path / f"{name}.db"))
        return opened.setdefault(name, MemDB())
    return make


def _as_fast_sync_gives_them(chain):
    """The chain as a syncing node holds it: every block decoded from its
    served bytes, the seen commit of h the `last_commit` OBJECT of block
    h + 1 (`blockchain/reactor.py`), the tip's own from the chain."""
    blocks = [Block.decode_bytes(b.encode()) for b, _ps, _seen in chain]
    seens = [nxt.last_commit for nxt in blocks[1:]] + [chain[-1][2]]
    return [(b, ps, seen)
            for b, (_b, ps, _s), seen in zip(blocks, chain, seens)]


def _save(store, triples):
    for block, ps, seen in triples:
        store.save_block(block, ps, seen)


def _c_row(store, h):
    return store.db.get(b"C:%d" % h)


def _recorded(t0, name):
    return [s for s in tracing.RECORDER.since(t0)
            if s["name"] == name and s["ts"] >= t0]


def _loads_the_chain(store, chain, lo=1):
    """Every commit from `lo` up: the block's for a height below the top,
    the seen one at every height, equal to and encoding as the chain's."""
    for h in range(lo, store.height + 1):
        want = chain[h - 1][2]
        got = [store.load_seen_commit(h)]
        if h < store.height:
            got.append(store.load_block_commit(h))
            assert store.load_block(h + 1).last_commit == want
        for commit in got:
            assert commit == want and commit.encode() == want.encode()
    assert store.load_block_commit(store.height) is None


def _routes(block_store):
    from tendermint_tpu.rpc.routes import Routes
    return Routes(SimpleNamespace(
        block_store=block_store,
        config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False))))


def _parent_rows(chain, db):
    """The rows the store wrote before the marker existed: this store's,
    with every `C:` row put back whole by hand."""
    _save(BlockStore(db), chain)
    for block, _ps, _seen in chain:
        db.set(b"C:%d" % block.height, block.last_commit.encode())
    return db


# -- (a) a fast-sync shaped chain ------------------------------------------

@pytest.mark.parametrize("n_vals", [4, 100])
@pytest.mark.parametrize("shape", ["objects", "decoded"])
def test_every_commit_of_a_fast_synced_chain_is_the_marker(
        chains, new_db, n_vals, shape):
    chain = chains[n_vals]
    triples = chain if shape == "objects" else _as_fast_sync_gives_them(chain)
    store = BlockStore(new_db())
    aliased0 = REGISTRY.blockstore_commits_aliased.value
    t0 = tracing.now_epoch()
    _save(store, triples)
    # block 1 carries the empty commit and no seen commit came before it
    assert _c_row(store, 1) == EMPTY_COMMIT.encode() != MARKER
    assert [_c_row(store, h) for h in range(2, N_BLOCKS + 1)] == \
        [MARKER] * (N_BLOCKS - 1)
    assert REGISTRY.blockstore_commits_aliased.value - aliased0 == \
        N_BLOCKS - 1
    instants = _recorded(t0, "store.commit_alias")
    assert len(instants) == N_BLOCKS - 1
    assert all(s["ph"] == "i" and "args" not in s for s in instants)
    # every seen commit is whole, whatever its height
    for h in range(1, N_BLOCKS + 1):
        assert store.db.get(b"SC:%d" % h) == chain[h - 1][2].encode()
    _loads_the_chain(store, chain)
    _loads_the_chain(BlockStore(new_db()), chain)
    # `/commit` answers what it answers on a store of whole rows
    here = _routes(store)
    whole = _routes(BlockStore(_parent_rows(chain, new_db("parent"))))
    for h in range(1, N_BLOCKS + 1):
        got = here.commit({"height": h})
        assert got == whole.commit({"height": h})
        assert got == {"canonical": h != N_BLOCKS, "precommits": n_vals,
                       "block_id": {"hash": chain[h - 1][0].hash().hex()},
                       "height": h}


# -- (b) a seen commit that is not the next block's last commit ------------

@pytest.mark.parametrize("n_vals", [4, 100])
def test_a_seen_commit_of_other_precommits_leaves_the_row_whole(
        chains, new_db, n_vals):
    """The live path: the node saw +2/3 with one precommit fewer than
    the next proposer put into its `LastCommit`."""
    chain = chains[n_vals]
    store = BlockStore(new_db())
    _save(store, chain[:2])
    block, ps, seen = chain[2]
    fewer = Commit(block_id=seen.block_id,
                   precommits=[None] + list(seen.precommits[1:]))
    assert fewer.encode() != chain[3][0].last_commit.encode()
    store.save_block(block, ps, fewer)
    aliased0 = REGISTRY.blockstore_commits_aliased.value
    _save(store, chain[3:5])
    assert _c_row(store, 4) == chain[3][0].last_commit.encode()
    assert _c_row(store, 5) == MARKER
    assert REGISTRY.blockstore_commits_aliased.value - aliased0 == 1
    # the BLOCK's commit, not the one this node saw
    got = store.load_block_commit(3)
    assert got == seen and got.num_sigs() == n_vals
    assert store.load_seen_commit(3) == fewer != got
    assert store.load_seen_commit(3).num_sigs() == n_vals - 1
    assert store.load_block_commit(4) == chain[3][2]


# -- (c) across a reopened and a bootstrapped store ------------------------

@pytest.mark.parametrize("n_vals", [4, 100])
def test_the_first_save_of_a_reopened_store_is_whole_the_next_a_marker(
        chains, new_db, n_vals):
    chain = chains[n_vals]
    _save(BlockStore(new_db()), chain[:3])
    store = BlockStore(new_db())
    assert store.height == 3
    _save(store, chain[3:])
    assert _c_row(store, 3) == MARKER
    assert _c_row(store, 4) == chain[3][0].last_commit.encode()
    assert [_c_row(store, h) for h in range(5, N_BLOCKS + 1)] == \
        [MARKER] * (N_BLOCKS - 4)
    _loads_the_chain(store, chain)
    _loads_the_chain(BlockStore(new_db()), chain)


@pytest.mark.parametrize("n_vals", [4, 100])
def test_the_first_save_after_a_bootstrap_is_whole_the_next_a_marker(
        chains, new_db, n_vals):
    chain = chains[n_vals]
    store = BlockStore(new_db())
    store.bootstrap(2)
    _save(store, chain[2:])
    assert _c_row(store, 3) == chain[2][0].last_commit.encode()
    assert [_c_row(store, h) for h in range(4, N_BLOCKS + 1)] == \
        [MARKER] * (N_BLOCKS - 3)
    _loads_the_chain(store, chain, lo=3)
    # block 3 brought the commit of the snapshot's height, as ever
    assert store.load_block_commit(2) == chain[1][2]
    assert store.load_seen_commit(2) is None
    assert store.load_block_commit(1) is None


# -- (d) prune --------------------------------------------------------------

@pytest.mark.parametrize("n_vals", [4, 100])
@pytest.mark.parametrize("retain", [3, 5, N_BLOCKS])
def test_the_commit_below_a_pruned_base_comes_from_the_blocks_parts(
        chains, new_db, n_vals, retain):
    """`prune(k)` takes `SC:k-1` and leaves `C:k`: a marker there is
    followed into block k's own bytes, which end with the same commit."""
    chain = chains[n_vals]
    store = BlockStore(new_db())
    _save(store, chain)
    assert store.prune(retain) == retain - 1
    for store in (store, BlockStore(new_db())):
        assert store.base == retain and _c_row(store, retain) == MARKER
        assert store.load_seen_commit(retain - 1) is None
        got = store.load_block_commit(retain - 1)
        assert got == chain[retain - 2][2]
        assert got.encode() == chain[retain - 2][2].encode()
        for h in range(0, retain - 1):
            assert store.load_block_commit(h) is None
            assert store.load_seen_commit(h) is None
        _loads_the_chain(store, chain, lo=retain)


def test_a_store_pruned_to_its_tip_takes_the_next_block(chains, new_db):
    """Everything below height + 1 pruned, `SC:height` with it: the next
    save still compares with what THIS object wrote, and the marker it
    writes is read through block height + 1."""
    chain = chains[4]
    store = BlockStore(new_db())
    _save(store, chain[:4])
    assert store.prune(5) == 4
    _save(store, chain[4:])
    assert _c_row(store, 5) == MARKER
    assert store.load_block_commit(4) == chain[3][2]
    _loads_the_chain(store, chain, lo=5)


# -- (e) a store written before the marker existed -------------------------

@pytest.mark.parametrize("n_vals", [4, 100])
def test_a_store_of_whole_rows_loads_as_it_did(chains, new_db, n_vals):
    chain = chains[n_vals]
    db = _parent_rows(chain[:5], new_db())
    store = BlockStore(db)
    assert all(_c_row(store, h) == chain[h - 1][0].last_commit.encode()
               != MARKER for h in range(1, 6))
    _loads_the_chain(store, chain)
    routes = _routes(store)
    for h in range(1, 6):
        assert routes.commit({"height": h})["precommits"] == n_vals
    # and goes on under this build: whole, then markers
    _save(store, chain[5:])
    assert _c_row(store, 6) == chain[5][0].last_commit.encode()
    assert _c_row(store, 7) == MARKER
    _loads_the_chain(BlockStore(db), chain)


def test_a_node_restarts_on_a_store_of_whole_rows_and_on_one_of_markers(
        chains, new_db):
    """The handshake replays the stored blocks into a fresh app and the
    consensus state rebuilds its last commit from `SC:` of the top: the
    same on the parent's rows and on this build's."""
    from tendermint_tpu.config import test_config
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.consensus.state import ConsensusState
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    chain = chains[4]
    privs, _vs = make_validators(4)
    written = BlockStore(new_db())
    _save(written, chain)
    old = cb._current
    cb.set_backend("python")
    try:
        for db in (_parent_rows(chain, new_db("parent")), written.db):
            store = BlockStore(db)
            state = get_state(MemDB(), make_genesis(CHAIN, privs))
            conns = ClientCreator("kvstore").new_app_conns()
            for block, ps, _seen in chain:
                execution.apply_block(state, None, conns.consensus, block,
                                      ps.header, execution.MockMempool(),
                                      check_last_commit=False)
            # the restart: the state as it was saved, a fresh app
            conns = ClientCreator("kvstore").new_app_conns()
            Handshaker(state, store).handshake(conns)
            assert state.last_block_height == N_BLOCKS
            assert state.app_hash == conns.query.info().last_block_app_hash
            cs = ConsensusState(test_config().consensus, state,
                                conns.consensus, store,
                                Mempool(conns.mempool))
            assert cs.last_commit.make_commit() == chain[-1][2]
    finally:
        cb._current = old


# -- (f) a write that fails -------------------------------------------------

class _FailsOnce:
    """A db whose next `set_batch` raises and writes nothing."""

    def __init__(self, db):
        self._db, self.fail_next = db, False

    def set_batch(self, kvs):
        if self.fail_next:
            self.fail_next = False
            raise OSError("disk full")
        self._db.set_batch(kvs)

    def __getattr__(self, name):
        return getattr(self._db, name)


@pytest.mark.parametrize("n_vals", [4, 100])
def test_a_failed_write_moves_neither_the_height_nor_what_is_compared(
        chains, new_db, n_vals):
    chain = chains[n_vals]
    db = _FailsOnce(new_db())
    store = BlockStore(db)
    _save(store, chain[:3])
    # a fork's block 4 with another seen commit fails to be written ...
    block, ps, seen = chain[3]
    other = Commit(block_id=seen.block_id,
                   precommits=list(seen.precommits[:-1]) + [None])
    aliased0 = REGISTRY.blockstore_commits_aliased.value
    db.fail_next = True
    with pytest.raises(OSError, match="disk full"):
        store.save_block(block, ps, other)
    assert store.height == 3 and _c_row(store, 4) is None
    assert REGISTRY.blockstore_commits_aliased.value == aliased0
    # ... the retry aliases against `SC:3`, which was written, and block
    # 5 against the `SC:4` of the retry, not of the save that failed
    store.save_block(block, ps, seen)
    assert _c_row(store, 4) == MARKER
    db.fail_next = True
    with pytest.raises(OSError, match="disk full"):
        store.save_block(*chain[4])
    store.save_block(*chain[4])
    assert _c_row(store, 5) == MARKER and store.height == 5
    _loads_the_chain(store, chain)

    # a save that failed after a seen commit of OTHER bytes was written:
    # the next block's row is whole, as no `SC:` row holds its bytes
    store = BlockStore(_FailsOnce(new_db("second")))
    _save(store, chain[:3])
    store.save_block(block, ps, other)
    store.db.fail_next = True
    with pytest.raises(OSError, match="disk full"):
        store.save_block(*chain[4])
    store.save_block(*chain[4])
    assert _c_row(store, 5) == chain[4][0].last_commit.encode()
    assert store.load_block_commit(4) == seen != store.load_seen_commit(4)


# -- (g) the bytes of one save ------------------------------------------------

class _Recording:
    """A db that keeps every batch it is given."""

    def __init__(self, db):
        self._db, self.batches = db, []

    def set_batch(self, kvs):
        self.batches.append(list(kvs))
        self._db.set_batch(kvs)

    def __getattr__(self, name):
        return getattr(self._db, name)


@pytest.mark.parametrize("n_vals,whole,aliased", [
    (4, (2_850, 2_950), (2_030, 2_130)),
    (100, (56_300, 56_600), (37_600, 37_900))])
def test_one_save_is_one_write_and_a_commit_less(chains, tmp_path, n_vals,
                                                 whole, aliased):
    """Two thirds of a save's bytes were one commit three times (`SC:h-1`
    a call before, inside `P:h:0`, `C:h`): the marker takes the third
    copy, 18,680 B at 100 validators, out of the same one transaction."""
    chain = chains[n_vals]
    db = _Recording(SQLiteDB(str(tmp_path / "blocks.db")))
    store = BlockStore(db)
    _save(store, chain[:2])
    reopened = BlockStore(db)
    t0 = tracing.now_epoch()
    reopened.save_block(*chain[2])         # whole: the parent's batch
    reopened.save_block(*chain[3])
    assert len(_recorded(t0, "db.write")) == 2 and len(db.batches) == 4

    def size(batch):
        return sum(len(k) + len(v) for k, v in batch)
    commit = len(chain[2][2].encode())
    assert whole[0] <= size(db.batches[2]) <= whole[1]
    assert aliased[0] <= size(db.batches[3]) <= aliased[1]
    assert size(db.batches[2]) - size(db.batches[3]) == commit
    for batch in db.batches[2:]:
        keys = [k.split(b":")[0] for k, _v in batch]
        assert keys == [b"H", b"P", b"C", b"SC", b"blockStore"]
    assert Commit.decode(Reader(dict(db.batches[2])[b"C:3"])) == chain[1][2]
    with pytest.raises(ValueError):
        Commit.decode(Reader(MARKER))
