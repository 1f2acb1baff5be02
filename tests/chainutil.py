"""Test helper: deterministic chain construction with real signatures.

The implementation moved to `tendermint_tpu/scenarios/fixtures.py` so
the fault-scenario engine (and `cli chaos`) can build chains outside
pytest; this module stays as the test suite's import point.
"""

from __future__ import annotations

from tendermint_tpu.scenarios.fixtures import (  # noqa: F401
    PART_SIZE, build_chain, kvstore_app_hashes, make_commit, make_genesis,
    make_validators, sign_vote)


def fast_sync_in_process(chain_id: str, n_blocks: int, batch_size: int,
                         sqlite_dir=None, timeout: float = 40.0,
                         n_vals: int = 4):
    """Fast-sync a fresh `n_blocks` chain of `n_vals` validators from one
    in-process source peer
    through the real reactors (pool, look-ahead, `apply_window`), with
    the python crypto backend; the syncer keeps its stores in sqlite
    under `sqlite_dir` when given.  Returns the syncer's
    BlockchainReactor once its store holds n_blocks - 1 (the tip waits
    for a successor)."""
    import time

    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.p2p import connect_switches, make_switch
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB, SQLiteDB

    privs, vs = make_validators(n_vals)
    gen = make_genesis(chain_id, privs)
    chain = build_chain(privs, vs, chain_id, n_blocks,
                        app_hashes=kvstore_app_hashes(n_blocks))

    def node(fast_sync, dbs, **kw):
        state = get_state(dbs[0], gen)
        conns = ClientCreator("kvstore").new_app_conns()
        store = BlockStore(dbs[1])
        reactor = BlockchainReactor(state, conns.consensus, store,
                                    fast_sync=fast_sync, **kw)
        return reactor, make_switch(chain_id, {"blockchain": reactor})

    src, src_sw = node(False, (MemDB(), MemDB()))
    for block, ps, seen in chain:
        src.store.save_block(block, ps, seen)
        execution.apply_block(src.state, None, src.proxy, block, ps.header,
                              execution.MockMempool(),
                              check_last_commit=False)
    dbs = (MemDB(), MemDB()) if sqlite_dir is None else (
        SQLiteDB(f"{sqlite_dir}/state.db"), SQLiteDB(f"{sqlite_dir}/blocks.db"))
    bc, sync_sw = node(True, dbs, batch_size=batch_size)
    old = cb._current
    cb.set_backend("python")
    src_sw.start()
    sync_sw.start()
    try:
        connect_switches(sync_sw, src_sw)
        deadline = time.time() + timeout
        # a block is stored before it is applied: wait for the app hash
        # too, or it is read a block early
        want = chain[-1][0].header.app_hash
        while ((bc.store.height < n_blocks - 1 or bc.state.app_hash != want)
               and time.time() < deadline):
            time.sleep(0.02)
        assert bc.store.height >= n_blocks - 1, bc.pool.status()
        assert bc.state.app_hash == want
    finally:
        src_sw.stop()
        sync_sw.stop()
        bc.stop()
        if bc._thread is not None:
            bc._thread.join(timeout=10)
        cb._current = old
    return bc
