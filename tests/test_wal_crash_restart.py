"""Crash mid-record-write: torn WAL frames, restart, and catch-up.

`tests/test_wal_corruption.py` pins mid-file corruption (flipped bits in
committed frames).  This file pins the OTHER failure shape the
crash-restart storm injects: a writer killed between write() calls
leaves a torn frame at the tail — a valid-looking header promising more
bytes than follow.  Recovery must drop exactly the torn frame, a
restarted node must keep committing, and a node that fell behind while
down must catch up over fast-sync.

Also pins the CommitFormatError blame path the scenario harness
surfaced: a STALE commit (wrong height — a replayed finality proof)
must raise a typed error carrying the height, not a bare ValueError
that the sync loop can only log (which used to stall the pool forever).
"""

import contextlib
import os
import struct
import time

import pytest

from tendermint_tpu.consensus.wal import REC_ENDHEIGHT, REC_MESSAGE, WAL
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.scenarios import fixtures, harness, injectors

pytestmark = pytest.mark.faults


@contextlib.contextmanager
def _python_backend():
    old = cb._current
    cb.set_backend("python")
    try:
        yield
    finally:
        cb._current = old


class _StubCtx:
    """Just enough ScenarioContext for an injector outside the engine."""

    def __init__(self):
        self.notes = []

    def note(self, event, **fields):
        self.notes.append({"event": event, **fields})

    plan = note


def _write_wal(path, heights=3, msgs_per_height=3):
    w = WAL(path)
    expect = []
    for h in range(1, heights + 1):
        for i in range(msgs_per_height):
            payload = bytes([h, i]) * (10 + i)
            w.save_message(payload)
            expect.append((REC_MESSAGE, payload))
        w.write_end_height(h)
        expect.append((REC_ENDHEIGHT, struct.pack(">Q", h)))
    w.close()
    return expect


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_torn_tail_frame_recovery(tmp_path, seed):
    """tear_wal_tail appends a frame whose header promises more bytes
    than were written (and, in its page-cache variant, also cuts the
    real tail mid-frame).  read_all must recover every intact record
    and fsck must flag the garbage without inventing records."""
    import random
    path = str(tmp_path / "cs.wal")
    expect = _write_wal(path)
    ctx = _StubCtx()
    injectors.tear_wal_tail(ctx, path, random.Random(seed))
    (note,) = ctx.notes
    assert note["event"] == "wal.torn"
    # variant 1 truncates the previous tail mid-frame first, losing the
    # last committed record; variant 0 only appends the torn frame
    want = expect[:-1] if note["variant"] else expect
    assert WAL.read_all(path) == want
    report = WAL.fsck(path)
    assert report["records"] == len(want)
    assert report["tail_garbage"] or report["bad_regions"]


def test_node_restarts_past_torn_wal_tail(tmp_path):
    """One crash-restart cycle on a real sqlite-backed node: run, tear
    the WAL tail (SIGKILL mid-write), restart — the node must replay
    past the torn frame, keep the committed prefix byte-identical, and
    keep committing."""
    import random
    home = str(tmp_path / "home")
    n1 = harness.solo_node(home, "torn-chain")
    n1.start()
    try:
        assert harness.wait_until(lambda: n1.block_store.height >= 3,
                                  timeout=60), "seed node never reached 3"
        h1 = n1.block_store.height
        prefix = {h: n1.block_store.load_block(h).hash()
                  for h in range(1, h1 + 1)}
    finally:
        n1.stop()

    wal_path = os.path.join(home, "data", "cs.wal")
    injectors.tear_wal_tail(_StubCtx(), wal_path, random.Random(5))

    n2 = harness.solo_node(home, "torn-chain")
    n2.start()
    try:
        assert harness.wait_until(
            lambda: n2.block_store.height >= h1 + 2, timeout=60), \
            f"restarted node stuck at {n2.block_store.height} (was {h1})"
        for h, bh in prefix.items():
            assert n2.block_store.load_block(h).hash() == bh, \
                f"restart rewrote committed block {h}"
    finally:
        n2.stop()


N_CATCHUP_BLOCKS = 12
PRE_CRASH_HEIGHT = 4


def test_crashed_node_catches_up_over_fastsync(tmp_path):
    """A node that crashed at height 4 while the network reached 11
    must resume FAST-SYNC from its persisted height (not height 0) and
    converge byte-identically, app hash included."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.p2p.switch import connect_switches, make_switch
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.utils.db import MemDB

    chain_id = "catchup-chain"
    with _python_backend():
        privs, vs = fixtures.make_validators(4, seed=9)
        gen = fixtures.make_genesis(chain_id, privs)
        hashes = fixtures.kvstore_app_hashes(N_CATCHUP_BLOCKS)
        chain = fixtures.build_chain(privs, vs, chain_id, N_CATCHUP_BLOCKS,
                                     app_hashes=hashes)
        src_sw, _, src_store = harness.fastsync_source(chain_id, chain, gen)

        # the restarted node: store + state already advanced to the
        # pre-crash height, exactly what Node.__init__ reloads from disk
        state = get_state(MemDB(), gen)
        conns = ClientCreator("kvstore").new_app_conns()
        store = BlockStore(MemDB())
        for block, ps, seen in chain[:PRE_CRASH_HEIGHT]:
            store.save_block(block, ps, seen)
            execution.apply_block(state, None, conns.consensus, block,
                                  ps.header, execution.MockMempool(),
                                  check_last_commit=False)
        assert store.height == PRE_CRASH_HEIGHT
        bc = BlockchainReactor(state, conns.consensus, store,
                               fast_sync=True, batch_size=4)
        assert bc.pool.next_height == PRE_CRASH_HEIGHT + 1
        sync_sw = make_switch(chain_id, {"blockchain": bc},
                              moniker="restarted")
        src_sw.start()
        sync_sw.start()
        try:
            connect_switches(sync_sw, src_sw)
            deadline = time.time() + 60
            # the store moves a block before the state does: wait for
            # both, or the app hash below is read between the two
            while ((store.height < N_CATCHUP_BLOCKS - 1
                    or bc.state.last_block_height < N_CATCHUP_BLOCKS - 1)
                   and time.time() < deadline):
                time.sleep(0.02)
            assert store.height >= N_CATCHUP_BLOCKS - 1, \
                f"catch-up stalled at {store.height}"
            for h in range(1, N_CATCHUP_BLOCKS - 1):
                assert (store.load_block(h).hash()
                        == src_store.load_block(h).hash()), h
            assert bc.state.app_hash == hashes[-1]
        finally:
            src_sw.stop()
            sync_sw.stop()


def test_stale_commit_raises_typed_format_error():
    """A commit replayed for the wrong height must surface as
    CommitFormatError carrying the claimed height — the reactor maps it
    to redo(height+1), evicting the deliverer instead of stalling."""
    from tendermint_tpu.types.validator import (CommitFormatError,
                                                verify_commits_batched)
    chain_id = "fmt-chain"
    with _python_backend():
        privs, vs = fixtures.make_validators(4, seed=8)
        chain = fixtures.build_chain(privs, vs, chain_id, 5)
        stale = chain[3][2]                  # seen-commit for height 4
        with pytest.raises(CommitFormatError) as ei:
            verify_commits_batched(vs, chain_id,
                                   [(stale.block_id, 2, stale)])
    assert ei.value.height == 2
    assert isinstance(ei.value, ValueError)  # callers that caught the
    # old bare ValueError still do
