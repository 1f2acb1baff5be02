"""Block validation and execution against the ABCI app.

Reference: `state/execution.go` — `ApplyBlock` (`:210`) = validate ->
exec txs on the consensus conn -> index txs -> save ABCIResponses ->
update validator set from EndBlock diffs (`:117-156`) ->
`CommitStateUpdateMempool` with the mempool locked across the app Commit
(`:248-271`) -> save state; `validateBlock` verifies LastCommit with
LastValidators.VerifyCommit (`:177-202`) — here one batched device call;
`ExecCommitBlock` for fast replay (`:291-308`).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from tendermint_tpu.abci.types import RequestBeginBlock
from tendermint_tpu.state.state import ABCIResponses, State
from tendermint_tpu.types import BlockID
from tendermint_tpu.types.events import EventCache, event_tx
from tendermint_tpu.utils.fail import fail_point
from tendermint_tpu.utils.tracing import CAT_NONE, RECORDER, perf_to_epoch


class MockMempool:
    """No-op mempool for replay paths (reference `types/services.go:31-42`)."""

    def lock(self):
        pass

    def unlock(self):
        pass

    def update(self, height: int, txs: list[bytes]):
        pass


@dataclass
class TxEvent:
    """Payload of a per-tx event (fired during exec, flushed post-commit)."""
    height: int
    tx: bytes
    result: object
    index: int


def validate_block(state: State, block, check_last_commit: bool = True) -> None:
    """Full contextual validation (reference `state/execution.go:173-202`).

    `check_last_commit=False` skips the +2/3 signature verification — for
    the fast-sync pipeline, which verifies every commit in a batched
    device call BEFORE applying (so re-verifying here would double the
    dominant cost; the reference does pay it twice,
    `blockchain/reactor.go:230` then `state/execution.go:177-202`).
    """
    block.validate_basic()
    h = block.header
    if h.chain_id != state.chain_id:
        raise ValueError(f"wrong chain id {h.chain_id!r}")
    if h.height != state.last_block_height + 1:
        raise ValueError(f"wrong height {h.height}, "
                         f"expected {state.last_block_height + 1}")
    if h.last_block_id.key() != state.last_block_id.key():
        raise ValueError("wrong last_block_id")
    if h.app_hash != state.app_hash:
        raise ValueError(f"wrong app_hash {h.app_hash.hex()} "
                         f"!= {state.app_hash.hex()}")
    if h.validators_hash != state.validators.hash():
        raise ValueError("wrong validators_hash")
    if h.height > 1:
        if block.last_commit.size() != state.last_validators.size():
            raise ValueError("last_commit size != last validator set")
        if check_last_commit:
            # THE hot verification: +2/3 of last_validators signed last
            state.last_validators.verify_commit(
                state.chain_id, h.last_block_id, h.height - 1,
                block.last_commit)


def exec_block_on_app(proxy_consensus, block, event_cache: EventCache | None):
    """BeginBlock / DeliverTx xN / EndBlock (reference
    `state/execution.go:43-115`); returns ABCIResponses."""
    proxy_consensus.begin_block(
        RequestBeginBlock(hash=block.hash(), header=block.header))
    results = []
    for i, tx in enumerate(block.txs):
        res = proxy_consensus.deliver_tx(tx)
        results.append(res)
        if event_cache is not None:
            from tendermint_tpu.types.tx import Tx
            event_cache.fire(event_tx(Tx(tx).hash),
                             TxEvent(block.height, tx, res, i))
    end = proxy_consensus.end_block(block.height)
    diffs = [(v.pub_key, v.power) for v in end.diffs]
    return ABCIResponses(height=block.height, deliver_txs=results,
                         end_block_diffs=diffs)


def apply_block(state: State, event_cache, proxy_consensus, block,
                part_set_header, mempool, tx_indexer=None,
                check_last_commit: bool = True) -> State:
    """Validate, execute, commit one block; returns the advanced state
    (reference `state/execution.go:210-245`).  Mutates `state` in place
    and persists it; callers pass a copy if they need the old one."""
    validate_block(state, block, check_last_commit=check_last_commit)
    fail_point("ApplyBlock.validated")
    resp = exec_block_on_app(proxy_consensus, block, event_cache)
    fail_point("ApplyBlock.executed")
    if tx_indexer is not None:
        tx_indexer.index_block(block, resp)
    state.save_abci_responses(resp)
    fail_point("ApplyBlock.savedResponses")
    block_id = BlockID(hash=block.hash(), parts=part_set_header)
    state.set_block_and_validators(block.header, block_id,
                                   resp.end_block_diffs)
    # commit the app + update mempool under its lock
    commit_state_update_mempool(state, proxy_consensus, block, mempool)
    fail_point("ApplyBlock.committed")
    state.save()
    return state


def _stage(name: str, t0: float) -> float:
    """Record the apply stage `name` from `t0` (perf_counter) to now and
    return now: the steps of a block are contiguous, so each stage
    starts where the last ended, one clock read a boundary.  A bare
    record() with no args (the span() context manager costs about three
    times as much, 512 times a window) and CAT_NONE: the stages nest
    under the reactor's `fastsync.apply`, which carries the category, so
    the attribution has their wall clock already and skips them."""
    t1 = time.perf_counter()
    RECORDER.record(name, perf_to_epoch(t0), t1 - t0, None, cat=CAT_NONE)
    return t1


def apply_window(state: State, event_cache, proxy_consensus, items,
                 mempool, tx_indexer=None, check_last_commit: bool = False,
                 before_block=None, on_applied=None, stop_when=None) -> int:
    """Apply a verified fast-sync WINDOW of blocks (`items` =
    [(block, part_set_header)]) — `apply_block` unrolled across the
    window so the per-block overheads amortize: the consensus conn's
    lock is acquired ONCE for the whole window (via `AppConn.batched`,
    when the conn offers it) instead of ~4 round-trips per block.

    Per-block semantics are otherwise identical — same validation, same
    fail points, same mempool locking around each app Commit, one
    `state.save()` a block (the store is never more than one block ahead
    of the state, which is what the handshake can recover) — so crash
    tests and fault injection see the same sequence.  Hooks:
    `before_block(block, psh)` runs pre-validate (the reactor saves to
    the block store here, keeping store-before-state); `on_applied(block)`
    runs after each block's commit; `stop_when()` (checked after
    on_applied) ends the window early — the reactor stops when the
    validator set changes, since later blocks were verified against a
    stale set.  Returns the number of blocks applied.

    Every step of every block is one flight-recorder record,
    `fastsync.apply.<stage>` (`_stage`), so the eight stages of a window
    sum to the reactor's `fastsync.apply` span around this call.
    """
    batched = getattr(proxy_consensus, "batched", None)
    if batched is None:
        from contextlib import nullcontext
        ctx = nullcontext(proxy_consensus)
    else:
        ctx = batched()
    applied = 0
    with ctx as app:
        t = time.perf_counter()
        for block, psh in items:
            if before_block is not None:
                before_block(block, psh)
            t = _stage("fastsync.apply.store_save", t)
            validate_block(state, block, check_last_commit=check_last_commit)
            fail_point("ApplyBlock.validated")
            t = _stage("fastsync.apply.validate", t)
            resp = exec_block_on_app(app, block, event_cache)
            fail_point("ApplyBlock.executed")
            if tx_indexer is not None:
                tx_indexer.index_block(block, resp)
            t = _stage("fastsync.apply.abci_exec", t)
            state.save_abci_responses(resp)
            fail_point("ApplyBlock.savedResponses")
            t = _stage("fastsync.apply.save_responses", t)
            block_id = BlockID(hash=block.hash(), parts=psh)
            state.set_block_and_validators(block.header, block_id,
                                           resp.end_block_diffs)
            t = _stage("fastsync.apply.update_state", t)
            commit_state_update_mempool(state, app, block, mempool)
            fail_point("ApplyBlock.committed")
            t = _stage("fastsync.apply.abci_commit", t)
            state.save()
            applied += 1
            t = _stage("fastsync.apply.state_save", t)
            if on_applied is not None:
                on_applied(block)
            stop = stop_when is not None and stop_when()
            t = _stage("fastsync.apply.advance", t)
            if stop:
                break
    return applied


def commit_state_update_mempool(state: State, proxy_consensus, block,
                                mempool) -> None:
    """App Commit with the mempool locked so no CheckTx runs against a
    half-committed app (reference `state/execution.go:248-271`)."""
    mempool.lock()
    try:
        res = proxy_consensus.commit()
        if not res.is_ok:
            raise RuntimeError(f"app Commit failed: {res.log}")
        state.app_hash = res.data
        mempool.update(block.height, block.txs)
    finally:
        mempool.unlock()


def exec_commit_block(proxy_consensus, block) -> bytes:
    """Execute + commit without state mutation — handshake replay of
    app-missing blocks (reference `state/execution.go:291-308`)."""
    exec_block_on_app(proxy_consensus, block, None)
    res = proxy_consensus.commit()
    if not res.is_ok:
        raise RuntimeError(f"app Commit failed: {res.log}")
    return res.data
