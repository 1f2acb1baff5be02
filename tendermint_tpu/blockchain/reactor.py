"""BlockchainReactor: fast-sync — batched block download + verified replay.

Reference: `blockchain/reactor.go` — `poolRoutine` (`:169-257`) with the
SYNC_LOOP hot loop (`:213-252`): peek blocks, re-hash the part set,
`Validators.VerifyCommit` against the NEXT block's LastCommit, save,
ApplyBlock; status exchange and the switch-to-consensus ticker
(`:196-212`); channel 0x40 (`:19`).

The TPU redesign: instead of verifying one block per tick, the loop
drains a contiguous WINDOW of K downloaded blocks and verifies all their
commit signatures in ONE device batch (`verify_commits_batched`), then
applies sequentially (app execution is inherently serial).  Commit
verification inside ApplyBlock is skipped — the batch already proved
every commit, where the reference pays the signature cost twice.
"""

from __future__ import annotations

import threading
import time

from tendermint_tpu.blockchain import messages as BM
from tendermint_tpu.blockchain.pool import BlockPool
from tendermint_tpu.crypto import backend as crypto_backend
from tendermint_tpu.p2p.peer import Peer, Reactor
from tendermint_tpu.p2p.types import ChannelDescriptor
from tendermint_tpu.state import execution
from tendermint_tpu.types import BlockID
from tendermint_tpu.types.part_set import from_data_batched
from tendermint_tpu.types.validator import (CommitFormatError,
                                            CommitPowerError,
                                            CommitSignatureError,
                                            verify_commits_batched)
from tendermint_tpu.utils import attribution, tracing
from tendermint_tpu.utils.chaos import DeviceFault
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.metrics import REGISTRY
from tendermint_tpu.utils.threadledger import ThreadLedger

log = get_logger("blockchain")

BLOCKCHAIN_CHANNEL = 0x40
SYNC_TICK = 0.01                 # reference trySyncTicker (100ms)
STATUS_INTERVAL = 2.0            # reference statusUpdateTicker (10s)
DEFAULT_BATCH = 64               # blocks verified per device call


class _Lookahead:
    """Speculative verification of the NEXT sync window in a background
    thread: part-set re-hash + grouped device verify against a validator
    set SNAPSHOT, while the main loop applies the current window.  The
    consumer (`_sync_step`) discards the result unless the live set hash
    and next height still match; verification errors are recorded, not
    acted on — the synchronous path re-verifies and owns the blame logic."""

    def __init__(self, vals, chain_id: str, blocks, ledger: ThreadLedger):
        self.vals_hash = vals.hash()
        self.first_height = blocks[0].height
        self.window = None
        self.parts_list = None
        self.items = None
        self.error: BaseException | None = None
        self._vals = vals
        self._chain_id = chain_id
        self._blocks = blocks
        self._ledger = ledger
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="fastsync-lookahead")
        self.thread.start()

    def _run(self) -> None:
        try:
            with tracing.span("fastsync.lookahead",
                              first_height=self.first_height,
                              blocks=len(self._blocks)):
                window, parts_list, items = \
                    BlockchainReactor._prepare_window(self._blocks,
                                                      self.vals_hash)
                if window:
                    verify_commits_batched(self._vals, self._chain_id,
                                           items)
            self.window, self.parts_list, self.items = (window, parts_list,
                                                        items)
        except BaseException as e:
            self.error = e
        finally:
            # this thread lives for one window, so it says what CPU it
            # used itself (`cpu.lookahead`): the span's wall less that is
            # what it waited, for the GIL (apply runs meanwhile) or for
            # the device
            self._ledger.thread_exiting()


class BlockchainReactor(Reactor):
    def __init__(self, state, proxy_consensus, block_store,
                 fast_sync: bool = True, batch_size: int = DEFAULT_BATCH):
        super().__init__()
        self.state = state
        self.proxy = proxy_consensus
        self.store = block_store
        self.fast_sync = fast_sync
        self.batch_size = batch_size
        # a snapshot-restored node's state can be AHEAD of its (pruned /
        # freshly bootstrapped) block store — sync from whichever cursor
        # is further along, never re-request blocks the state already
        # executed
        self.pool = BlockPool(
            max(block_store.height, state.last_block_height) + 1)
        self.pool.on_evict = self._on_pool_evict
        self.on_caught_up = None          # cb(state) -> switch_to_consensus
        # an Event the sync waits for before it requests a block, or None:
        # the node sets it when the plane can verify a window.  Blocks
        # that arrive sooner only wait, and the sync thread's first verify
        # then races the warm-up to load the same program: a boot of 28
        # or of 37 s with 273 KB blocks (PERF.md §6, PR 29)
        self.request_when: threading.Event | None = None
        self._stopped = threading.Event()
        self._thread: threading.Thread | None = None
        self._switched = False
        # True once `on_caught_up` has returned: consensus holds the state
        # from then on, and until then this reactor's is the applied one
        # (`Node.state`)
        self.handed_over = False
        self._lookahead: _Lookahead | None = None
        self.lookahead_hits = 0     # speculative windows actually consumed
        # what the threads used and waited, a window; its probe of the
        # GIL lives as long as the fast-sync thread
        self._ledger = ThreadLedger()

    def get_channels(self):
        return [ChannelDescriptor(id=BLOCKCHAIN_CHANNEL, priority=5,
                                  send_queue_capacity=100,
                                  recv_message_capacity=32 << 20)]

    def start(self) -> None:
        if self.fast_sync:
            self._thread = threading.Thread(target=self._pool_routine,
                                            daemon=True, name="fast-sync")
            self._ledger.probe.start()
            self._thread.start()

    def stop(self) -> None:
        self._stopped.set()
        self._ledger.probe.stop()
        la = self._lookahead
        if la is not None:
            la.thread.join(timeout=5)

    # -- peer lifecycle -------------------------------------------------
    def add_peer(self, peer: Peer) -> None:
        # advertise our height; ask for theirs (reference :96-106)
        peer.try_send(BLOCKCHAIN_CHANNEL,
                      BM.encode_msg(BM.StatusResponse(self.store.height)))
        peer.try_send(BLOCKCHAIN_CHANNEL,
                      BM.encode_msg(BM.StatusRequest()))

    def remove_peer(self, peer: Peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    def _on_pool_evict(self, peer_id: str, reason: str) -> None:
        if self.switch is None:
            return
        if reason.startswith("bad block"):
            # a PROVEN commit lie (the typed commit checks — format /
            # signature / power — failed on a block this peer served):
            # immediate ban, not just a strike.  Timeout evictions land
            # in the else-branch: slow is not malicious, no strike.
            if self.switch.report_misbehavior(peer_id, reason, ban=True):
                return               # report_misbehavior already removed it
        p = self.switch.get_peer(peer_id)
        if p is not None:
            self.switch.stop_peer_for_error(p, reason)

    # -- inbound --------------------------------------------------------
    def receive(self, ch_id: int, peer: Peer, raw: bytes) -> None:
        try:
            msg = BM.decode_msg(raw)
        except (ValueError, IndexError) as e:
            # fuzz-detected garbage: an undecodable message on an
            # authenticated channel is the peer's doing — one strike
            self.switch.report_misbehavior(peer.id, f"bad bc msg: {e}")
            self.switch.stop_peer_for_error(peer, f"bad bc msg: {e}")
            return
        if isinstance(msg, BM.BlockRequest):
            block = (self.store.load_block(msg.height)
                     if msg.height <= self.store.height else None)
            if block is not None:
                peer.try_send(BLOCKCHAIN_CHANNEL, BM.encode_msg(
                    BM.BlockResponse(block.encode())))
            else:
                peer.try_send(BLOCKCHAIN_CHANNEL, BM.encode_msg(
                    BM.NoBlockResponse(msg.height)))
        elif isinstance(msg, BM.BlockResponse):
            t0 = time.perf_counter()
            try:
                block = msg.block()
            except (ValueError, IndexError) as e:
                self.switch.report_misbehavior(peer.id, f"bad block: {e}")
                self.switch.stop_peer_for_error(peer, f"bad block: {e}")
                return
            # one record a block, in the p2p receive thread, which
            # decodes the next windows while apply runs; bookkeeping
            # (CAT_NONE), so the window histograms read as before
            tracing.RECORDER.record(
                "fastsync.decode", tracing.perf_to_epoch(t0),
                time.perf_counter() - t0, None, cat=tracing.CAT_NONE)
            if self.pool.add_block(peer.id, block):
                # feed the peer's flowrate meter — the slow-drip
                # eviction (reference minRecvRate) keys off this
                self.pool.record_bytes(peer.id, len(raw))
            else:
                # delivered and dropped: the slot is no longer this
                # peer's (re-requested after a timeout), is already
                # filled, or was never asked for
                tracing.instant("pool.late_block", height=block.height,
                                peer=peer.id[:12], bytes=len(raw))
        elif isinstance(msg, BM.StatusRequest):
            peer.try_send(BLOCKCHAIN_CHANNEL, BM.encode_msg(
                BM.StatusResponse(self.store.height)))
        elif isinstance(msg, BM.StatusResponse):
            self.pool.set_peer_height(peer.id, msg.height)

    # -- the sync loop ---------------------------------------------------
    def _pool_routine(self) -> None:
        """The fast-sync thread: it ends at the hand-over to consensus
        or at `stop()`, and the GIL probe with it."""
        try:
            self._sync_until_caught_up()
        finally:
            self._ledger.probe.stop()

    def _sync_until_caught_up(self) -> None:
        """Reference `poolRoutine` :169-257."""
        last_status = 0.0
        while not self._stopped.is_set():
            now = time.monotonic()
            if now - last_status >= STATUS_INTERVAL:
                if self.switch is not None:
                    self.switch.broadcast(
                        BLOCKCHAIN_CHANNEL,
                        BM.encode_msg(BM.StatusRequest()))
                last_status = now
            if self.request_when is None or self.request_when.is_set():
                self._send_requests()
            try:
                progressed = self._sync_step()
            except Exception:
                log.exception("sync step failed",
                              next_height=self.pool.next_height)
                progressed = False
            if self._stopped.is_set():
                # stopped during this step (its last window, perhaps): a
                # node that is going down hands nothing to consensus and
                # starts no live warm-up (`caught_up` in node/node.py)
                return
            if self.pool.is_caught_up() and not self._switched:
                self._switched = True
                log.info("fast-sync caught up",
                         height=self.state.last_block_height)
                if self.on_caught_up is not None:
                    self.on_caught_up(self.state)
                self.handed_over = True
                return
            if not progressed:
                time.sleep(SYNC_TICK)

    def _send_requests(self) -> None:
        if self.switch is None:
            return
        # what has arrived of each peer's unfinished message: the pool
        # sees whole blocks only, and a peer that is sending is not
        # silent yet (`BlockPool._waiting_since` bounds what that buys)
        self.pool.note_receiving({
            peer.id: peer.receiving(BLOCKCHAIN_CHANNEL)
            for peer in self.switch.peers()})
        for height, peer_id in self.pool.schedule():
            peer = self.switch.get_peer(peer_id)
            if peer is not None:
                peer.try_send(BLOCKCHAIN_CHANNEL,
                              BM.encode_msg(BM.BlockRequest(height)))

    @staticmethod
    def _prepare_window(blocks, vals_hash: bytes):
        """Cut the window at the first valset change, re-hash part sets in
        one device batch, and assemble verify items.

        Each header commits to the validator set of ITS height.  EndBlock
        diffs can change the set mid-window, so only the prefix whose
        headers match vals_hash is prepared; the rest re-verifies next
        tick against the updated state (reference verifies per block:
        `blockchain/reactor.go:230-231`).  Returns (window, parts_list,
        items); an empty window means the very next block mismatches.

        A cut window has any size from 1 to 63, wherever the chain puts
        its change.  It costs one verify call of a full window: the
        backend pads its lanes into the smallest program it has already
        compiled (`TpuBackend._warm_shape`), never a compile of the odd
        size's own bucket.  What a change does cost is the next set's
        comb table, built in line by the first verify call that meets
        the set, and the look-ahead of the window after the cut, which
        was prepared against the old set and is dropped.
        """
        window = blocks[:-1]              # each needs its successor's
        cut = len(window)                 # LastCommit as its +2/3 proof
        for i, b in enumerate(window):
            if b.header.validators_hash != vals_hash:
                cut = i
                break
        if 0 < cut < len(window):
            tracing.instant("fastsync.valset_cut", height=window[cut].height,
                            blocks=cut)
            # the next set's table will be derived from this one's: the
            # programs for that load while this window is applied
            crypto_backend.valset_change_ahead(blocks[cut].last_commit.size())
        window = window[:cut]
        # full 64KB chunks lockstep on device, tails + trees on host —
        # proving data integrity like the reference's per-block re-hash
        # (`blockchain/reactor.go:224`) at batch rates
        with tracing.span("fastsync.prepare.encode"):
            datas = [b.encode() for b in window]
        with tracing.span("fastsync.prepare.parts"):
            parts_list = from_data_batched(datas)
        items = []
        with tracing.span("fastsync.prepare.block_ids"):
            for i, b in enumerate(window):
                bid = BlockID(b.hash(), parts_list[i].header)
                items.append((bid, b.height, blocks[i + 1].last_commit))
        return window, parts_list, items

    def _window_ready(self, blocks) -> bool:
        """Verify `blocks` (a window plus its successor) now, or wait?
        A window goes when it is full, or when no fuller one can come:
        the best peer's height ends inside it — the tip of the chain.
        With no peer at all the tip is unknown, so it waits (a starved
        boot evicts every peer for request timeouts at once, and they
        redial).  Draining whatever happens to have arrived would cost
        a full window's verify call for every handful of blocks (an odd
        size is padded into the warmed full-window program, see
        `_prepare_window`), and one sqlite-bound apply and one dropped
        look-ahead a call."""
        if len(blocks) > self.batch_size:
            return True
        best = self.pool.max_peer_height()
        return 0 < best < blocks[0].height + self.batch_size

    def _sync_step(self) -> bool:
        """Drain one verified window: batch-verify K contiguous blocks'
        commits in one device call, then save + apply each — with the
        NEXT window verified speculatively in a background thread while
        this one applies (device verify and host ABCI/store work overlap;
        the speculation is discarded if the validator set moved)."""
        peek = self.pool.peek_contiguous(2 * (self.batch_size + 1))
        if len(peek) < 2:
            return False
        blocks = peek[:self.batch_size + 1]
        if not self._window_ready(blocks):
            return False
        chain_id = self.state.chain_id
        vals_hash = self.state.validators.hash()
        verified = None
        la, self._lookahead = self._lookahead, None
        if la is not None:
            la.thread.join()
            if (la.error is None and la.window and
                    la.vals_hash == vals_hash and
                    la.first_height == blocks[0].height):
                verified = (la.window, la.parts_list, la.items)
                self.lookahead_hits += 1
            # stale or failed speculation: fall through and re-verify
            # synchronously so the error/redo paths below stay in charge
        t0 = time.perf_counter()
        if verified is None:
            with tracing.span("fastsync.prepare",
                              first_height=blocks[0].height,
                              blocks=len(blocks) - 1):
                window, parts_list, items = self._prepare_window(blocks,
                                                                 vals_hash)
            if not window:
                # the very next block disagrees with our state's validator
                # set: the block is bad (or stale) — re-fetch it elsewhere
                log.warn("next block's validators_hash mismatches state",
                         height=blocks[0].height)
                self.pool.redo(blocks[0].height)
                return False
            try:
                with tracing.span("fastsync.verify",
                                  first_height=window[0].height,
                                  blocks=len(window)):
                    verify_commits_batched(self.state.validators, chain_id,
                                           items)
            except DeviceFault as e:
                # OUR device failed, not the peer: every rung of the
                # crypto ladder errored out.  Blaming the deliverer here
                # (redo/evict) would partition us from honest peers for a
                # local hardware problem — keep the blocks queued and let
                # the next tick retry once a rung recovers.
                log.warn("device fault during commit verify; will retry",
                         height=blocks[0].height, error=str(e)[:200])
                return False
            except CommitFormatError as e:
                # a structurally-wrong commit (stale finality proof, bad
                # size) rides in the successor block's LastCommit — same
                # blame as a pruned commit: height+1's deliverer lied
                log.warn("stale/malformed commit; punishing successor's "
                         "deliverer", height=e.height, error=str(e)[:200])
                self.pool.redo(e.height + 1)
                return False
            except CommitSignatureError as e:
                # the commit for height h rides in block h+1's LastCommit:
                # a forged signature implicates the successor's deliverer
                log.warn("bad commit signature; punishing deliverer",
                         height=e.height)
                self.pool.redo(e.height + 1)
                return False
            except CommitPowerError as e:
                if e.foreign_votes:
                    # votes endorse a DIFFERENT block: block h itself was
                    # tampered — its deliverer lied
                    log.warn("commit votes for another block; punishing "
                             "deliverer", height=e.height)
                    self.pool.redo(e.height)
                else:
                    # every vote endorses our block but too few are
                    # present: the commit rides in h+1's LastCommit, so
                    # the SUCCESSOR's deliverer pruned it — an honest
                    # deliverer of h must not be evicted for that
                    log.warn("commit pruned; punishing successor's "
                             "deliverer", height=e.height)
                    self.pool.redo(e.height + 1)
                return False
            verified = (window, parts_list, items)
        window, parts_list, items = verified
        dt = time.perf_counter() - t0
        # speculative verify-ahead: the next contiguous window, against a
        # SNAPSHOT of the current set (apply below mutates the live one)
        nxt = peek[len(window):len(window) + self.batch_size + 1]
        if len(nxt) >= 2 and self._window_ready(nxt) and \
                not self._stopped.is_set():
            self._lookahead = _Lookahead(
                self.state.validators.copy(), chain_id, nxt, self._ledger)
        commit_by_height = {h: c for _bid, h, c in items}
        parts_by_height = {b.height: p for b, p in zip(window, parts_list)}

        def _save_to_store(b, _psh):
            # store-before-state is the crash-recovery discipline (the
            # handshake covers store==state+1); but the pool advances
            # only AFTER a successful apply so an in-process app/WAL
            # fault re-fetches and re-applies instead of wedging the
            # sync.
            if self.store.height < b.height:
                self.store.save_block(b, parts_by_height[b.height],
                                      commit_by_height[b.height])

        def _advance(b):
            self.pool.pop(1)
            REGISTRY.blocks_synced.inc()

        def _valset_moved():
            # validator set changed: the rest of the window was verified
            # against a stale set — drop and re-verify
            moved = self.state.validators.hash() != vals_hash
            if moved:
                log.info("valset changed mid-window; flushing",
                         height=self.state.last_block_height)
            return moved

        with tracing.span("fastsync.apply", first_height=window[0].height,
                          blocks=len(window)):
            # the window-batched apply: per-block validate/exec/save
            # discipline identical to apply_block (one state save a
            # block: a durable node must keep store <= state+1 for the
            # handshake), but the app conn's lock is held once for the
            # whole window instead of ~4 acquisitions per block
            p0, cpu0 = time.perf_counter(), time.thread_time()
            applied = execution.apply_window(
                self.state, None, self.proxy,
                [(b, p.header) for b, p in zip(window, parts_list)],
                execution.MockMempool(), check_last_commit=False,
                before_block=_save_to_store, on_applied=_advance,
                stop_when=_valset_moved)
            # wall less this thread's own CPU is what apply waited, for
            # the GIL (look-ahead and p2p decode run meanwhile) or for
            # sqlite's I/O: `offcpu.apply`, and its writes' share of it
            p1 = time.perf_counter()
            self._ledger.apply_ended(p1 - p0, time.thread_time() - cpu0,
                                     tracing.perf_to_epoch(p1))
        # the window-boundary span: covers verify (or lookahead reuse)
        # through apply under one window=<first_height> key, which is
        # what the attribution profiler groups by
        lo = tracing.perf_to_epoch(t0)
        hi = tracing.perf_to_epoch(time.perf_counter())
        tracing.RECORDER.record(
            "fastsync.window", lo, hi - lo,
            {"window": window[0].height, "blocks": applied})
        self._ledger.window_ended(hi)
        try:
            # per-window pipeline health -> Prometheus histograms, from
            # this window's own categorized records: the read costs what
            # the window recorded, not what the ring holds.  A failure
            # here must never fail the sync itself
            attribution.observe_window_metrics(
                attribution.attribute_interval(
                    attribution.spans_by_category(
                        tracing.RECORDER.since(lo, categorized=True)),
                    lo, hi))
        except Exception as e:
            log.debug("window metrics not observed", error=repr(e)[:200])
        log.debug("synced window", blocks=applied,
                  sigs=sum(i[2].size() for i in items),
                  verify_seconds=round(dt, 4),
                  height=self.state.last_block_height)
        return True

