"""BlockPool: concurrent block download scheduling for fast-sync.

Reference: `blockchain/pool.go` — up to 300 heights in flight, 75 per
peer (`:14-19`), per-peer height tracking from status messages, slow/
unresponsive peers evicted (`removeTimedoutPeers` `:100-118`),
`PeekTwoBlocks`/`PopRequest`/`RedoRequest` feeding the sync loop
(`:154-201`).  The reference runs one goroutine per height
(`bpRequester`); here a single scheduler assigns request slots and the
reactor's pool routine drives (`schedule()` returns what to request),
which batches naturally with the device-verify window.

Timeouts follow the reference in timing out PEERS, not queue positions:
`REQUEST_TIMEOUT` is how long a peer with requests outstanding may
deliver nothing (`_waiting_since`).  With 273 KB blocks at 512 KB/s a
peer delivers one block each 0.53 s, and the ~19 requests it holds of
300 in flight are all older than 3 s long before their turn comes.
Only a block the pool ACCEPTS restarts a peer's clock (the reference's
`decrPending`); bytes on the wire hold it for `WIRE_HOLD` at most.
"""

from __future__ import annotations

import time

from tendermint_tpu.utils import lockwitness, tracing
from tendermint_tpu.utils.log import get_logger

log = get_logger("blockpool")

MAX_PENDING = 300                # reference maxPendingRequests
MAX_PENDING_PER_PEER = 75        # reference maxPendingRequestsPerPeer
REQUEST_TIMEOUT = 3.0            # redo a request its peer has left
                                 # unanswered this long (`_waiting_since`)
MAX_PEER_TIMEOUTS = 4            # evict after this many consecutive redos
WIRE_HOLD = MAX_PEER_TIMEOUTS * REQUEST_TIMEOUT   # what bytes on the wire
                                 # may add to a clock (`_waiting_since`)
MIN_RECV_RATE = 10_240           # reference minRecvRate (10 KB/s),
                                 # blockchain/pool.go:14-19
STARVE_AGE = 1.0                 # a request outstanding this long marks
                                 # the peer as starving the sync window


class _Slot:
    __slots__ = ("height", "peer_id", "sent_at", "seq", "block")

    def __init__(self, height: int, peer_id: str, seq: int):
        self.height = height
        self.peer_id = peer_id
        self.sent_at = time.monotonic()
        self.seq = seq                # the order requests were sent in
        self.block = None


class _Progress:
    """What a peer has been seen to deliver: the silence clock's input."""
    __slots__ = ("at", "seq", "wire_at", "mark_at", "mark_bytes")

    def __init__(self, now: float):
        self.at = None                # last block the pool ACCEPTED
        self.seq = 0                  # newest request it has answered
        self.wire_at = float("-inf")  # when the message now crossing the
        #                               link last grew at min_recv_rate
        #                               or more: provisional, not `at`
        self.mark_at = now            # that message when last credited
        self.mark_bytes = 0           # (nothing: a delivery, an idle look)


class BlockPool:
    def __init__(self, start_height: int,
                 min_recv_rate: int = MIN_RECV_RATE):
        self.next_height = start_height       # first height not yet popped
        self.min_recv_rate = min_recv_rate
        self._slots: dict[int, _Slot] = {}
        self._peers: dict[str, int] = {}      # peer_id -> reported height
        self._peer_pending: dict[str, int] = {}
        self._peer_timeouts: dict[str, int] = {}
        self._peer_meters: dict[str, object] = {}   # peer_id -> Meter
        self._peer_progress: dict[str, _Progress] = {}
        self._seq = 0
        self._lock = lockwitness.new_lock("blockpool.lock",
                                          reentrant=False)
        self.on_evict = None                  # cb(peer_id, reason)

    # -- peers ----------------------------------------------------------
    def set_peer_height(self, peer_id: str, height: int) -> None:
        from tendermint_tpu.utils.flowrate import Meter
        with self._lock:
            self._peers[peer_id] = height
            self._peer_pending.setdefault(peer_id, 0)
            self._peer_timeouts.setdefault(peer_id, 0)
            self._peer_meters.setdefault(peer_id, Meter())
            if peer_id not in self._peer_progress:
                self._peer_progress[peer_id] = _Progress(time.monotonic())

    def record_bytes(self, peer_id: str, nbytes: int) -> None:
        """Feed the peer's receive meter (called per delivered block)."""
        with self._lock:
            m = self._peer_meters.get(peer_id)
        if m is not None:
            m.update(nbytes)

    def note_receiving(self, partial: dict[str, int]) -> None:
        """`partial[peer_id]` bytes of an unfinished message from the
        peer have arrived (the reactor reads its connections every
        tick).  273 KB take half a second at the link's 512 KB/s and
        several while a boot's compiles hold the GIL, and a peer whose
        block is crossing is not silent.  Only bytes that came at
        `min_recv_rate` or more since the last credit count, so a drip
        is none.  What the message will turn out to be is not known
        yet, so this is `wire_at` and never `at`: `_waiting_since`
        bounds what it is worth."""
        now = time.monotonic()
        with self._lock:
            for peer_id, nbytes in partial.items():
                p = self._peer_progress.get(peer_id)
                if p is None:
                    continue
                if nbytes == 0 or nbytes < p.mark_bytes:
                    # nothing is crossing, or another message than
                    # before: what comes next is measured from here
                    p.mark_at, p.mark_bytes = now, nbytes
                elif nbytes > p.mark_bytes and nbytes - p.mark_bytes >= \
                        self.min_recv_rate * (now - p.mark_at):
                    p.mark_at, p.mark_bytes = now, nbytes
                    p.wire_at = now

    def remove_peer(self, peer_id: str) -> None:
        with self._lock:
            self._peers.pop(peer_id, None)
            self._peer_pending.pop(peer_id, None)
            self._peer_timeouts.pop(peer_id, None)
            self._peer_meters.pop(peer_id, None)
            self._peer_progress.pop(peer_id, None)
            for slot in list(self._slots.values()):
                if slot.peer_id == peer_id and slot.block is None:
                    del self._slots[slot.height]

    def max_peer_height(self) -> int:
        with self._lock:
            return max(self._peers.values(), default=0)

    def num_peers(self) -> int:
        with self._lock:
            return len(self._peers)

    # -- scheduling -----------------------------------------------------
    def schedule(self) -> list[tuple[int, str]]:
        """(height, peer_id) pairs the reactor should request now: new
        heights up to the in-flight cap, plus timed-out redos reassigned
        to other peers."""
        out = []
        now = time.monotonic()
        evictions: set[str] = set()
        with self._lock:
            # rate-based eviction (reference removeTimedoutPeers,
            # blockchain/pool.go:100-118): a peer that keeps a request
            # outstanding past STARVE_AGE while its delivery rate is
            # under min_recv_rate throttles the whole window — evict it
            # even though it answers just inside the redo timeout (the
            # slow-drip case the redo counter never catches)
            starving: set[str] = set()
            for slot in self._slots.values():
                if slot.block is None and now - slot.sent_at >= STARVE_AGE:
                    starving.add(slot.peer_id)
            for pid in starving:
                # the meter sees whole blocks, so between two 273 KB
                # blocks it decays: a peer with a message on the wire
                # at min_recv_rate or more is not dripping (should it
                # never end in a block, `_waiting_since` times it out)
                p = self._peer_progress.get(pid)
                if p is not None and now - p.wire_at < STARVE_AGE:
                    continue
                m = self._peer_meters.get(pid)
                # total > 0: never judge a peer that has not delivered
                # its FIRST block yet (the reference's curRate == 0
                # exclusion — "curRate can be 0 on start"); the redo
                # timeout handles truly dead peers
                if m is not None and m.total > 0 and \
                        m.age(now) >= STARVE_AGE and \
                        m.rate(now) < self.min_recv_rate:
                    evictions.add(pid)
            # redo timed-out requests on a different peer
            for slot in self._slots.values():
                if slot.block is not None:
                    continue
                since, reason = self._waiting_since(slot)
                if now - since < REQUEST_TIMEOUT:
                    continue
                old = slot.peer_id
                self._peer_pending[old] = \
                    max(0, self._peer_pending.get(old, 1) - 1)
                t = self._peer_timeouts.get(old, 0) + 1
                self._peer_timeouts[old] = t
                if t >= MAX_PEER_TIMEOUTS:
                    evictions.add(old)
                peer = self._pick_peer(slot.height, exclude=old)
                if peer is None:
                    peer = self._pick_peer(slot.height)
                if peer is None:
                    # nobody to reassign to; don't re-count this slot
                    # against `old` on every pass
                    slot.sent_at = now
                    continue
                slot.peer_id = peer
                slot.sent_at = now
                slot.seq = self._next_seq()
                self._peer_pending[peer] = \
                    self._peer_pending.get(peer, 0) + 1
                out.append((slot.height, peer))
                # the protocol working, not a refusal (`pool.redo`):
                # `old` may still answer, and the reactor then counts
                # that block as `pool.late_block`
                tracing.instant("pool.rerequest", height=slot.height,
                                old=old[:12], new=peer[:12], reason=reason)
            # new requests
            h = self.next_height
            while len(self._slots) < MAX_PENDING:
                while h in self._slots:
                    h += 1
                if h > self.max_peer_height_locked():
                    break
                peer = self._pick_peer(h)
                if peer is None:
                    break
                slot = _Slot(h, peer, self._next_seq())
                self._slots[h] = slot
                self._peer_pending[peer] = \
                    self._peer_pending.get(peer, 0) + 1
                out.append((h, peer))
        for pid in evictions:
            self._evict(pid, "request timeouts")
        return out

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _waiting_since(self, slot: _Slot) -> tuple[float, str]:
        """(since when, why) an unanswered request counts against its
        peer.  A peer answers its requests in the order they were sent
        (one ordered channel), each at what its link carries, so a
        request that waits its turn behind earlier ones is not overdue
        by its age: its clock runs from the peer's last ACCEPTED block
        (`add_block`), and `REQUEST_TIMEOUT` measures the peer's SILENCE
        (the reference times out peers, not requests, and restarts a
        peer's timer in `decrPending` only: `removeTimedoutPeers`).  A
        request the peer has passed over (it answered one sent later)
        is not coming: its clock is its own.

        Bytes on the wire (`note_receiving`) hold the clock while they
        flow, for `WIRE_HOLD` at most: the strikes the peer has, spent
        at once.  They may be a block nobody asked for, so they never
        restart it: a peer that streams at any rate and answers nothing
        is re-requested `WIRE_HOLD + REQUEST_TIMEOUT` after its last
        accepted block (15 s, as long as the reference's `peerTimeout`
        lets a peer go without one)."""
        p = self._peer_progress.get(slot.peer_id)
        if p is None:
            return slot.sent_at, "silent"
        if p.seq > slot.seq:
            return slot.sent_at, "skipped"
        since = slot.sent_at if p.at is None else max(slot.sent_at, p.at)
        return max(since, min(p.wire_at, since + WIRE_HOLD)), "silent"

    def max_peer_height_locked(self) -> int:
        return max(self._peers.values(), default=0)

    def _pick_peer(self, height: int, exclude: str | None = None):
        cands = [p for p, ph in self._peers.items()
                 if ph >= height and p != exclude and
                 self._peer_pending.get(p, 0) < MAX_PENDING_PER_PEER]
        if not cands:
            return None
        # least-loaded peer spreads the window
        return min(cands, key=lambda p: self._peer_pending.get(p, 0))

    def _evict(self, peer_id: str, reason: str) -> None:
        with self._lock:
            if peer_id not in self._peers:
                return
        log.info("evicting slow peer", peer=peer_id[:12], reason=reason)
        tracing.instant("pool.evict", peer=peer_id[:12], reason=reason)
        self.remove_peer(peer_id)
        if self.on_evict is not None:
            self.on_evict(peer_id, reason)

    # -- delivery -------------------------------------------------------
    def add_block(self, peer_id: str, block) -> bool:
        """Accept a block if it matches an outstanding request from that
        peer (reference `AddBlock` pool.go:203+)."""
        with self._lock:
            slot = self._slots.get(block.height)
            if slot is None or slot.peer_id != peer_id or \
                    slot.block is not None:
                return False
            slot.block = block
            self._peer_pending[peer_id] = \
                max(0, self._peer_pending.get(peer_id, 1) - 1)
            self._peer_timeouts[peer_id] = 0
            p = self._peer_progress.get(peer_id)
            if p is not None:
                p.at, p.seq = time.monotonic(), max(p.seq, slot.seq)
                # the next block starts from nothing, now: whenever the
                # reactor looks next, what has come of it is measured
                # from here (it cannot look while a window applies)
                p.mark_at, p.mark_bytes = p.at, 0
            return True

    def peek_contiguous(self, max_n: int) -> list:
        """Blocks [next_height, ...] with no gaps, up to max_n — the
        batched generalization of the reference's PeekTwoBlocks."""
        out = []
        with self._lock:
            h = self.next_height
            while len(out) < max_n:
                slot = self._slots.get(h)
                if slot is None or slot.block is None:
                    break
                out.append(slot.block)
                h += 1
        return out

    def pop(self, n: int) -> None:
        """Advance past n processed blocks (reference `PopRequest`)."""
        with self._lock:
            for _ in range(n):
                self._slots.pop(self.next_height, None)
                self.next_height += 1

    def redo(self, height: int) -> None:
        """Re-request a height whose block failed verification; the peer
        that sent it lied — evict it (reference `RedoRequest`)."""
        with self._lock:
            slot = self._slots.pop(height, None)
        if slot is not None:
            tracing.instant("pool.redo", height=height,
                            peer=slot.peer_id[:12])
            self._evict(slot.peer_id, f"bad block at height {height}")
            # drop any later blocks that peer delivered: they're suspect
            with self._lock:
                for h in list(self._slots):
                    s = self._slots[h]
                    if s.peer_id == slot.peer_id:
                        del self._slots[h]

    def is_caught_up(self) -> bool:
        """Reference `IsCaughtUp` pool.go:128 — synced to within one block
        of the best peer (peers lag by one while committing)."""
        with self._lock:
            if not self._peers:
                return False
            return self.next_height >= self.max_peer_height_locked()

    def status(self) -> dict:
        now = time.monotonic()
        with self._lock:
            ready = sum(1 for s in self._slots.values()
                        if s.block is not None)
            return {"next_height": self.next_height,
                    "in_flight": len(self._slots) - ready,
                    "ready": ready, "peers": len(self._peers),
                    "max_peer_height": self.max_peer_height_locked(),
                    "peer_rates": {p[:12]: round(m.rate(), 1)
                                   for p, m in self._peer_meters.items()},
                    # requests each peer has yet to answer, and how long
                    # ago the pool last accepted one of its blocks
                    # (None: never)
                    "peer_outstanding": {p[:12]: n for p, n in
                                         self._peer_pending.items()},
                    "peer_idle_s": {
                        pid[:12]: (None if p.at is None
                                   else round(now - p.at, 3))
                        for pid, p in self._peer_progress.items()}}
