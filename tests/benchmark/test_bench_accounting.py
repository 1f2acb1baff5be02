"""The benchmark's rule for operations and failures
(`benchmark/lib/accounting.py`), on spans the program really records."""

import time

import pytest

import benchutil  # noqa: F401  (puts the repo on sys.path)
from benchmark.lib import accounting, reducers
from tendermint_tpu.blockchain import pool as pool_mod
from tendermint_tpu.utils import tracing

T0 = 1_000.0


def window(first_height, end, blocks=64, dur=0.5):
    return {"name": "fastsync.window", "ph": "X", "ts": end - dur,
            "dur": dur, "args": {"window": first_height, "blocks": blocks}}


def five_windows():
    # completions at +1 .. +5 s; the window is open over (+0.7, +4.6)
    return [window(1 + 64 * i, T0 + 1 + i) for i in range(5)]


def hashes(h):
    return "%064x" % h


def recorded_since(mark: int) -> list[dict]:
    spans = tracing.RECORDER.snapshot()
    return spans[len(spans) - (tracing.RECORDER.total - mark):]


def test_a_window_edge_with_blocks_in_flight_is_not_attempted():
    """Windows 1 and 5 straddle the edges: window 1 only anchors the
    clock, window 5 (half applied at the close) counts neither as work
    nor as time."""
    a = accounting.account(five_windows(), T0 + 0.7, T0 + 4.6, hashes,
                           hashes)
    assert (a["t_first"], a["t_last"]) == (T0 + 1, T0 + 4)
    assert a["windows"] == 3 and a["applied"] == 192
    assert a["heights"][0] == 65 and a["heights"][-1] == 256
    assert a["attempted"] == 192 and a["failed"] == 0
    assert a["blocks_per_s"] == pytest.approx(64.0)


def test_fewer_than_two_completions_measure_nothing():
    with pytest.raises(accounting.WindowTooShort):
        accounting.account(five_windows(), T0 + 0.7, T0 + 1.5, hashes,
                           hashes)


def _pool_with_two_peers(start: int = 1):
    pool = pool_mod.BlockPool(start)
    # 60 heights between two peers: each has room for the other's (75 a peer)
    pool.set_peer_height("peer-a" * 4, start + 59)
    pool.set_peer_height("peer-b" * 4, start + 59)
    evicted = []
    pool.on_evict = lambda pid, reason: evicted.append((pid, reason))
    return pool, evicted


def test_a_timed_out_and_rerequested_block_is_no_failure():
    """The pool asks another peer for a block whose request timed out;
    enough of those evict the slow peer.  Both are the protocol working:
    `failed` stays 0, the eviction shows per layer."""
    mark = tracing.RECORDER.total
    pool, evicted = _pool_with_two_peers()
    first = pool.schedule()
    assert first
    late = time.monotonic() - pool_mod.REQUEST_TIMEOUT - 1.0
    quiet = set()
    for slot in pool._slots.values():
        if slot.peer_id == "peer-a" * 4:
            slot.sent_at = late                     # peer-a went quiet
            quiet.add(slot.height)
    again = pool.schedule()
    assert quiet and quiet <= {h for h, pid in again if pid == "peer-b" * 4}
    assert evicted and evicted[0][1] == "request timeouts"
    spans = five_windows() + [
        dict(s, ts=T0 + 2.5) for s in recorded_since(mark)]
    a = accounting.account(spans, T0 + 0.7, T0 + 4.6, hashes, hashes)
    assert a["failed"] == 0 and a["attempted"] == 192
    ctx = {"spans": accounting.in_interval(spans, a["t_first"], a["t_last"])}
    assert reducers.span_count(ctx, "pool.evict") == 1.0
    assert reducers.span_count(ctx, "pool.redo") == 0.0


def test_a_refused_valid_block_is_a_failure():
    """`pool.redo` drops a delivered block and bans its deliverer.  The
    served chain is valid, so that is a height refused though valid."""
    mark = tracing.RECORDER.total
    pool, evicted = _pool_with_two_peers(start=100)
    pool.schedule()
    pool.redo(130)
    assert evicted and evicted[0][1] == "bad block at height 130"
    spans = five_windows() + [
        dict(s, ts=T0 + 2.5) for s in recorded_since(mark)]
    a = accounting.account(spans, T0 + 0.7, T0 + 4.6, hashes, hashes)
    assert a["refused"] == [130] and a["failed"] == 1
    assert a["attempted"] == 193
    # the same refusal outside the measured interval is in flight at an
    # edge: counted neither way
    early = five_windows() + [
        dict(s, ts=T0 + 0.9) for s in recorded_since(mark)]
    b = accounting.account(early, T0 + 0.7, T0 + 4.6, hashes, hashes)
    assert b["failed"] == 0 and b["attempted"] == 192


def test_a_wrong_stored_hash_is_a_failure():
    a = accounting.account(
        five_windows(), T0 + 0.7, T0 + 4.6,
        lambda h: hashes(h + (h == 100)), hashes)
    assert a["wrong_hash"] == [100] and a["failed"] == 1
    missing = accounting.account(
        five_windows(), T0 + 0.7, T0 + 4.6,
        lambda h: None if h == 200 else hashes(h), hashes)
    assert missing["wrong_hash"] == [200] and missing["failed"] == 1


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (100, 10), (10, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert accounting.percentile(list(range(10, 0, -1)), q) == want
