"""A run closes at its close (`benchmark/lib/cell.py`): whatever the
profiler is doing, the harness reads the close when it is due, stops the
sync there, and judges the tip by what it read; rehearsed on the CPU.
In a file of its own so that these rehearsals run beside those of
`test_bench_rehearsal.py`, not after them."""

import re
import threading
import time

import pytest

import benchutil
from benchmark.lib import cell as cell_mod

CLOSE = re.compile(r"close: read ([0-9.]+) ms after it was due, node at "
                   r"(\d+); the sync stopped ([0-9.]+)s after the close, "
                   r"node at (\d+)")
TRACE = re.compile(r"trace: stop asked ([0-9.]+)s after the open, written "
                   r"([0-9.]+)s after the close")
INTERVAL = re.compile(r"node at (\d+) at the open and (\d+) of (\d+) served "
                      r"at the close \((-?\d+) heights outside the interval\)")


def test_a_slow_stop_trace_does_not_move_the_close():
    """The trace ends 2 s into a 6 s window and its stop_trace() does
    not return before the sync thread has ended.  The parent's harness
    read its close on the thread that was inside it, so only when the
    node was at the served tip, and exited 4 (seen on the parent, PR 26)."""
    result, out = benchutil.rehearse(seed=2**31 + 19, trace=True,
                                     trace_max_s=2.0, hold_trace_s=3.0)
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    read_late_ms, at_close, stopped_s, at_stop = map(
        float, CLOSE.search(out).groups())
    asked_s, written_s = map(float, TRACE.search(out).groups())
    window_ms = result["metrics"]["reactor.window_ms"]["value"]
    # read within one window of t_close; the sync ends with the window
    # it was applying, and nothing of the chain's rest is synced while
    # the trace is written
    assert read_late_ms < window_ms
    assert 0 <= at_stop - at_close <= 2 * cell_mod.WINDOW_BLOCKS
    assert stopped_s < written_s
    assert abs(asked_s - 2.0) < 0.5           # the traced part is as asked
    h_open, h_close, served, outside = map(
        int, INTERVAL.search(out).groups())
    assert h_close == at_close < served - 2 * cell_mod.WINDOW_BLOCKS
    assert 0 <= outside < 3 * cell_mod.WINDOW_BLOCKS
    assert result["attempted"] == h_close - h_open - outside
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(out))


def test_a_node_at_the_tip_at_the_close_has_measured_nothing():
    """A chain of seven windows: the node is at its tip when the window
    closes.  Exit 4, no result line, and the message says where the
    window opened and what kind of run it was."""
    r = benchutil.run_rehearsal(
        seed=2**31 + 20, seconds=2.0, trace=False,
        chain={"parent_blocks_per_s": 1, "warmup_s": 0})
    assert r.returncode == cell_mod.EXIT_MEASURED_NOTHING, \
        r.stdout[-3000:] + r.stderr[-3000:]
    assert '"correct"' not in r.stdout
    said = next(ln for ln in r.stderr.splitlines()
                if ln.startswith("benchmark:"))
    assert re.search(r"reached height \d+ of 448 served before the window "
                     r"closed \(it opened at height \d+; an untraced run\)",
                     said), said
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(r.stdout))


@pytest.mark.parametrize("busy", [False, True], ids=["idle", "busy"])
def test_closer_takes_the_close_when_due_on_its_own_thread(busy):
    """Whatever the thread of the clock is doing: here it sleeps through
    the close and a second beyond, as inside a slow stop_trace()."""
    took = []

    def take():
        took.append((time.monotonic(), threading.current_thread().name))
        return {"height": 7}

    due = time.monotonic() + 0.3
    closer = cell_mod.Closer(due, take)
    closer.start()
    if busy:
        time.sleep(1.3)
    assert closer.taken(5.0) == {"height": 7}
    (at, thread), = took
    assert thread == "bench-close" and 0 <= at - due < 0.25


def test_closer_hands_on_what_taking_the_close_raised_and_can_be_called_off():
    def take():
        raise RuntimeError("the fast-sync thread did not stop")
    closer = cell_mod.Closer(time.monotonic() + 0.1, take)
    closer.start()
    with pytest.raises(RuntimeError, match="did not stop"):
        closer.taken(5.0)
    took = []
    closer = cell_mod.Closer(time.monotonic() + 0.3, lambda: took.append(1))
    closer.start()
    closer.call_off()
    with pytest.raises(TimeoutError, match="not taken"):
        closer.taken(0.5)
    assert took == [] and not closer.is_alive()
