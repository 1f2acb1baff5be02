"""The thread ledger (`utils/threadledger.py`): quantity records of the
flight recorder, CPU by kind of thread a window, what apply and its
sqlite writes waited off the CPU, and the probe of the GIL."""

import hashlib
import os
import statistics
import sys
import threading
import time

import pytest

from chainutil import fast_sync_in_process, make_genesis, make_validators
from tendermint_tpu.utils import threadledger, tracing
from tendermint_tpu.utils.db import MemDB, SQLiteDB
from tendermint_tpu.utils.metrics import REGISTRY
from tendermint_tpu.utils.threadledger import (GilProbe, ThreadLedger,
                                               role_of, thread_cpu_s)
from tendermint_tpu.utils.tracing import PH_COUNTER, FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CLOCK = 2e-6       # an epoch timestamp holds a quarter of a microsecond


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(s):
    return s["ts"] + s["dur"]


# -- the quantity record -------------------------------------------------------

@pytest.mark.parametrize("value_s", [0.0, 1e-4, 0.3, 7.5])
def test_a_quantity_is_kept_exactly_when_its_windows_span_is(value_s):
    """Three windows of 0.4 s; the benchmark keeps what ended after the
    first window's end, up to and including the last one's: window 0's
    quantity is out, the others' in, whatever their size (7.5 s is 16
    threads' CPU in a 0.4 s window, and starts before window 0 does)."""
    from benchmark.lib import accounting
    rec = FlightRecorder(64)
    t = tracing.now_epoch()
    ends = [t + 0.4 * (k + 1) for k in range(3)]
    for k, hi in enumerate(ends):
        rec.record("fastsync.window", hi - 0.4, 0.4,
                   {"window": 1 + 64 * k, "blocks": 64})
        rec.quantity("cpu.recv", value_s, hi)
    spans = rec.snapshot()
    t_first, t_last, windows = accounting.measured_interval(
        spans, t, ends[-1] + 1.0)
    assert (t_first, t_last) == (_end(spans[0]), _end(spans[4]))
    kept = accounting.in_interval(spans, t_first, t_last)
    assert [s["name"] for s in kept] == ["fastsync.window", "cpu.recv"] * 2
    assert [s for s in kept if s["name"] == "fastsync.window"] == windows
    for q, hi in zip(_named(spans, "cpu.recv"), ends):
        assert q["ph"] == PH_COUNTER and q["dur"] == value_s
        assert "cat" not in q and "args" not in q
        assert _end(q) == pytest.approx(hi - 1e-6, abs=CLOCK / 2)
        assert _end(q) < hi


def test_quantities_read_like_any_record_and_render_as_counter_events():
    rec = FlightRecorder(8)
    t = tracing.now_epoch()
    rec.record("fastsync.window", t - 0.5, 0.5, {"window": 1, "blocks": 64})
    rec.quantity("offcpu.apply", 0.25, t)
    assert [s["name"] for s in rec.since(t - 0.1)] == \
        ["fastsync.window", "offcpu.apply"]
    assert rec.since(t - 0.1, categorized=True) == []   # CAT_NONE, no prefix
    x, c = [e for e in rec.to_chrome_trace()["traceEvents"]
            if e["ph"] != "M"]
    assert x["ph"] == "X" and x["dur"] == pytest.approx(0.5e6)
    assert c["ph"] == "C" and c["name"] == "offcpu.apply"
    assert c["args"] == {"seconds": 0.25} and "dur" not in c
    assert c["ts"] == pytest.approx((t - 1e-6) * 1e6, abs=1.0)


# -- roles, and the clock of a thread ---------------------------------------------

@pytest.mark.parametrize("name,role", [
    ("fast-sync", "apply"), ("fastsync-lookahead", "lookahead"),
    ("mconn-recv", "recv"), ("mconn-send", "send"),
    ("batchplane", "other"), ("crypto-precompile", "other"),
    ("MainThread", "other"), ("Thread-7 (process_request_thread)", "other"),
    ("gil-lag", "other"), ("", "other")])
def test_a_threads_role_goes_by_its_names_prefix(name, role):
    assert role_of(name) == role
    assert role in threadledger.ROLES


def test_a_threads_cpu_clock_is_read_by_its_kernel_id():
    mine = thread_cpu_s(threading.current_thread())
    assert 0.0 <= time.thread_time() - mine < 0.05
    never = threading.Thread(target=lambda: None)
    assert thread_cpu_s(never) is None
    gone = threading.Thread(target=lambda: None)
    gone.start()
    gone.join(5)
    assert not gone.is_alive() and gone.native_id is not None
    # `join` returns when the thread's Python is over; the kernel's thread
    # goes a moment later, and then the read is a clean EINVAL: None
    deadline = time.monotonic() + 5
    while thread_cpu_s(gone) is not None and time.monotonic() < deadline:
        time.sleep(0.001)
    assert thread_cpu_s(gone) is None


def _spin(cpu_s, done=None, hold=None):
    """Use `cpu_s` of this thread's own CPU with the GIL released most of
    the time (sha256 of 1 MiB), say so, then stay alive until released."""
    buf = bytes(1 << 20)
    while time.thread_time() < cpu_s:
        hashlib.sha256(buf).digest()
    if done is not None:
        done.append(time.thread_time())
    if hold is not None:
        hold.wait(20)


def _ledger():
    rec = FlightRecorder(256)
    return ThreadLedger(rec), rec


def test_the_first_reading_is_the_baseline_and_writes_nothing():
    ledger, rec = _ledger()
    ledger.window_ended(tracing.now_epoch())
    assert rec.total == 0
    ledger.window_ended(tracing.now_epoch())
    assert [s["name"] for s in rec.snapshot()] == [
        "cpu.apply", "cpu.lookahead", "cpu.recv", "cpu.send",
        "cpu.other", "cpu.process"]


def test_two_spinning_threads_of_a_role_read_as_their_cpu():
    ledger, rec = _ledger()
    ledger.window_ended(tracing.now_epoch())
    before = dict(REGISTRY.thread_cpu_seconds.items())
    done, hold = [], threading.Event()
    threads = [threading.Thread(target=_spin, args=(0.2, done, hold),
                                name="mconn-recv", daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 20
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(done) == 2
    hi = tracing.now_epoch()
    ledger.window_ended(hi)
    hold.set()
    for t in threads:
        t.join(5)
    got = {s["name"]: s for s in rec.snapshot()}
    recv = got["cpu.recv"]["dur"]
    # new since the baseline, so their whole CPU: 0.2 s each, read while
    # they wait (what they used after saying `done` is a few us)
    assert 0.3 <= recv <= 0.5
    assert recv == pytest.approx(sum(done), abs=0.02)
    assert all(_end(s) < hi and _end(s) == pytest.approx(hi - 1e-6,
                                                         abs=CLOCK)
               for s in got.values())
    # the whole process holds the roles' sum
    roles = sum(got["cpu." + r]["dur"] for r in threadledger.ROLES)
    assert recv <= roles <= got["cpu.process"]["dur"] + 0.02
    after = dict(REGISTRY.thread_cpu_seconds.items())
    assert after["recv"] - before.get("recv", 0.0) == pytest.approx(recv)
    assert after["process"] - before.get("process", 0.0) == \
        pytest.approx(got["cpu.process"]["dur"])


def test_a_thread_that_exits_before_its_clock_is_read_costs_nothing(
        monkeypatch):
    """`enumerate()` still lists a thread that has exited by the time its
    clock is read: no exception, and nothing under its role.  What it
    used since the reading before is lost to the roles."""
    ledger, rec = _ledger()
    done, hold = [], threading.Event()

    def sender():
        _spin(0.05, done, hold)
        _spin(0.1)

    t = threading.Thread(target=sender, name="mconn-send", daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while not done and time.monotonic() < deadline:
        time.sleep(0.005)
    assert done
    ledger.window_ended(tracing.now_epoch())       # baseline, `t` in it
    listed = threading.enumerate()
    assert t in listed
    hold.set()
    t.join(20)
    deadline = time.monotonic() + 5
    while thread_cpu_s(t) is not None and time.monotonic() < deadline:
        time.sleep(0.001)
    assert not t.is_alive() and thread_cpu_s(t) is None
    monkeypatch.setattr(threading, "enumerate", lambda: listed)
    ledger.window_ended(tracing.now_epoch())
    got = {s["name"]: s["dur"] for s in rec.snapshot()}
    assert len(got) == 6
    # its last 0.05 s are in no role (other tests' idle senders may be)
    assert got["cpu.send"] < 0.03 and got["cpu.other"] < 0.03
    assert got["cpu.process"] >= 0.04


def test_the_lookaheads_own_report_lands_in_the_window_it_ended_in():
    ledger, rec = _ledger()
    ledger.window_ended(tracing.now_epoch())
    said = []

    def lookahead():
        _spin(0.05)
        said.append(time.thread_time())
        ledger.thread_exiting()

    t = threading.Thread(target=lookahead, name="fastsync-lookahead")
    t.start()
    # read while it runs: a self-reporting role is not read from outside
    ledger.window_ended(tracing.now_epoch())
    t.join(20)
    assert said
    ledger.window_ended(tracing.now_epoch())
    ledger.window_ended(tracing.now_epoch())
    ahead = [s["dur"] for s in _named(rec.snapshot(), "cpu.lookahead")]
    assert ahead[0] == 0.0 and ahead[2] == 0.0
    assert ahead[1] == pytest.approx(said[0], abs=0.005) and ahead[1] >= 0.05


# -- sqlite on and off the CPU ---------------------------------------------------

def _new_tally():
    """The calling thread's tally as a thread starts with it."""
    threadledger._writes.__dict__.clear()


def test_a_threads_writes_split_into_on_and_off_the_cpu(tmp_path):
    _new_tally()
    mem = MemDB()
    mem.set(b"k", b"v")
    mem.set_batch([(b"a", b"1"), (b"b", b"2")])
    mem.delete(b"k")
    assert threadledger.take_writes() == (0.0, 0.0)     # MemDB: nothing
    db = SQLiteDB(str(tmp_path / "kv.db"))
    t0 = tracing.now_epoch()
    for i in range(40):
        db.set(b"key%d" % i, bytes(2000))
    db.set_batch([(b"b%d" % i, bytes(100)) for i in range(900)])
    db.delete(b"key0")
    me = threading.get_ident()
    writes = [s for s in tracing.RECORDER.since(t0)
              if s["name"] == "db.write" and s["tid"] == me and
              s["ts"] >= t0]
    assert len(writes) == 42
    other = []

    def elsewhere():
        for i in range(threadledger._SAMPLED_WRITES):
            db.set(b"other%d" % i, b"x")
        other.append(threadledger.take_writes())

    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(10)
    on, off = threadledger.take_writes()
    assert on > 0.0 and off >= 0.0
    assert on + off == pytest.approx(sum(s["dur"] for s in writes),
                                     rel=1e-9)
    # a tally is its thread's own, and taking it empties it
    assert other and other[0][0] > 0.0 and other[0] != (on, off)
    assert threadledger.take_writes() == (0.0, 0.0)
    db.close()


def _ticking_clock(monkeypatch, reads):
    """`time.thread_time` answers from `reads`, and counts its calls."""
    calls = []

    def thread_time():
        calls.append(None)
        return reads.pop(0)

    monkeypatch.setattr(time, "thread_time", thread_time)
    return calls


def test_one_write_in_eight_reads_the_cpu_clock(monkeypatch):
    """A read of the CPU clock is a system call on the chip's host: one
    transaction in eight pays two, and the whole wall is split by the
    share the sampled ones were on the CPU."""
    _new_tally()
    calls = _ticking_clock(monkeypatch, [1.0, 1.001, 2.0, 2.003])
    for k in range(16):
        threadledger.tally_write(0.004, threadledger.write_begins())
        if k == 6:
            # none sampled yet: nothing to split by, and nothing taken
            assert threadledger.take_writes() == (0.0, 0.0)
    assert len(calls) == 4
    on, off = threadledger.take_writes()
    # 4 ms of the sampled 8 ms on the CPU: half of the 64 ms
    assert on == pytest.approx(0.032) and off == pytest.approx(0.032)


def test_a_cpu_clock_that_moves_in_ticks_is_summed_not_held_to_each_write(
        monkeypatch):
    """The chip's host counts a thread's CPU in ticks of 10 ms: a 2 ms
    transaction reads 0 or 10.  Held to its own wall, each tick would
    lose 8 ms; only the sum is held to the wall."""
    _new_tally()
    monkeypatch.setattr(threadledger, "_SAMPLED_WRITES", 1)
    _ticking_clock(monkeypatch, [0.0] * 18 + [0.0, 0.01] + [0.0, 0.01])
    for _ in range(10):
        threadledger.tally_write(0.002, threadledger.write_begins())
    on, off = threadledger.take_writes()
    assert on == pytest.approx(0.010) and off == pytest.approx(0.010)
    # a window that is one transaction and one tick: 10 ms of CPU in 2 ms
    # of wall.  Held to itself it would read as all on the CPU; it is
    # split by the thread's share so far, 20 ms of 22
    threadledger.tally_write(0.002, threadledger.write_begins())
    on, off = threadledger.take_writes()
    assert on == pytest.approx(0.002 * 20 / 22)
    assert off == pytest.approx(0.002 * 2 / 22)


def test_a_windows_writes_are_split_by_the_share_of_the_threads_life(
        monkeypatch):
    """A window samples 24 transactions, 20-50 ms of wall, which a clock
    of 10 ms ticks reads as 0 to 5 ticks: the window's own share would
    be noise (and, cut to [0, 1], biased), so the sampled CPU and wall
    are carried over the thread's life and every window is split by the
    running share."""
    _new_tally()
    monkeypatch.setattr(threadledger, "_SAMPLED_WRITES", 1)
    # three windows of four 5 ms transactions; the ticks fall 0, 3, 0
    _ticking_clock(monkeypatch,
                   [0.0] * 8 + [0.0, 0.01, 0.01, 0.02, 0.02, 0.03] +
                   [0.03] * 10)
    took = []
    for _window in range(3):
        for _ in range(4):
            threadledger.tally_write(0.005, threadledger.write_begins())
        took.append(threadledger.take_writes())
    assert took[0] == (0.0, 0.020)
    assert took[1] == pytest.approx((0.020 * 30 / 40, 0.020 * 10 / 40))
    assert took[2] == pytest.approx((0.020 * 30 / 60, 0.020 * 30 / 60))
    for on, off in took:
        assert on + off == pytest.approx(0.020)
    # a clock that counts more CPU than wall over a whole life: no
    # amount of seconds off the CPU is negative
    _new_tally()
    _ticking_clock(monkeypatch, [0.0, 0.01])
    threadledger.tally_write(0.002, threadledger.write_begins())
    assert threadledger.take_writes() == (0.002, 0.0)


N_BLOCKS, BATCH = 40, 8
FAST_PROBE_S = 0.02


@pytest.fixture(scope="module")
def synced(tmp_path_factory):
    """(reactor, records, names of the threads seen alive together with
    the fast-sync thread) of a short chain synced in process on sqlite."""
    t_start = tracing.now_epoch()
    beside, off = set(), threading.Event()

    def watch():
        while not off.wait(0.005):
            names = {t.name for t in threading.enumerate()}
            if "fast-sync" in names:
                beside.update(names)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    step = threadledger._PROBE_INTERVAL_S
    threadledger._PROBE_INTERVAL_S = FAST_PROBE_S   # the sync is short
    try:
        bc = fast_sync_in_process(
            "thread-ledger-chain", N_BLOCKS, BATCH,
            sqlite_dir=str(tmp_path_factory.mktemp("sync")))
    finally:
        threadledger._PROBE_INTERVAL_S = step
        off.set()
        watcher.join(10)
    # a quantity may start before the sync did: it is kept by its end
    spans = [s for s in tracing.RECORDER.since(t_start)
             if s["ts"] >= t_start or s["ph"] == PH_COUNTER]
    return bc, spans, beside


def test_a_windows_write_tally_is_its_db_write_records(synced):
    _bc, spans, _names = synced
    applies = _named(spans, "fastsync.apply")
    off = _named(spans, "offcpu.db_write")
    waits = _named(spans, "offcpu.apply")
    assert applies and len(off) == len(waits) == len(applies)
    assert not _named(spans, "oncpu.db_write")   # the rest of `db.write`
    for a, q_off, q_wait in zip(applies, off, waits):
        writes = [s for s in _named(spans, "db.write")
                  if s["tid"] == a["tid"] and
                  a["ts"] - CLOCK <= s["ts"] and _end(s) <= _end(a) + CLOCK]
        assert len(writes) == 3 * a["args"]["blocks"]
        # a share of the window's own transactions (the running share of
        # the sampled ones, 3 of a window's 24 here), so only a run's sum
        # holds the writes' wait under apply's, which cannot pass its wall
        assert 0.0 <= q_off["dur"] <= sum(s["dur"] for s in writes) * 1.01
        assert 0.0 <= q_wait["dur"] <= a["dur"]
        for q in (q_off, q_wait):
            assert q["ph"] == PH_COUNTER and a["ts"] < _end(q) <= _end(a)


def test_every_window_after_the_first_has_its_six_cpu_records(synced):
    _bc, spans, _names = synced
    windows = _named(spans, "fastsync.window")
    assert len(windows) >= 3
    for role in threadledger.ROLES + ("process",):
        qs = _named(spans, "cpu." + role)
        assert len(qs) == len(windows) - 1, role
        for q, w in zip(qs, windows[1:]):
            assert q["ph"] == PH_COUNTER and q["dur"] >= 0.0
            assert _end(q) == pytest.approx(_end(w) - 1e-6, abs=CLOCK)
            assert _end(q) < _end(w)
    assert not _named(spans, "gil.lag.mean")     # `gil.lag` is the record
    # the fast-sync thread ran its applies; the process holds every role
    assert sum(q["dur"] for q in _named(spans, "cpu.apply")) > 0.0
    for k in range(len(windows) - 1):
        roles = sum(_named(spans, "cpu." + r)[k]["dur"]
                    for r in threadledger.ROLES)
        assert roles <= _named(spans, "cpu.process")[k]["dur"] + 0.02


# -- the probe --------------------------------------------------------------------

def test_the_probe_lives_and_ends_with_the_fast_sync_thread(synced):
    bc, spans, names = synced
    assert "gil-lag" in names            # alive beside the fast-sync thread
    assert not bc._ledger.probe.is_alive()          # the switch stopped `bc`
    assert not any(t.name == "gil-lag" for t in threading.enumerate())
    lags = _named(spans, "gil.lag")
    assert lags and all(s["dur"] >= 0.0 and s["ph"] == "X" and
                        s["thread"] == "gil-lag" and "cat" not in s
                        for s in lags)
    # at most one wake an interval, each on the grid of due times
    step = FAST_PROBE_S
    for a, b in zip(lags, lags[1:]):
        n = (b["ts"] - a["ts"]) / step
        assert n == pytest.approx(round(n), abs=1e-3) and round(n) >= 1


def _tip_reactor():
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state.state import get_state
    privs, _vs = make_validators(4)
    state = get_state(MemDB(), make_genesis("probe-chain", privs))
    conns = ClientCreator("kvstore").new_app_conns()
    return BlockchainReactor(state, conns.consensus, BlockStore(MemDB()),
                             fast_sync=True)


def _probes():
    return [t for t in threading.enumerate() if t.name == "gil-lag"]


def test_the_probe_is_gone_when_stop_returns():
    bc = _tip_reactor()
    assert not _probes()
    bc.start()
    try:
        assert bc._thread.is_alive() and len(_probes()) == 1
    finally:
        bc.stop()
    assert not _probes()                          # no join after stop()
    bc._thread.join(5)
    assert not bc._thread.is_alive()
    bc.stop()                                     # twice is harmless


def test_the_probe_is_gone_after_the_hand_over():
    bc = _tip_reactor()
    at_hand_over = []

    class TipPool:
        next_height = 1

        def is_caught_up(self):
            return True

    bc.pool = TipPool()
    bc._sync_step = lambda: False
    bc.on_caught_up = lambda state: at_hand_over.append(len(_probes()))
    bc.start()
    bc._thread.join(10)
    assert not bc._thread.is_alive() and bc.handed_over
    assert at_hand_over == [1] and not _probes()


def test_the_probe_waits_longer_beside_spinning_python(monkeypatch):
    step = 0.05
    monkeypatch.setattr(threadledger, "_PROBE_INTERVAL_S", step)

    def median_lag(seconds):
        rec = FlightRecorder(256)
        probe = GilProbe(rec)
        t0 = time.monotonic()
        probe.start()
        time.sleep(seconds)
        probe.stop()
        elapsed = time.monotonic() - t0
        assert not probe.is_alive()
        lags = [s["dur"] for s in rec.snapshot()]
        # at most 20 wakes a second; a late wake skips due times
        assert 3 <= len(lags) <= elapsed / step + 1 and min(lags) >= 0.0
        return statistics.median(lags), len(lags)

    idle, n_idle = median_lag(0.6)
    off = threading.Event()

    def python_spin():
        while not off.is_set():
            sum(range(1000))

    spinners = [threading.Thread(target=python_spin, daemon=True)
                for _ in range(3)]
    for t in spinners:
        t.start()
    try:
        busy, n_busy = median_lag(0.8)
    finally:
        off.set()
        for t in spinners:
            t.join(5)
    # a wake beside three threads that never let go waits for a forced
    # hand-off (the switch interval, 5 ms) or several; idle, for none
    assert busy > idle and busy > 1e-3
    h = REGISTRY.gil_lag_seconds
    assert h.count >= n_idle + n_busy and h.sum > 0.0
