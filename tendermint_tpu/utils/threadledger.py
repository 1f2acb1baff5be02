"""Thread ledger: what the host's threads used and what they waited.

Every span of the flight recorder times one thread on the wall clock.
What bounds a catch-up is a contest BETWEEN threads (one GIL, one disk,
a fast-sync thread against 16 receive threads), which no such span can
see.  The ledger writes three readings into the same recorder:

* `cpu.<role>`, one quantity record a role a fast-sync window
  (`ThreadLedger.window_ended`): the CPU seconds every kind of Python
  thread used since the window before, by its name's prefix (`ROLES`),
  and `cpu.process` for the whole process, XLA's and the runtime's
  threads included.  Also `REGISTRY.thread_cpu_seconds{role}`.
* `offcpu.apply` and `offcpu.db_write`, one quantity record each a
  window's apply (`ThreadLedger.apply_ended`): wall less CPU of the
  apply, and the part of the fast-sync thread's sqlite transactions
  that was off the CPU (their wall, split by what one transaction in
  eight has read of the CPU clock over the thread's life:
  `write_begins`, `take_writes`).  Off the CPU inside a transaction is
  the WAL's sync and the wait to get the GIL back on the way out of
  sqlite; the rest of the `db.write` records' time is sqlite and the
  kernel on the CPU; `offcpu.apply` less `offcpu.db_write` is what
  apply's own Python lost to other threads between writes.
* `gil.lag`, a TRUE span (`GilProbe`): a thread that sleeps to a due
  time wants the GIL when it wakes and nothing else, as a thread coming
  back from `sqlite3_step` does; the span runs from the due time to the
  instant the thread runs again.  Also `REGISTRY.gil_lag_seconds`.

A quantity record (`FlightRecorder.quantity`) holds seconds of CPU or
of waiting in its `dur`, and ends with the interval it belongs to.
Where the kernel counts a thread's CPU in ticks (10 ms on the chip's
host) every `cpu.*` and `*.db_write` is a multiple of the tick: a
window's reading is a sample, the mean over a run's windows the number.

What a dead thread loses: a thread that exits between two readings
takes what it used since the first with it (a peer that hangs up, the
RPC's handler threads, which live for one request).  It still counts
under `cpu.process`.  A thread that lives for one window by design, the
look-ahead, says what it used as its last act (`thread_exiting`).
"""

from __future__ import annotations

import threading
import time

from tendermint_tpu.utils.metrics import REGISTRY
from tendermint_tpu.utils.tracing import CAT_NONE, RECORDER, perf_to_epoch

# thread-name prefix -> role; names as the program gives them
# (`blockchain/reactor.py`, `p2p/connection.py`); any other is `other`
_ROLE_BY_PREFIX = (("fast-sync", "apply"),
                   ("fastsync-lookahead", "lookahead"),
                   ("mconn-recv", "recv"),
                   ("mconn-send", "send"))
ROLES = ("apply", "lookahead", "recv", "send", "other")
# roles whose threads report their own CPU as they exit: the reading of
# the live threads leaves them out, or their seconds would count twice
_SELF_REPORTED = frozenset({"lookahead"})

# Every wake is a forced hand-off of the GIL: at 20 a second the probe
# alone cost a 4-validator catch-up 4 % (my chip runs, PR 41), ~2 ms of
# the apply thread's a wake
_PROBE_INTERVAL_S = 0.25


def role_of(thread_name: str) -> str:
    for prefix, role in _ROLE_BY_PREFIX:
        if thread_name.startswith(prefix):
            return role
    return "other"


def thread_cpu_s(thread: threading.Thread) -> float | None:
    """CPU seconds a live thread has used, or None for one that has
    exited or never started.  Read by the kernel's thread id: a Python
    thread is detached, so `time.pthread_getcpuclockid(ident)` on one
    that has just exited reads freed memory, while the kernel refuses a
    dead id cleanly (EINVAL).  `(~tid << 3) | 6` is Linux's clock id of
    a thread's scheduler CPU time, what `pthread_getcpuclockid`
    returns."""
    tid = thread.native_id
    if tid is None:
        return None
    try:
        return time.clock_gettime(((~tid) << 3) | 6)
    except OSError:
        return None


# -- a thread's sqlite transactions, on and off the CPU ----------------------
# One transaction in eight reads the CPU clock.  Where that clock is a
# system call and not the vDSO's (the chip's host, gVisor: 6 us a read,
# 12 beside busy threads, where a plain kernel reads it in 0.33), two
# reads a transaction were 3 % of a 4-validator catch-up.  Not a
# multiple of 3: a block's three stores are sampled alike.
_SAMPLED_WRITES = 8


class _Writes(threading.local):
    n = 0
    wall = 0.0                           # since the thread last took it
    sampled_wall = sampled_ran = 0.0     # over the thread's life


_writes = _Writes()


def write_begins() -> float | None:
    """Called where a transaction of the calling thread begins
    (`utils/db.py`): the thread's CPU clock if this one is sampled,
    else None; `tally_write` takes it back."""
    _writes.n += 1
    return None if _writes.n % _SAMPLED_WRITES else time.thread_time()


def tally_write(wall_s: float, cpu0: float | None) -> None:
    """One transaction of the calling thread ended: its wall clock is
    added to the thread's own tally, and for a sampled one its CPU
    (sqlite and the kernel, the GIL released) too; no record a write.
    No single transaction is held to its bounds: where the CPU clock
    moves in ticks of 10 ms (the chip's host) a 2 ms transaction reads
    0 or 10, and only the sum over many is a reading."""
    _writes.wall += wall_s
    if cpu0 is not None:
        _writes.sampled_wall += wall_s
        _writes.sampled_ran += time.thread_time() - cpu0


def take_writes() -> tuple[float, float]:
    """(on the CPU, off it): the wall clock of the calling thread's
    transactions since it last took them, split by the share of their
    wall that ALL its sampled ones so far spent on the CPU.  The share
    is the thread's running one and not the window's own: a window
    samples 24 transactions, 20-50 ms of wall, which a clock of 10 ms
    ticks reads as 0 to 5 ticks.  (0, 0), and nothing taken, while no
    transaction was sampled yet.  A clock that counts more CPU than
    wall over the whole life reads as nothing off the CPU."""
    w = _writes
    if not w.sampled_wall:
        return 0.0, 0.0
    on = w.wall * min(max(w.sampled_ran, 0.0) / w.sampled_wall, 1.0)
    got = on, w.wall - on
    w.wall = 0.0
    return got


class ThreadLedger:
    """The readings of one fast-sync thread, which calls `apply_ended`
    and `window_ended` itself; `thread_exiting` is for the threads it
    starts."""

    def __init__(self, recorder=RECORDER):
        self._recorder = recorder
        self.probe = GilProbe(recorder)
        self._cpu: dict[threading.Thread, float] | None = None
        self._process = 0.0
        self._exited = dict.fromkeys(_SELF_REPORTED, 0.0)
        self._lock = threading.Lock()

    def thread_exiting(self) -> None:
        """The calling thread's whole CPU, as its last act: counted in
        the window that ends next."""
        role = role_of(threading.current_thread().name)
        with self._lock:
            self._exited[role] += time.thread_time()

    def apply_ended(self, wall_s: float, ran_s: float,
                    end_epoch: float) -> None:
        """An apply of the calling thread took `wall_s`, of which it ran
        `ran_s`: the rest, and what the thread's transactions since the
        last apply spent off the CPU, as quantities ending at
        `end_epoch`."""
        q = self._recorder.quantity
        q("offcpu.apply", max(wall_s - ran_s, 0.0), end_epoch)
        q("offcpu.db_write", take_writes()[1], end_epoch)

    def window_ended(self, end_epoch: float) -> None:
        """Read every live thread's CPU clock; what each role and the
        process used since the last reading, as quantities ending at
        `end_epoch`.  The first reading is the baseline and writes
        nothing."""
        process = time.process_time()
        with self._lock:
            used = dict.fromkeys(ROLES, 0.0) | self._exited
            self._exited = dict.fromkeys(_SELF_REPORTED, 0.0)
        first, was, cpu = self._cpu is None, self._cpu or {}, {}
        for t in threading.enumerate():
            role = role_of(t.name)
            c = None if role in _SELF_REPORTED else thread_cpu_s(t)
            if c is not None:             # None: exited since enumerate()
                cpu[t] = c
                # a thread new since the last reading started after it
                used[role] += c - was.get(t, 0.0)
        used["process"] = process - self._process
        self._cpu, self._process = cpu, process
        if first:
            return
        for role, s in used.items():
            s = max(s, 0.0)
            self._recorder.quantity("cpu." + role, s, end_epoch)
            REGISTRY.thread_cpu_seconds.labels(role).inc(s)


class GilProbe:
    """A daemon thread `gil-lag` that sleeps to a due time every
    `_PROBE_INTERVAL_S` and records one `gil.lag` span from the due time
    to the instant it runs again.  A late wake skips to the next due
    time of the grid, so at most one wake an interval."""

    def __init__(self, recorder=RECORDER):
        self._recorder = recorder
        self._off = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="gil-lag")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """Gone when this returns (a wake away at most), from any thread
        but its own; safe to call twice, or before `start`."""
        self._off.set()
        if self._thread.is_alive():
            self._thread.join()

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def _run(self) -> None:
        due = time.perf_counter() + _PROBE_INTERVAL_S
        while not self._off.wait(max(due - time.perf_counter(), 0.0)):
            lag = max(time.perf_counter() - due, 0.0)
            self._recorder.record("gil.lag", perf_to_epoch(due), lag,
                                  cat=CAT_NONE)
            REGISTRY.gil_lag_seconds.observe(lag)
            due += _PROBE_INTERVAL_S * (int(lag / _PROBE_INTERVAL_S) + 1)
