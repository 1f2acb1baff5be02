"""Shared by the tests of the benchmark: where things are, and the tiny
CPU rehearsal of `benchmark/run.py`'s run (4 validators, 2 peers, a few
windows), told to expect the CPU, in a process of its own."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# what run.py does, minus its look for a chip: the real cell's files with
# the sizes cut to what a test can hold.  `config` and `traffic` are laid
# over the cell's files, and `prelude` is run before the cell is loaded (a
# test's own app registers itself there).  `hold_trace_s` makes the
# profiler's stop_trace() as slow as a real cell's: it returns only when
# the sync thread has ended (stopped by the harness, or at the served tip)
# and no sooner than that many seconds
REHEARSAL = """
import time
T = time.monotonic()
import json, os, sys
os.environ.setdefault("TM_FLIGHT_RECORDER_CAP", "4194304")
sys.path.insert(0, {root!r})
{prelude}
from benchmark.lib import cell as cm
cell = cm.load_cell({root!r}, "testnet-4v.empty-blocks")
cell["config"] = dict(cell["config"], validators=4, source_peers=2,
                      **{config!r})
cell["traffic"] = dict(cell["traffic"], **{traffic!r},
                       chain={{"default": {chain!r}}})
cm.TRACE_MAX_S = {trace_max_s}
if {hold_trace_s}:
    import jax.profiler
    boot, stop, seen = cm.boot_node, jax.profiler.stop_trace, {{}}

    def boot_node(*a):
        seen["node"], cfg = boot(*a)
        return seen["node"], cfg

    def slow_stop_trace():
        sync = seen["node"].switch.reactor("blockchain")._thread
        t0 = time.monotonic()
        while time.monotonic() - t0 < 120 and (
                sync.is_alive() or time.monotonic() - t0 < {hold_trace_s}):
            time.sleep(0.05)
        stop()

    cm.boot_node, jax.profiler.stop_trace = boot_node, slow_stop_trace
try:
    r = cm.run_cell({root!r}, cell, {seed}, {seconds}, {trace}, T,
                    expect_platform="cpu", known_kinds=("cpu",),
                    fault={fault!r})
except cm.MeasuredNothing as e:
    print("benchmark:", e, file=sys.stderr)
    sys.exit(cm.EXIT_MEASURED_NOTHING)
cm.report_compared(r["checks"])
print(json.dumps(r))
"""
CHAIN = {"parent_blocks_per_s": 400, "warmup_s": 8}


def cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TM_TABLE_CACHE_DIR",
                        "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


def run_rehearsal(seed: int, seconds: float = 6.0, trace: bool = True,
                  fault=None, chain=CHAIN, trace_max_s: float = 20.0,
                  hold_trace_s: float = 0.0, config=None, traffic=None,
                  prelude: str = "",
                  timeout: float = 900) -> subprocess.CompletedProcess:
    code = REHEARSAL.format(root=REPO, seed=seed, seconds=seconds,
                            trace=trace, fault=fault, chain=chain,
                            trace_max_s=trace_max_s,
                            hold_trace_s=hold_trace_s, config=config or {},
                            traffic=traffic or {}, prelude=prelude)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=cpu_env(), capture_output=True, text=True,
                          timeout=timeout)


def rehearse(seed: int, **kw):
    """(result object, stdout) of one rehearsal run that has to give a
    result; the numbers it compared are its last lines on stderr."""
    r = run_rehearsal(seed, **kw)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    compared = r.stderr.strip().splitlines()[-len(result["checks"]):]
    assert [ln.split()[2] for ln in compared] == list(result["checks"]), \
        r.stderr[-3000:]
    return result, r.stdout


def child_pids(stdout: str) -> list[int]:
    line = next(ln for ln in stdout.splitlines()
                if ln.startswith("[bench] children:"))
    return [int(w) for w in line.replace(",", " ").split() if w.isdigit()]


def alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    # a zombie still answers signal 0: look at its state
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False
