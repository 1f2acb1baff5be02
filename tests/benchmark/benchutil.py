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
# the sizes cut to what a test can hold
REHEARSAL = """
import time
T = time.monotonic()
import json, os, sys
os.environ.setdefault("TM_FLIGHT_RECORDER_CAP", "1048576")
sys.path.insert(0, {root!r})
from benchmark.lib import cell as cm
cell = cm.load_cell({root!r}, "testnet-4v.empty-blocks")
cell["config"] = dict(cell["config"], validators=4, source_peers=2)
cell["traffic"] = dict(cell["traffic"], chain={{"default": {{
    "parent_blocks_per_s": 400, "warmup_s": 8}}}})
r = cm.run_cell({root!r}, cell, {seed}, {seconds}, {trace}, T,
                expect_platform="cpu", known_kinds=("cpu",), fault={fault!r})
print(json.dumps(r))
"""


def cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TM_TABLE_CACHE_DIR",
                        "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


def rehearse(seed: int, seconds: float = 6.0, trace: bool = True,
             fault=None):
    """(result object, stdout) of one rehearsal run."""
    code = REHEARSAL.format(root=REPO, seed=seed, seconds=seconds,
                            trace=trace, fault=fault)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=cpu_env(),
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stdout


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
