"""Shared by the tests of the benchmark: where things are, and the tiny
CPU rehearsal of `benchmark/run.py`'s run (4 validators, 2 peers, a few
windows), told to expect the CPU, in a process of its own."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# -- what is held of EVERY configuration and EVERY cell ----------------------------
# Each rule is a function of (root, bench, entry): the tree that holds the
# files, its BENCHMARK.json as read, one entry of `configs` or `workloads`.
# The parametrised tests call them on REPO and the tree's own file, one case
# an entry; `test_bench_absent_cell.py`'s guard calls them on a copy with a
# candidate cell appended, so a rule a `model_config` PR would trip on fails
# in the PR that writes the rule.  A new test that holds something of every
# configuration or cell is written as such a function and added to
# ENTRY_RULES (benchmark/README.md, "Adding a cell as files").

def _json_at(root: str, *path) -> dict:
    with open(os.path.join(root, *path)) as f:
        return json.load(f)


def config_entry_and_file_hold(root: str, bench: dict, c: dict) -> None:
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["source"]) <= 200
    assert len(c["why"]) <= 200 and len(c["reduced"]) <= 16
    assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    cfg = _json_at(root, c["file"])
    assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    assert cfg["guarantees"] and cfg["assumed"] and cfg["chips"] == 1
    assert all(NAME.match(k) and k in cfg for k in c["reduced"])
    assert any(w["config"] == c["name"] for w in bench["workloads"])


def workload_entry_and_files_hold(root: str, bench: dict, w: dict) -> None:
    from benchmark.lib import cell as cell_mod
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = cell_mod.load_cell(root, w["name"])
    assert cell["traffic"]["name"] == w["traffic"]
    assert set(cell["traffic"]["block"]) == {"txs_per_block", "tx_bytes",
                                             "keys"}
    # the chain served is whole windows plus the block with the last
    # commit, and grows with the window
    n = cell_mod.chain_blocks(cell, bench["run_seconds"])
    assert n % 64 == 1 and n > cell_mod.chain_blocks(cell, 5)
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


# the app each ACCEPTED configuration was accepted with (None: the
# program's default), a record beside the rule: a name that is not here
# is held to the rule alone
ACCEPTED_APPS = {"catchup-100v": None, "testnet-4v": None,
                 "catchup-1ktx-100v": None,
                 "catchup-churn-100v": "valset_kvstore",
                 "catchup-300v": None, "catchup-absent-100v": None}


def config_states_an_app_that_fits(root: str, bench: dict, c: dict) -> None:
    """The app a configuration states is in the program's registry and
    fits the mix of EVERY cell that runs it (`cell.app_fits_plans`:
    `val:` txs come back as `EndBlock` diffs exactly where the mix states
    a `valset` or a `powers` plan), a node booted on it is the deployment
    as stated, and a node on any other app is an error."""
    from benchmark.lib import cell as cell_mod
    from tendermint_tpu.config import Config
    cfg = _json_at(root, c["file"])
    default = Config().base.proxy_app
    if c["name"] in ACCEPTED_APPS:
        assert cfg["app"] == (ACCEPTED_APPS[c["name"]] or default)
    app = cfg.get("app", default)
    mixes = [w["traffic"] for w in bench["workloads"]
             if w["config"] == c["name"]]
    assert mixes
    for mix in mixes:              # which looks the app up in the registry
        cell_mod.app_fits_plans(
            cfg, _json_at(root, "benchmark", "traffic", mix + ".json"))
    booted = Config()
    booted.base.proxy_app = app
    cell_mod.stated_as_run(cfg, booted)
    if "app" in cfg:
        booted.base.proxy_app = "counter" if app != "counter" else "nilapp"
        with pytest.raises(RuntimeError, match="'app'"):
            cell_mod.stated_as_run(cfg, booted)


ENTRY_RULES = {"configs": (config_entry_and_file_hold,
                           config_states_an_app_that_fits),
               "workloads": (workload_entry_and_files_hold,)}


def every_entry_holds(root: str, bench: dict) -> int:
    """Every rule of ENTRY_RULES held to every entry of its list; how
    many (rule, entry) pairs that was."""
    pairs = [(rule, entry) for key, rules in ENTRY_RULES.items()
             for entry in bench[key] for rule in rules]
    for rule, entry in pairs:
        rule(root, bench, entry)
    return len(pairs)

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


def fast_sync(built: dict, chain_id: str, app: str, tip: int):
    """The built chain from the benchmark's own source store through the
    real pool, reactor, look-ahead and `apply_window` (8-block windows,
    the native backend) up to `tip`; returns the syncing reactor, stopped."""
    from benchmark.lib import chain, source_child
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.p2p import connect_switches, make_switch
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    gen = chain.genesis_doc(chain.genesis_dict(chain_id,
                                               built["valsets"][0][1]))
    fast = P2PConfig(laddr="", pex=False, send_rate=64 << 20,
                     recv_rate=64 << 20)
    src = BlockchainReactor(get_state(MemDB(), gen), None,
                            source_child.ServedStore(built["encoded"]),
                            fast_sync=False)
    src_sw = make_switch(chain_id, {"blockchain": src}, config=fast)
    bc = BlockchainReactor(
        get_state(MemDB(), gen),
        ClientCreator(app).new_app_conns().consensus,
        BlockStore(MemDB()), fast_sync=True, batch_size=8)
    sync_sw = make_switch(chain_id, {"blockchain": bc}, config=fast)
    old = cb._current
    cb.set_backend("native")
    src_sw.start()
    sync_sw.start()
    try:
        connect_switches(sync_sw, src_sw)
        deadline = time.time() + 60
        while (bc.state.last_block_height < tip and time.time() < deadline):
            time.sleep(0.02)
        assert bc.state.last_block_height == tip, bc.pool.status()
    finally:
        src_sw.stop()
        sync_sw.stop()
        bc.stop()
        if bc._thread is not None:
            bc._thread.join(timeout=10)
        cb._current = old
    return bc


def child_index(tmp_path, spec: dict):
    """(the ready line, the index) of a source child run on `spec`, which
    gets its `index_path` here."""
    from benchmark.lib import children
    index_path = str(tmp_path / "index.json")
    spec_path = str(tmp_path / "spec.json")
    with open(spec_path, "w") as f:
        json.dump(dict(spec, index_path=index_path), f)
    kids = children.Children(REPO)
    try:
        child = kids.start("benchmark.lib.source_child", spec_path)
        ready = kids.read_json_line(child, 120, "the source child")
    finally:
        kids.stop_all()
    with open(index_path) as f:
        return ready, json.load(f)
