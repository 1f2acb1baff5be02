"""Capture-proof bench harness tests (bench.py): atomic partial-results
checkpointing, headline-so-far selection, the wall-clock budget manager,
the fixture cache, and the SIGTERM flush path — the guarantee that a
`timeout`-killed bench still leaves a parseable report (one such run
died at rc=124 with nothing parsed)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_headline_prefers_config3_then_config1():
    anchor = {"native_scalar_sigs_per_sec": 1000.0}
    assert bench._headline({})["metric"] == "bench_failed"
    h1 = bench._headline({**anchor, "config1": {"sigs_per_sec": 5000.0}})
    assert h1["metric"] == "batch_verify_sigs_per_sec"
    assert h1["vs_baseline"] == 5.0
    h3 = bench._headline({**anchor,
                          "config1": {"sigs_per_sec": 5000.0},
                          "config3": {"sigs_per_sec": 9000.0}})
    assert h3["metric"] == "fastsync_replay_commit_sigs_per_sec"
    assert h3["value"] == 9000.0
    # no anchor recorded yet: headline still renders, ratio degrades to 0
    h = bench._headline({"config1": {"sigs_per_sec": 5000.0}})
    assert h["vs_baseline"] == 0


def test_checkpoint_records_atomically(tmp_path):
    path = str(tmp_path / "partial.json")
    ck = bench.BenchCheckpoint(path)
    ck.record("native_scalar_sigs_per_sec", 1000.0)
    ck.record("config1", {"sigs_per_sec": 4000.0})
    with open(path) as f:
        doc = json.load(f)
    assert doc["partial"] is True
    assert doc["results"]["config1"]["sigs_per_sec"] == 4000.0
    assert doc["headline"]["metric"] == "batch_verify_sigs_per_sec"
    assert not os.path.exists(path + ".tmp")
    ck.flush(final=True)
    with open(path) as f:
        assert json.load(f)["partial"] is False


def test_budget_manager():
    b = bench.BudgetManager(0.0)            # no deadline: everything fits
    assert b.allows(10_000.0)
    assert b.remaining() == float("inf")
    b = bench.BudgetManager(60.0)
    assert b.allows(5.0, "small step")
    assert not b.allows(120.0, "too big")
    assert 0 < b.remaining() <= 60.0


def test_fixture_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("TM_BENCH_CACHE_DIR", str(tmp_path))
    # salt no longer keys the cache: retries re-salt from the memoized
    # base fixture instead of building a second on-disk entry
    path = bench._fixture_cache_file(4, 10, 128)
    assert str(tmp_path) in path
    assert bench._fixture_cache_load(path) is None
    hashes = [b"", b"\x01" * 20, b"\x02" * 20]
    sigs = np.arange(8 * 64, dtype=np.uint8).reshape(8, 64)
    bench._fixture_cache_save(path, hashes, sigs)
    got = bench._fixture_cache_load(path)
    assert got is not None
    assert got[0] == hashes
    assert (got[1] == sigs).all()
    # over the size cap: silently not cached
    monkeypatch.setenv("TM_BENCH_CACHE_MAX_MB", "0.0001")
    path2 = bench._fixture_cache_file(4, 11, 128)
    bench._fixture_cache_save(path2, hashes, sigs)
    assert bench._fixture_cache_load(path2) is None


_DRIVER = r"""
import json, os, signal, sys, time
sys.path.insert(0, {repo!r})
import bench

ck = bench.BenchCheckpoint({partial!r}, trace_path={trace!r})
ck.install_signal_handlers()
ck.record("native_scalar_sigs_per_sec", 1000.0)
ck.record("config0", {{"config": 0, "blocks_per_sec": 50.0}})
ck.record("config1", {{"config": 1, "sigs_per_sec": 42000.0}})
from tendermint_tpu.utils import tracing
with tracing.span("bench.fixture_build", n_blocks=10):
    pass
print("READY", flush=True)
time.sleep(60)          # "mid-config": killed here by the test
"""


def test_sigterm_mid_run_leaves_parseable_partial(tmp_path):
    """Kill the bench process with SIGTERM while a config is 'running':
    the partial JSON on disk must parse and contain every completed
    config, the last stdout line must be the headline-so-far JSON, the
    trace file must be valid Chrome trace JSON, and the exit code must
    be the timeout convention (124)."""
    partial = str(tmp_path / "partial.json")
    trace = str(tmp_path / "trace.json")
    src = _DRIVER.format(repo=REPO, partial=partial, trace=trace)
    proc = subprocess.Popen([sys.executable, "-c", src],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=REPO)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 124, err
    with open(partial) as f:
        doc = json.load(f)
    assert doc["partial"] is True
    assert doc["results"]["config0"]["blocks_per_sec"] == 50.0
    assert doc["results"]["config1"]["sigs_per_sec"] == 42000.0
    last = out.strip().splitlines()[-1]
    headline = json.loads(last)
    assert headline["metric"] == "batch_verify_sigs_per_sec"
    assert headline["value"] == 42000.0
    assert headline["vs_baseline"] == 42.0
    with open(trace) as f:
        tdoc = json.load(f)
    assert any(e["name"] == "bench.fixture_build"
               for e in tdoc["traceEvents"])


def test_sigterm_during_c_call_still_flushes(tmp_path):
    """A SIGTERM landing while the main thread is inside a long C call
    (the shape of an XLA compile) must still flush: the Python-level
    handler is deferred until the call returns, so the wakeup-fd watcher
    thread has to do it.  The pbkdf2 below is pure C for minutes; only
    the watcher path can exit within the communicate timeout."""
    partial = str(tmp_path / "p.json")
    src = _DRIVER.format(repo=REPO, partial=partial, trace=None)
    src = src.replace(
        "time.sleep(60)",
        "import hashlib; "
        "hashlib.pbkdf2_hmac('sha256', b'x', b'y', 1_000_000_000)")
    proc = subprocess.Popen([sys.executable, "-c", src],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=REPO)
    try:
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.3)          # let the main thread enter the C call
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 124, err
    with open(partial) as f:
        assert json.load(f)["results"]["config1"]["sigs_per_sec"] == 42000.0
    assert json.loads(out.strip().splitlines()[-1])["value"] == 42000.0


def test_sigalrm_handler_installed(tmp_path):
    """SIGALRM takes the same flush path (a bench run can arm an alarm
    as its own deadline)."""
    partial = str(tmp_path / "p.json")
    src = _DRIVER.format(repo=REPO, partial=partial, trace=None)
    src = src.replace("time.sleep(60)",
                      "signal.alarm(1); time.sleep(60)")
    proc = subprocess.run([sys.executable, "-c", src],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=30)
    assert proc.returncode == 124, proc.stderr
    with open(partial) as f:
        assert json.load(f)["results"]["config1"]["sigs_per_sec"] == 42000.0
