"""`benchmark/run.py`'s run, rehearsed on the CPU at a tiny size, sound
and with the timed path broken underneath; and its refusal to run
without a chip.  The rehearsal skips only the harness's look for a chip:
chain builder, children, node, window, accounting, check and result line
are the real ones."""

import json
import os
import subprocess
import sys

import pytest

import benchutil
from benchutil import REPO

# what a CPU run reports of `per_layer`: what the harness takes itself,
# and every metric read from the program's own spans (`program_span`),
# which are there on a CPU as on the chip.  Read from BENCHMARK.json, so
# that a PR that adds such a metric as data files is held to it too
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    PER_LAYER_ON_CPU = {
        "pool.link_util_pct", "batchplane.wait_ms", "rpc.status_p95_ms"} | {
        m["name"] for m in json.load(_f)["per_layer"]
        if m["source"] == "program_span"}
DEVICE_ONLY = {"kernel.verify_ms", "verify_grouped_templated_roofline",
               "device.idle_pct", "device.hbm_peak_MiB"}


def test_sound_rehearsal_is_correct_names_the_cpu_and_leaves_no_child():
    result, out = benchutil.rehearse(seed=2**31 + 17, trace=True)
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 64
    assert result["attempted"] % 64 == 0          # whole windows only
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["kind"] == "cpu"
    # a CPU run reports no device metric: they are named as not measured
    assert set(result["metrics"]) == PER_LAYER_ON_CPU
    assert set(result["not_measured"]) == DEVICE_ONLY
    assert "busy_s" not in result["device"]
    assert result["metrics"]["pool.redos"]["value"] == 0
    assert result["metrics"]["reactor.window_ms"]["value"] > 0
    assert "verdict control" in out and "NOT OK" not in out
    # every number compared, beside its limit, last in the line
    assert list(result)[-1] == "checks" and len(result["checks"]) == 13
    assert all(c["ok"] and ("at_most" in c or "at_least" in c)
               for c in result["checks"].values())
    pids = benchutil.child_pids(out)
    assert len(pids) == 2 and not any(benchutil.alive(p) for p in pids)


def test_rehearsal_with_the_verdicts_thrown_away_is_not_correct():
    """The timed path broken underneath: the device's verdicts are
    replaced by all-valid where they are produced.  The chain still
    syncs to the right hashes; the verdict control has to say no."""
    result, out = benchutil.rehearse(seed=2**31 + 18, trace=False,
                                     fault="accept_all")
    assert result["correct"] is False, out[-3000:]
    assert result["failed"] == 0                  # the chain was sound
    assert set(result["metrics"]) == {
        "sync_blocks_per_s", "boot_to_first_window_s", "setup_s"}
    bad = [ln for ln in out.splitlines() if "NOT OK" in ln]
    assert len(bad) == 1 and "verdict control" in bad[0]
    assert [k for k, c in result["checks"].items() if not c["ok"]] == [
        "control_lanes_differ"]
    assert result["checks"]["control_lanes_differ"]["value"] > 0
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(out))


@pytest.mark.parametrize("script", ["benchmark/run.py"])
def test_without_a_chip_it_exits_non_zero_and_prints_no_result(script):
    r = subprocess.run(
        [sys.executable, script, "--workload", "testnet-4v.empty-blocks",
         "--seed", "3", "--seconds", "2", "--trace", "0"], cwd=REPO,
        env=benchutil.cpu_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode == 2, r.stdout[-2000:] + r.stderr[-2000:]
    assert "jax found 1 of platform 'cpu'" in r.stderr
    assert '"correct"' not in r.stdout
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(r.stdout))
