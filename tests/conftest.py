"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's strategy of running multi-node nets in-process
(reference `p2p/switch.go:495-543` MakeConnectedSwitches): we run multi-chip
sharding tests on a virtual CPU mesh so the suite needs no TPU pod.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the crypto kernels take ~1min to compile on
# the CPU backend; cache them across test runs — in the one directory the
# program itself uses (JAX_COMPILATION_CACHE_DIR, else .tm_cache/ in the
# checkout), enabled here before any test can compile.
from tendermint_tpu.crypto import backend as _cb  # noqa: E402

_cb.enable_compile_cache()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, f"rep_{rep.when}", rep)


@pytest.fixture(autouse=True)
def _flight_recorder_postmortem(request):
    """Post-mortem artifacts for the faults tier: when a `faults`-marked
    test FAILS, dump the flight-recorder Chrome trace and a rung-labeled
    metric snapshot to the scenario artifact dir (same layout and triage
    flow as `cli chaos run`; see README "Failure scenarios")."""
    yield
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.failed:
        return
    if request.node.get_closest_marker("faults") is None:
        return
    import json
    import re
    from tendermint_tpu.scenarios.engine import artifacts_root
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", request.node.nodeid)[-80:]
    d = os.path.join(artifacts_root(None), f"pytest-{safe}")
    os.makedirs(d, exist_ok=True)
    tracing.RECORDER.dump(os.path.join(d, "trace.json"))
    with open(os.path.join(d, "metrics.json"), "w") as f:
        json.dump(REGISTRY.snapshot(), f, indent=1)
    print(f"\n[faults post-mortem] trace + metrics dumped to {d}")


@pytest.fixture(autouse=True)
def _isolate_table_disk_cache(tmp_path, monkeypatch):
    """Every test gets a private comb-table disk cache: without this,
    tests would persist tables into the shared cache directory and
    later runs could verify against STALE tables whenever a test changes
    its key generation under an unchanged set_key label."""
    monkeypatch.setenv("TM_TABLE_CACHE_DIR", str(tmp_path / "_tblcache"))
