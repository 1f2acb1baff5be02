"""chip_smoke.py and the compile cache as the chip tool and the driver
use them — exercised here on the CPU.

The smoke itself only passes on the chip (`python chip_smoke.py` through
the chip tool); tier-1 runs the same function at toy size with the
expected platform passed in, and checks the refusals around it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from tendermint_tpu import batchplane  # noqa: E402
from tendermint_tpu.crypto import backend as cb  # noqa: E402

# one full reactor window (64 blocks + the successor that carries the
# last commit) of 4 validators, 16 of the blocks with one full 64 KiB
# part; the forged chain is refused inside its first window.  The mixed
# batch is off here: its key set (the chain's plus an off-curve key) is
# one more comb-table build, half a minute on the CPU backend for
# nothing tier-1 can see; its construction is tested on its own below.
TOY = dict(n_vals=4, n_blocks=65, big_first=1, big_count=16, n_sources=2,
           forged_blocks=65, forge_at=30, wide_lanes=0, wide_templates=0)


def _cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TM_TABLE_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


def test_smoke_passes_at_toy_size_on_cpu(tmp_path):
    """The same `run()` the chip gets, at toy size, told to expect the
    CPU.  In a process of its own with ONE CPU device: under the suite's
    eight virtual devices the comb-table build alone takes a minute."""
    code = ("import json, chip_smoke\n"
            f"r = chip_smoke.run(expect_platform='cpu', seed=7, **{TOY!r})\n"
            "print(json.dumps(r))\n")
    env = _cpu_env(TM_TABLE_CACHE_DIR=str(tmp_path / "tables"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    out = r.stdout
    assert json.loads(out.strip().splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "blocks synced: 64" in out
    assert "device part-hash windows: [16]" in out
    assert "fallback calls: 0" in out
    assert "commit 30 refused, both deliverers of 31 banned" in out
    assert "one-op helper compiles: 0 / 0 /" in out


def test_mixed_batch_is_what_it_claims_to_be(capsys):
    """The seven adversarial lane classes really are invalid to OpenSSL
    and everything else valid (phase_wide checks that before it asks the
    backend) — here with the OpenSSL backend answering, so only the
    construction is under test."""
    old = cb._current
    try:
        cb.set_backend("native")
        privs, vs = chip_smoke.make_validators(5, 4)
        chip_smoke.phase_wide(5, privs, vs,
                              dict(wide_lanes=512, wide_templates=16))
    finally:
        batchplane.reset_plane()
        cb._current = old
    assert "456 valid / 56 invalid in 7 classes: all 512 verdicts" in \
        capsys.readouterr().out


def test_smoke_refuses_a_platform_it_was_not_asked_for():
    with pytest.raises(SystemExit) as e:
        chip_smoke.run(expect_platform="tpu", seed=0, **TOY)
    assert "found platform 'cpu'" in str(e.value)


def test_chip_smoke_script_fails_without_a_chip():
    """As the driver runs it in a sandbox: non-zero, names the platform
    it found, prints no result."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from tendermint_tpu.crypto import backend as cb
cb.enable_compile_cache()
jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(1024.0)).block_until_ready()
print(json.dumps({"jax_dir": jax.config.jax_compilation_cache_dir,
                  "ours": cb.compile_cache_dir(),
                  "table": cb.TpuBackend._table_cache_path(b"k")}))
"""


def _run_cache_probe(**env) -> dict:
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=REPO, timeout=120,
        capture_output=True, text=True,
        env=_cpu_env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env))
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory
    in code: executables and comb tables land there and the in-checkout
    path is untouched.  Unset, both go to the one path in the checkout."""
    checkout = os.path.join(REPO, ".tm_cache")
    before = set(os.listdir(checkout)) if os.path.isdir(checkout) else None
    placed = str(tmp_path / "placed")
    got = _run_cache_probe(JAX_COMPILATION_CACHE_DIR=placed)
    assert got["jax_dir"] == got["ours"] == placed
    assert got["table"].startswith(os.path.join(placed, "tables") + os.sep)
    assert any(n.startswith("jit__lambda") for n in os.listdir(placed)), \
        "nothing was cached where the env said"
    after = set(os.listdir(checkout)) if os.path.isdir(checkout) else None
    assert after == before, "the in-checkout cache was touched"

    got = _run_cache_probe()
    assert got["jax_dir"] == got["ours"] == checkout
    assert got["table"].startswith(os.path.join(checkout, "tables") + os.sep)
    assert any(n.startswith("jit__lambda") for n in os.listdir(checkout))
