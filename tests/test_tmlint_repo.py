"""Tier-1 gate: tmlint over the real package must report zero
non-baselined findings.  Policy: hot-path modules (ops/, crypto/,
parallel/) may never be baselined — a new implicit sync there fails
even if someone grandfathers it."""

import os
import subprocess
import sys

import pytest

from tendermint_tpu.analysis import (baseline_path, lint_paths,
                                     load_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOT_DIRS = ("ops/", "crypto/", "parallel/")


def repo_paths():
    return [os.path.join(REPO, "tendermint_tpu")]


@pytest.mark.lint
def test_package_has_no_fresh_findings():
    res = lint_paths(repo_paths(), root=REPO)
    assert res.files > 50, "lint saw suspiciously few files"
    assert not res.errors, res.errors
    fresh = res.fresh(load_baseline())
    assert fresh == [], "\n" + "\n".join(f.render() for f in fresh)


@pytest.mark.lint
def test_baseline_never_covers_hot_path_modules():
    import json
    with open(baseline_path()) as f:
        doc = json.load(f)
    offenders = [e for e in doc["findings"]
                 if e["path"].partition("tendermint_tpu/")[2]
                 .startswith(HOT_DIRS)]
    assert offenders == [], (
        "hot-path findings must be fixed, not baselined: "
        + ", ".join(e["fingerprint"] for e in offenders))


@pytest.mark.lint
def test_cli_lint_exits_zero_on_repo():
    out = subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "lint", "--json"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    import json
    doc = json.loads(out.stdout)
    assert doc["fresh_count"] == 0
