"""Native C++ merkle engine vs the host reference implementation."""

import os
import subprocess
import sys

import numpy as np
import pytest

from tendermint_tpu.types import merkle as host
from tendermint_tpu.utils import nativelib

pytestmark = pytest.mark.skipif(nativelib.get() is None,
                                reason="native toolchain unavailable")


def test_leaf_hashes_match_host():
    rng = np.random.default_rng(0)
    msgs = rng.integers(0, 256, (100, 77), dtype=np.uint8)
    got = nativelib.leaf_hashes(msgs)
    for i in range(100):
        assert got[i].tobytes() == host.leaf_hash(msgs[i].tobytes())


@pytest.mark.parametrize("n", [1, 2, 3, 7, 16, 100])
def test_merkle_roots_match_host(n):
    rng = np.random.default_rng(n)
    leaves = rng.integers(0, 256, (4, n, 33), dtype=np.uint8)
    got = nativelib.merkle_roots(leaves)
    for t in range(4):
        want = host.root([leaves[t, i].tobytes() for i in range(n)])
        assert got[t].tobytes() == want


def test_reuse_is_decided_by_source_hash_not_mtime(tmp_path, monkeypatch):
    """The .so is reused only when the hash recorded beside it at build
    time equals the source's: a copied tree has fresh mtimes everywhere,
    and git never carries the binary."""
    so = tmp_path / "libtmhash.so"
    rec = tmp_path / "libtmhash.so.src.sha256"
    monkeypatch.setattr(nativelib, "_SO", str(so))
    monkeypatch.setattr(nativelib, "_SO_SRC_HASH", str(rec))
    h = nativelib._src_hash()
    assert not nativelib._up_to_date(h)          # nothing built yet
    so.write_bytes(b"\x7fELF")
    assert not nativelib._up_to_date(h)          # binary, no record
    rec.write_text("0" * 64 + "\n")
    assert not nativelib._up_to_date(h)          # built from other source
    rec.write_text(h + "\n")
    assert nativelib._up_to_date(h)
    so.unlink()
    assert not nativelib._up_to_date(h)          # record, no binary
    assert nativelib.build_status in ("built", "reused")


_BUILD_IN = """
import sys
from tendermint_tpu.utils import nativelib as n
n._SO = sys.argv[1]
n._SO_SRC_HASH = n._SO + ".src.sha256"
lib = n.get()
import numpy as np
got = n.leaf_hashes(np.zeros((1, 8), dtype=np.uint8))
print(n.build_status, lib is not None and hasattr(lib, "tm_link_recv"),
      bytes(got[0]).hex())
"""


def test_two_processes_building_at_once_both_load_the_library(tmp_path):
    """The node and its source child may both find a fresh checkout: each
    builds under a name of its own and renames, so neither loads, or
    records as built, a file the other is still writing."""
    so = str(tmp_path / "libtmhash.so")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    procs = [subprocess.Popen([sys.executable, "-c", _BUILD_IN, so],
                              env=env, stdout=subprocess.PIPE, text=True)
             for _ in range(2)]
    outs = [p.communicate(timeout=240)[0].split() for p in procs]
    assert [p.returncode for p in procs] == [0, 0]
    want = host.leaf_hash(bytes(8)).hex()
    for status, loaded, digest in outs:
        assert status in ("built", "reused") and loaded == "True"
        assert digest == want
    assert "built" in [o[0] for o in outs]
    assert sorted(os.listdir(tmp_path)) == ["libtmhash.so",
                                            "libtmhash.so.src.sha256"]
    third = subprocess.run([sys.executable, "-c", _BUILD_IN, so], env=env,
                           capture_output=True, text=True, timeout=240)
    assert third.stdout.split()[:2] == ["reused", "True"]
