"""A validator set wider than any the chip had run before PR 39 (100
members, V bucket 128), on the host side: what a 300-vote commit and a
19,200-lane window are to the decoder, the lane builders and the
backend's bookkeeping.  Nothing here builds a comb table or compiles a
kernel (the table-backed comparison with OpenSSL is
`tests/test_wide_set_device.py`); the sets are the benchmark builder's
(`benchmark/lib/chain.py`, OpenSSL keys), the signatures random where
only their place matters."""

import json
import os
import re
import sys

import numpy as np
import pytest

from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
from tendermint_tpu.types import Commit
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.validator import window_commit_lanes
from tendermint_tpu.utils import metrics, tracing
from tendermint_tpu.utils.metrics import REGISTRY
from tests.test_wide_set_device import _since, openssl_verdicts
from tests.test_window_lanes import (CHAIN, assert_windows_equal,
                                     per_block_reference, rand_bid,
                                     wire_commit)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.lib import chain, control  # noqa: E402

SEED = 2**31 + 3902
WINDOW = 64                      # the reactor's DEFAULT_BATCH


@pytest.fixture(scope="module")
def sets():
    """{size: (signing seeds in set order, ValidatorSet)}."""
    return {n: chain.make_validators(SEED, n) for n in (130, 140, 300)}


@pytest.mark.parametrize("n_vals", [130, 140, 300])
def test_a_wide_commit_stays_in_its_wire_bytes(sets, n_vals):
    """`Commit.decode` keeps a full commit of any width as the bytes it
    came in (one big-integer compare over the vote records, PR 34):
    `encode()` hands the same bytes back, 188 B a vote."""
    _, vs = sets[n_vals]
    rng = np.random.default_rng(n_vals)
    bid = rand_bid(rng)
    commit = wire_commit(rng, vs, bid, 7)
    wire = commit.encode()
    again = Commit.decode(Reader(wire))
    assert again.wire_columns() is not None
    assert again.encode() == wire
    assert again.size() == n_vals and again.height() == 7
    assert 185 * n_vals < len(wire) < 192 * n_vals
    # the signature column is the votes' signatures, in set order
    assert again.wire_columns()[1] == b"".join(
        v.signature for v in again.precommits)


@pytest.mark.parametrize("n_vals", [140, 300])
def test_a_window_of_wide_commits_is_the_per_vote_path_column_for_column(
        sets, n_vals):
    """64 wire-backed commits through the vectorized pass
    (`_window_wire_columns`, one gather of the signature columns) give
    the arrays the vote-by-vote `commit_verify_lanes` gives: 64 x V
    lanes, block-major."""
    _, vs = sets[n_vals]
    rng = np.random.default_rng(n_vals + 1)
    items = []
    for h in range(1, WINDOW + 1):
        bid = rand_bid(rng)
        items.append((bid, h, wire_commit(rng, vs, bid, h)))
    fast = window_commit_lanes(vs, CHAIN, items)
    assert_windows_equal(fast, per_block_reference(vs, items))
    assert fast[2].shape == (WINDOW * n_vals, 64)
    assert fast[3].max() == n_vals - 1 and len(fast[0]) == WINDOW


@pytest.mark.parametrize("n_vals,v_bucket,window_lanes", [
    (4, 16, 256), (100, 128, 8192), (128, 128, 8192), (129, 256, 16384),
    (140, 256, 16384), (300, 512, 32768)])
def test_the_buckets_of_a_set_and_of_its_window(n_vals, v_bucket,
                                                window_lanes):
    assert cb._bucket(n_vals) == v_bucket
    assert cb._bucket(WINDOW * n_vals) == window_lanes


@pytest.mark.parametrize("name", ["catchup-100v", "catchup-300v"])
def test_the_table_a_configuration_states_is_the_one_the_program_builds(
        name):
    """`on_device` of the file: V bucket, the table's bytes (26 windows x
    1,024 digits x V bucket x three 32-byte limbs) and the verify
    program's lanes are the program's own."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    said = cfg["on_device"].replace(",", "")
    vb = cb._bucket(cfg["validators"])
    table = COMB_WINDOWS * COMB_DIGITS * vb * 3 * 32
    assert f"V bucket {vb}," in cfg["on_device"]
    assert f"{COMB_WINDOWS} x {COMB_DIGITS} x {vb} x 96 B = {table} B" in said
    lanes = cb._bucket(cfg["window_blocks"] * cfg["validators"])
    assert re.search(rf"\b{cfg['window_blocks'] * cfg['validators']} commit "
                     rf"lanes in the {lanes}-lane x 64-template", said)


@pytest.fixture()
def one_chip_host_kernels(monkeypatch):
    """A `TpuBackend` as a one-chip machine has it (no mesh) whose two
    device programs are stand-ins: a table of the real V bucket and one
    digit (nothing reads its rows here), and a templated verify that is
    OpenSSL on the host and notes the shapes it was handed."""
    import jax.numpy as jnp
    from tendermint_tpu.ops import ed25519 as dev
    handed = []

    def build(pubs):
        return (jnp.zeros((1, 1, len(pubs), 3, 32), jnp.uint8),
                jnp.ones((len(pubs),), bool))

    def verify(tbl, pub_ok, val_pubs, val_idx, tmpl_idx, templates, sigs,
               base_tbl):
        handed.append((tbl.shape[2], len(val_idx), len(templates)))
        return jnp.asarray(openssl_verdicts(*(np.asarray(x) for x in (
            val_pubs, val_idx, tmpl_idx, templates, sigs))))

    monkeypatch.setattr(dev, "build_neg_comb_jit", build)
    monkeypatch.setattr(dev, "verify_grouped_templated_jit", verify)
    be = cb.TpuBackend()
    be._mesh = None
    return be, handed


def test_a_300_validator_window_rides_the_32768_lane_program(
        sets, one_chip_host_kernels):
    """The control batch of the benchmark's new cell (64 templates x 300
    lanes, 1,500 forged, every expected verdict OpenSSL's) through the
    backend's bookkeeping: one call of 19,200 lanes in the (32,768, 64)
    program against a V-bucket-512 table, verdicts in the caller's order
    with forged lanes of validators above index 128 among them, and the
    lane counters moved by what was asked and what the program holds.
    The cold set's verify program is loaded beside the table build and
    NOT run: no dummy call, so no table of zeros on the device."""
    be, handed = one_chip_host_kernels
    seeds, vs = sets[300]
    batch = control.build(SEED, seeds, WINDOW)
    n = WINDOW * 300
    bad = ~batch["expect"]
    assert batch["expect"].size == n and batch["forged"] == 5 * (n // 64)
    assert (batch["val_idx"][bad] >= 128).sum() > 100
    assert (batch["val_idx"][bad] < 128).sum() > 100
    real, padded = REGISTRY.sigs_requested, REGISTRY.verify_lanes_padded
    before = (real.value, padded.value, REGISTRY.sigs_verified.value)
    t0 = tracing.now_epoch()
    got = be.verify_grouped_templated(
        vs.set_key(), vs.pubs_matrix(), batch["val_idx"], batch["tmpl_idx"],
        batch["templates"], batch["sigs"])
    assert got.shape == (n,) and got.tolist() == batch["expect"].tolist()
    assert handed == [(512, 32768, 64)]          # the call, and no other
    assert real.value - before[0] == n
    assert padded.value - before[1] == 32768
    assert REGISTRY.sigs_verified.value - before[2] == n - batch["forged"]
    assert [s["args"] for s in _since(t0, "verify.dispatch")] == [
        {"lanes": n, "bucket": 32768}]
    assert [s["args"]["v"] for s in _since(t0, "tables.build")] == [300]
    # a commit of the same set (300 lanes, 1 template: a bucket of its
    # own) is padded into the window's program, which has run
    one = slice(0, 300)
    got = be.verify_grouped_templated(
        vs.set_key(), vs.pubs_matrix(), batch["val_idx"][one],
        batch["tmpl_idx"][one], batch["templates"][:1], batch["sigs"][one])
    assert got.tolist() == batch["expect"][one].tolist()
    assert handed[-1] == (512, 32768, 64)
    assert real.value - before[0] == n + 300
    assert padded.value - before[1] == 2 * 32768


@pytest.mark.parametrize("n_vals,program", [(140, (256, 16384, 64)),
                                            (300, (512, 32768, 64))])
def test_a_cold_sets_verify_program_is_loaded_by_shapes_and_not_run(
        sets, one_chip_host_kernels, monkeypatch, n_vals, program):
    """Beside a cold set's table build the backend asks jit for the
    verify program of the call's own shapes (`lower(...).compile()`),
    with shapes alone: no array is made for it, so no table of zeros,
    1.25 GiB at V bucket 512, stands on the device beside the table
    being built, and the program runs once, for the call."""
    import jax
    from tendermint_tpu.ops import ed25519 as dev
    be, handed = one_chip_host_kernels
    asked = []

    class Lowered:
        def compile(self):
            asked.append("compiled")

    def lower(*specs):
        asked.append(specs)
        return Lowered()

    monkeypatch.setattr(dev.verify_grouped_templated_jit, "lower", lower,
                        raising=False)
    seeds, vs = sets[n_vals]
    batch = control.build(SEED, seeds, WINDOW)
    got = be.verify_grouped_templated(
        vs.set_key(), vs.pubs_matrix(), batch["val_idx"], batch["tmpl_idx"],
        batch["templates"], batch["sigs"])
    assert got.tolist() == batch["expect"].tolist()
    assert handed == [program]
    specs, done = asked
    assert done == "compiled"
    assert all(type(x) is jax.ShapeDtypeStruct for x in specs)
    vb, lanes, tmpls = program
    assert [x.shape for x in specs] == [
        (COMB_WINDOWS, COMB_DIGITS, vb, 3, 32), (vb,), (vb, 32), (lanes,),
        (lanes,), (tmpls, 128), (lanes, 64), tuple(be._base_tbl.shape)]
    # a second call of the set loads nothing: the table is resident
    be.verify_grouped_templated(
        vs.set_key(), vs.pubs_matrix(), batch["val_idx"], batch["tmpl_idx"],
        batch["templates"], batch["sigs"])
    assert len(asked) == 2 and handed == [program] * 2


def test_the_lane_counter_is_on_the_metrics_page():
    text = metrics.prometheus_text()
    for name in ("sigs_requested", "verify_lanes_padded"):
        assert f"# TYPE tendermint_{name} counter" in text
        assert re.search(rf"^tendermint_{name} \d+$", text, re.M)
