"""The verdict control (`benchmark/lib/control.py`): OpenSSL's verdicts
for a seeded batch with forged lanes, and a comparison that fails."""

import numpy as np
import pytest

import benchutil  # noqa: F401
from benchmark.lib import chain, control
from tendermint_tpu import batchplane
from tendermint_tpu.crypto import backend as cb


@pytest.fixture(scope="module")
def batch():
    seeds, _vs = chain.make_validators(11, 4)
    return control.build(11, seeds, 64)


def test_the_batch_is_a_reactor_window_with_five_kinds_forged(batch):
    assert batch["sigs"].shape == (256, 64)
    assert batch["templates"].shape == (64, 128)
    # OpenSSL itself rejects exactly the forged lanes
    assert int((~batch["expect"]).sum()) == batch["forged"] == \
        len(control.KINDS) * 4
    assert control.mismatches(batch["expect"].copy(), batch) == 0


def test_one_flipped_verdict_fails_the_control(batch):
    got = batch["expect"].copy()
    got[17] = not got[17]
    assert control.mismatches(got, batch) == 1


def test_a_verifier_that_accepts_everything_fails_the_control(batch):
    assert control.mismatches(np.ones(256, bool), batch) == batch["forged"]


def test_a_batch_without_its_forged_lanes_proves_nothing(batch):
    clean = dict(batch, expect=np.ones(256, bool))
    assert control.mismatches(np.ones(256, bool), clean) == 256


def test_the_control_goes_through_the_timed_paths_entry(batch):
    """With the OpenSSL backend answering, the batch plane's templated
    entry gives OpenSSL's verdicts: the plumbing and the lane layout are
    right."""
    old = cb._current
    try:
        cb.set_backend("native")
        _seeds, vs = chain.make_validators(11, 4)
        got = control.device_verdicts(vs, batch)
    finally:
        batchplane.reset_plane()
        cb._current = old
    assert control.mismatches(got, batch) == 0
