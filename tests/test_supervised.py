"""Supervised crypto backend: breaker, fallback ladder, chaos injection.

The invariant under test throughout: an infrastructure failure in a
crypto backend is NEVER reported as "bad signature" — it either falls
down the ladder to a correct answer or surfaces as DeviceFault.
"""

import secrets
import time

import numpy as np
import pytest

from tendermint_tpu.crypto import pure_ed25519 as ref
from tendermint_tpu.crypto.backend import PythonBackend
from tendermint_tpu.crypto.supervised import (CLOSED, HALF_OPEN, OPEN,
                                              SupervisedBackend)
from tendermint_tpu.utils.chaos import CryptoChaos, DeviceFault
from tendermint_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.faults


# -- fixtures ---------------------------------------------------------------

@pytest.fixture(scope="module")
def sigs():
    """(pubs, msgs, sigs) arrays: 8 valid ed25519 lanes, last one forged."""
    n = 8
    seeds = [secrets.token_bytes(32) for _ in range(n)]
    pubs = np.frombuffer(b"".join(ref.pubkey_from_seed(s) for s in seeds),
                         np.uint8).reshape(n, 32)
    msgs_b = [secrets.token_bytes(64) for _ in range(n)]
    sig_b = [ref.sign(seeds[i], msgs_b[i]) for i in range(n)]
    sig_b[-1] = bytes(64)                       # forged lane
    msgs = np.frombuffer(b"".join(msgs_b), np.uint8).reshape(n, 64)
    sg = np.frombuffer(b"".join(sig_b), np.uint8).reshape(n, 64)
    want = np.ones(n, dtype=bool)
    want[-1] = False
    return pubs, msgs, sg, want


class FlakyBackend:
    """Device stand-in: raises for the first `fail_n` calls (or forever
    with fail_n=-1), then answers correctly; optional per-call delay."""
    name = "flaky"

    def __init__(self, fail_n=0, delay_s=0.0, wrong=False):
        self.fail_n = fail_n
        self.delay_s = delay_s
        self.wrong = wrong
        self.calls = 0
        self._ref = PythonBackend()

    def verify_batch(self, pubkeys, msgs, sigs):
        self.calls += 1
        if self.fail_n < 0 or self.calls <= self.fail_n:
            raise RuntimeError(f"simulated XLA crash (call {self.calls})")
        if self.delay_s:
            time.sleep(self.delay_s)
        out = self._ref.verify_batch(pubkeys, msgs, sigs)
        if self.wrong:
            out = ~out
        return out

    def verify_grouped(self, set_key, val_pubs, val_idx, msgs, sigs):
        return self.verify_batch(np.asarray(val_pubs)[np.asarray(val_idx)],
                                 msgs, sigs)

    def verify_grouped_templated(self, set_key, val_pubs, val_idx,
                                 tmpl_idx, templates, sigs):
        return self.verify_grouped(
            set_key, val_pubs, val_idx,
            np.asarray(templates)[np.asarray(tmpl_idx)], sigs)


def make_sup(device, **knobs):
    knobs.setdefault("breaker_cooldown_s", 0.05)
    knobs.setdefault("retries", 0)
    knobs.setdefault("call_timeout_s", 10.0)
    return SupervisedBackend([("flaky", device), ("python", PythonBackend())],
                             **knobs)


# -- chaos spec parsing -----------------------------------------------------

def test_chaos_parse():
    c = CryptoChaos.parse("raise:every=50")
    assert (c.mode, c.every) == ("raise", 50)
    c = CryptoChaos.parse("latency:ms=250,every=2")
    assert (c.mode, c.ms, c.every) == ("latency", 250.0, 2)
    c = CryptoChaos.parse("wrong:lanes=3")
    assert (c.mode, c.lanes, c.every) == ("wrong", 3, 1)


@pytest.mark.parametrize("bad", ["explode", "raise:every=0", "raise:junk",
                                 "wrong:lanes", "latency:speed=9"])
def test_chaos_parse_rejects_junk(bad):
    with pytest.raises(ValueError):
        CryptoChaos.parse(bad)


def test_chaos_schedule_deterministic():
    """Same spec => identical fault schedule (pure function of counter)."""
    def schedule(n):
        c = CryptoChaos.parse("raise:every=3")
        hits = []
        for i in range(n):
            try:
                c.before_call()
                hits.append(False)
            except DeviceFault:
                hits.append(True)
        return hits

    a, b = schedule(20), schedule(20)
    assert a == b
    assert a == [(i + 1) % 3 == 0 for i in range(20)]


def test_chaos_from_env(monkeypatch):
    monkeypatch.delenv("TM_CHAOS_CRYPTO", raising=False)
    assert CryptoChaos.from_env() is None
    monkeypatch.setenv("TM_CHAOS_CRYPTO", "raise:every=7")
    c = CryptoChaos.from_env()
    assert c.mode == "raise" and c.every == 7


# -- fallback + breaker -----------------------------------------------------

def test_fallback_answers_correctly_on_device_fault(sigs):
    """A device crash falls to the floor and returns the REFERENCE
    answer, forged lane still rejected — never an exception, never a
    wrong verdict."""
    pubs, msgs, sg, want = sigs
    sup = make_sup(FlakyBackend(fail_n=-1))
    t0 = REGISTRY.crypto_fallback_calls.value
    out = sup.verify_batch(pubs, msgs, sg)
    assert (out == want).all()
    assert REGISTRY.crypto_fallback_calls.value > t0


def test_breaker_trips_after_threshold_and_recovers(sigs):
    pubs, msgs, sg, want = sigs
    dev = FlakyBackend(fail_n=3)
    sup = make_sup(dev, breaker_threshold=3, breaker_cooldown_s=0.05)
    trips0 = REGISTRY.crypto_breaker_trips.value
    recov0 = REGISTRY.crypto_breaker_recoveries.value
    rung = sup._rungs[0]
    # three faulting calls: breaker reaches OPEN on the third
    for _ in range(3):
        assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    assert rung.state == OPEN
    assert REGISTRY.crypto_breaker_trips.value == trips0 + 1
    # while OPEN, the device rung is skipped entirely
    calls = dev.calls
    assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    assert dev.calls == calls
    # after the cooldown a probe is admitted; the device now answers,
    # so the breaker closes and the rung serves again
    time.sleep(0.06)
    assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    assert rung.state == CLOSED
    assert dev.calls == calls + 1
    assert REGISTRY.crypto_breaker_recoveries.value == recov0 + 1


def test_failed_half_open_probe_reopens(sigs):
    pubs, msgs, sg, want = sigs
    dev = FlakyBackend(fail_n=10)
    sup = make_sup(dev, breaker_threshold=1, breaker_cooldown_s=0.05)
    assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    rung = sup._rungs[0]
    assert rung.state == OPEN
    time.sleep(0.06)
    trips0 = rung.trips
    assert (sup.verify_batch(pubs, msgs, sg) == want).all()  # probe fails
    assert rung.state == OPEN
    assert rung.trips == trips0 + 1


def test_retries_stay_on_rung_before_falling(sigs):
    """retries=2 gives the device 3 attempts; a fault that clears on the
    second attempt never leaves the rung."""
    pubs, msgs, sg, want = sigs
    dev = FlakyBackend(fail_n=1)
    sup = make_sup(dev, retries=2, breaker_threshold=10)
    out = sup.verify_batch(pubs, msgs, sg)
    assert (out == want).all()
    assert dev.calls == 2                     # fault, then success
    assert sup._rungs[0].state == CLOSED


def test_timeout_is_a_device_fault(sigs):
    pubs, msgs, sg, want = sigs
    sup = make_sup(FlakyBackend(delay_s=0.5), call_timeout_s=0.05,
                   breaker_threshold=1)
    t0 = time.monotonic()
    out = sup.verify_batch(pubs, msgs, sg)
    assert (out == want).all()                # floor answered
    assert time.monotonic() - t0 < 5.0
    assert sup._rungs[0].state == OPEN        # the hang tripped it


def test_all_rungs_failing_raises_device_fault(sigs):
    """With every rung unavailable the caller gets DeviceFault — a typed
    infra error, not a bool array claiming the signatures were bad.
    (A floor rung's raw exceptions propagate as-is — they are caller
    bugs — so the exhausted-ladder case is expressed by the floor itself
    signaling DeviceFault, as a deeper supervisor would.)"""
    pubs, msgs, sg, _ = sigs

    class DeadFloor:
        def verify_batch(self, *a):
            raise DeviceFault("floor offline")

    sup = SupervisedBackend([("a", FlakyBackend(fail_n=-1)),
                             ("b", DeadFloor())],
                            retries=0, breaker_threshold=100,
                            call_timeout_s=10.0)
    with pytest.raises(DeviceFault):
        sup.verify_batch(pubs, msgs, sg)


# -- chaos wiring -----------------------------------------------------------

def test_chaos_raise_mode_injects_into_device_rung_only(sigs):
    pubs, msgs, sg, want = sigs
    sup = make_sup(FlakyBackend(), breaker_threshold=100)
    sup.chaos = CryptoChaos.parse("raise:every=2")
    faults0 = REGISTRY.crypto_device_faults.value
    for _ in range(6):                        # every 2nd call faults
        assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    assert REGISTRY.crypto_device_faults.value - faults0 == 3


def test_chaos_latency_mode_trips_timeout(sigs):
    pubs, msgs, sg, want = sigs
    sup = make_sup(FlakyBackend(), call_timeout_s=0.05, breaker_threshold=1)
    sup.chaos = CryptoChaos.parse("latency:ms=500")
    assert (sup.verify_batch(pubs, msgs, sg) == want).all()
    assert sup._rungs[0].state == OPEN


def test_chaos_wrong_mode_caught_by_spot_check(sigs):
    """A silently corrupting device (all lanes flipped) is demoted to a
    fault by the reference spot check and the floor serves the truth."""
    pubs, msgs, sg, want = sigs
    sup = make_sup(FlakyBackend(), spot_check_every=1, breaker_threshold=1)
    sup.chaos = CryptoChaos.parse(f"wrong:lanes={len(want)}")
    mism0 = REGISTRY.crypto_spot_check_mismatches.value
    out = sup.verify_batch(pubs, msgs, sg)
    assert (out == want).all()
    assert REGISTRY.crypto_spot_check_mismatches.value > mism0
    assert sup._rungs[0].state == OPEN


def _entry_args(entry, pubs, msgs, sg):
    """The same lanes through each of the three verify entries: lane i
    is validator i's and, templated, signs template i."""
    lanes = np.arange(len(pubs), dtype=np.int32)
    return {"verify_batch": (pubs, msgs, sg),
            "verify_grouped": (b"set", pubs, lanes, msgs, sg),
            "verify_grouped_templated": (b"set", pubs, lanes, lanes, msgs,
                                         sg)}[entry]


# chaos mode -> (the ladder's knobs that catch it, the chaos spec)
DEVICE_FAULTS = {"raise": ({}, "raise:every=1"),
                 "latency": ({"call_timeout_s": 0.05}, "latency:ms=300"),
                 "wrong": ({"spot_check_every": 1}, "wrong:lanes=8")}


@pytest.mark.parametrize("mode", list(DEVICE_FAULTS))
@pytest.mark.parametrize("entry", ["verify_batch", "verify_grouped",
                                   "verify_grouped_templated"])
def test_every_verify_entry_survives_a_device_fault(sigs, entry, mode):
    """A device that raises, hangs past the call timeout or answers
    wrongly costs the caller of ANY entry a slow call: the floor's
    answer comes back, the device rung's fault is counted, nothing is
    raised.  The templated entry is the one a catching-up node calls."""
    pubs, msgs, sg, want = sigs
    knobs, spec = DEVICE_FAULTS[mode]
    dev = FlakyBackend()
    sup = make_sup(dev, breaker_threshold=100, **knobs)
    sup.chaos = CryptoChaos.parse(spec)
    faults0 = REGISTRY.crypto_device_faults.value
    fell0 = REGISTRY.crypto_fallback_calls.value
    out = getattr(sup, entry)(*_entry_args(entry, pubs, msgs, sg))
    assert (out == want).all()
    device, floor = sup._rungs
    assert (device.faults, device.calls, floor.calls) == (1, 1, 1)
    assert REGISTRY.crypto_device_faults.value - faults0 == 1
    assert REGISTRY.crypto_fallback_calls.value - fell0 == 1
    # a raise is injected before the device is reached and a wrong
    # answer after it (only the spot check catches that one); a hung
    # call is still on its way when the floor has answered
    if mode != "latency":
        assert dev.calls == (0 if mode == "raise" else 1)


# -- the blame invariant ----------------------------------------------------

def test_vote_tally_survives_device_fault():
    """VoteSet.add_votes_batched over a faulting device must ACCEPT the
    honest votes (scalar re-verify), not mark them invalid."""
    from dataclasses import replace

    from chainutil import make_validators
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.block import BlockID
    from tendermint_tpu.types.part_set import PartSetHeader
    from tendermint_tpu.types.vote import Vote, VoteSet

    privs, vs = make_validators(4)
    chain_id = "chaos-tally"
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    votes = []
    for i, pv in enumerate(privs):
        v = Vote(validator_address=pv.address, validator_index=i,
                 height=1, round=0, type=canonical.TYPE_PRECOMMIT,
                 block_id=bid)
        votes.append(replace(
            v, signature=pv.priv_key.sign(v.sign_bytes(chain_id))))

    old = cb._current
    try:
        cb._current = make_sup(FlakyBackend(fail_n=-1), retries=0,
                               breaker_threshold=100)
        vset = VoteSet(chain_id, 1, 0, canonical.TYPE_PRECOMMIT, vs)
        out = vset.add_votes_batched(votes)
        assert all(r is True for r in out), out
        assert vset.has_two_thirds_majority()
    finally:
        cb._current = old


def test_supervisor_status_shape(sigs):
    pubs, msgs, sg, _ = sigs
    sup = make_sup(FlakyBackend())
    sup.verify_batch(pubs, msgs, sg)
    st = sup.supervisor_status()
    assert st["active_rung"] == "flaky"
    assert [r["name"] for r in st["rungs"]] == ["flaky", "python"]
    assert st["rungs"][0]["calls"] == 1
    assert st["rungs"][0]["state"] == CLOSED


def test_build_ladder_skips_unavailable_and_keeps_floor(monkeypatch):
    """build() with an unconstructible primary still produces a working
    ladder ending on the python floor."""
    from tendermint_tpu.crypto import backend as cb

    def boom():
        raise ImportError("no device runtime here")

    monkeypatch.setitem(cb._BACKENDS, "tpu", boom)
    sup = SupervisedBackend.build("tpu")
    names = [r.name for r in sup._rungs]
    assert "tpu" not in names
    assert names[-1] == "python"
