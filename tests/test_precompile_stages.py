"""The boot warm-up in stages: a node that fast-syncs warms at boot only
the program a window runs, and the other five when it has caught up
(`TpuBackend.precompile_for_validators`, `Node._maybe_precompile`).
Every program costs seconds of Python tracing under the GIL, which the
block download of a catching-up node cannot spare (PERF.md §6, PR 29)."""

import threading
import time
import types

import numpy as np
import pytest

from tendermint_tpu.blockchain import messages as BM
from tendermint_tpu.blockchain.reactor import (DEFAULT_BATCH,
                                               BlockchainReactor)
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config import test_config as fast_config
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.crypto.supervised import SupervisedBackend
from tendermint_tpu.node.node import Node
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.state.state import get_state
from tendermint_tpu.types import (GenesisDoc, GenesisValidator, PrivKey,
                                  PrivValidator)
from tendermint_tpu.utils.db import MemDB

WINDOW_100 = ("templated", 8192, DEFAULT_BATCH)     # 64 blocks x 100 votes
LIVE_100 = [("plain", 16, 1), ("templated", 16, 1),
            ("plain", 128, 1), ("templated", 128, 1),
            ("plain", 8192, DEFAULT_BATCH)]


class _Vals:
    def __init__(self, n):
        self.n = n

    def size(self):
        return self.n

    def set_key(self):
        return b"k" * 32

    def pubs_matrix(self):
        return np.zeros((self.n, 32), np.uint8)


def _programs(n_vals, stage):
    """What `precompile_for_validators` hands to `precompile`."""
    got = []
    fake = types.SimpleNamespace(
        precompile=lambda key, pubs, programs, msg_len, stop: got.extend(
            programs))
    cb.TpuBackend.precompile_for_validators(fake, _Vals(n_vals), stage)
    return got


@pytest.mark.parametrize("stage,expect", [
    ("catchup", [WINDOW_100]),
    ("live", LIVE_100),
    ("all", LIVE_100 + [WINDOW_100]),
])
def test_stage_warms_its_programs_at_100_validators(stage, expect):
    assert sorted(_programs(100, stage)) == sorted(expect)


@pytest.mark.parametrize("n_vals", [1, 4, 100, 150])
def test_the_two_stages_are_all_and_share_nothing(n_vals):
    catchup, live = _programs(n_vals, "catchup"), _programs(n_vals, "live")
    assert len(catchup) == 1 and catchup[0][0] == "templated"
    assert not set(catchup) & set(live)
    assert sorted(catchup + live) == sorted(_programs(n_vals, "all"))
    assert _programs(n_vals, "all") == _programs(n_vals, "all")


def test_an_unknown_stage_is_an_error():
    with pytest.raises(ValueError, match="stage"):
        _programs(4, "later")


def test_precompile_calls_each_programs_own_entry_point():
    calls = []
    fake = types.SimpleNamespace(
        verify_grouped=lambda key, pubs, idx, msgs, sigs: calls.append(
            ("plain", len(idx), msgs.shape)),
        verify_grouped_templated=lambda key, pubs, idx, tidx, tmpl, sigs,
        exact_bucket=False: calls.append(
            ("templated", len(idx), tmpl.shape, int(tidx.max()),
             exact_bucket)))
    cb.TpuBackend.precompile(
        fake, b"k", np.zeros((4, 32), np.uint8),
        [("templated", 256, 64), ("plain", 16, 1)], 110)
    # a warm-up compiles each program's OWN bucket: a call that may be
    # padded into a bigger warm one would compile nothing
    assert calls == [("templated", 256, (64, 110), 63, True),
                     ("plain", 16, (16, 110))]


def test_a_stopped_warm_up_ends_before_its_next_program():
    stop, calls = threading.Event(), []

    def first_then_stop(*args, **_kw):
        calls.append(len(args[2]))
        stop.set()

    fake = types.SimpleNamespace(verify_grouped=first_then_stop,
                                 verify_grouped_templated=first_then_stop)
    cb.TpuBackend.precompile(
        fake, b"k", np.zeros((4, 32), np.uint8),
        [("plain", 16, 1), ("templated", 16, 1), ("plain", 256, 64)], 110,
        stop)
    assert calls == [16]


def test_the_supervised_ladder_passes_the_stage_on():
    got = []
    rung = types.SimpleNamespace(name="fake", backend=types.SimpleNamespace(
        precompile_for_validators=lambda vals, stage, stop: got.append(
            (stage, stop))))
    fake = types.SimpleNamespace(_rungs=[rung])
    stop = threading.Event()
    SupervisedBackend.precompile_for_validators(fake, _Vals(4), "catchup",
                                                stop)
    SupervisedBackend.precompile_for_validators(fake, _Vals(4))
    assert got == [("catchup", stop), ("all", None)]


# -- the node ---------------------------------------------------------------

class _Recorder(cb.PythonBackend):
    """A backend with a device plane to warm, which records the stages;
    a stage named in `hold` ends when its Event is set."""
    warmed: list = []
    hold: dict = {}

    def precompile_for_validators(self, vals, stage="all", stop=None):
        if stage in self.hold:
            assert self.hold[stage].wait(5)
        type(self).warmed.append(
            (stage, vals.size(), threading.current_thread().name))


def _node(monkeypatch, n_vals, fast_sync, hold=None):
    monkeypatch.setitem(cb._BACKENDS, "stage-recorder", _Recorder)
    monkeypatch.setattr(_Recorder, "warmed", [])
    monkeypatch.setattr(_Recorder, "hold", dict(hold or {}))
    cfg = fast_config()
    cfg.base.crypto_backend = "stage-recorder"
    cfg.base.fast_sync = fast_sync
    cfg.crypto.supervised = False
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    pvs = [PrivValidator(PrivKey(bytes([i + 1]) * 32)) for i in range(n_vals)]
    gen = GenesisDoc(chain_id="stages", genesis_time_ns=1, validators=[
        GenesisValidator(pv.pub_key.bytes_, 10) for pv in pvs])
    prev = cb.get_backend()
    try:
        return Node(cfg, priv_validator=pvs[0], genesis_doc=gen)
    finally:
        monkeypatch.setattr(cb, "_current", prev)


def _wait_warmed(n):
    deadline = time.monotonic() + 5
    while len(_Recorder.warmed) < n and time.monotonic() < deadline:
        time.sleep(0.01)
    return list(_Recorder.warmed)


def test_a_fast_syncing_node_warms_the_window_then_the_rest_at_catch_up(
        monkeypatch):
    handed = []

    def live_stage_started():
        return (any(w[0] == "live" for w in _Recorder.warmed) or
                any(t.name == "crypto-precompile"
                    for t in threading.enumerate()))

    monkeypatch.setattr(
        ConsensusReactor, "switch_to_consensus",
        lambda self, state: handed.append((state, live_stage_started())))
    node = _node(monkeypatch, 2, fast_sync=True)
    assert _wait_warmed(1) == [("catchup", 2, "crypto-precompile")]
    bc = node.switch.reactor("blockchain")
    bc.on_caught_up(bc.state)
    assert _wait_warmed(2)[1] == ("live", 2, "crypto-precompile")
    # the hand-over still happens, and after the live stage was started:
    # whoever sees consensus running can wait for that thread's end
    assert handed == [(bc.state, True)]


def test_a_fast_syncing_node_asks_for_blocks_once_the_window_is_warm(
        monkeypatch):
    warm = threading.Event()
    node = _node(monkeypatch, 2, fast_sync=True, hold={"catchup": warm})
    gate = node.switch.reactor("blockchain").request_when
    assert gate is not None and not gate.is_set() and not _Recorder.warmed
    warm.set()
    assert gate.wait(5)
    assert _wait_warmed(1) == [("catchup", 2, "crypto-precompile")]


def test_stopping_the_node_waits_for_its_warm_up(monkeypatch):
    """A process that exits with a thread inside an XLA compile aborts:
    `stop()` returns when the warm-up has ended."""
    warm = threading.Event()
    node = _node(monkeypatch, 2, fast_sync=True, hold={"catchup": warm})
    stopper = threading.Thread(target=node.stop)
    stopper.start()
    stopper.join(0.3)
    assert stopper.is_alive() and not _Recorder.warmed
    warm.set()
    stopper.join(5)
    assert not stopper.is_alive()
    assert _Recorder.warmed == [("catchup", 2, "crypto-precompile")]


def test_a_warm_up_that_fails_still_lets_the_sync_ask(monkeypatch):
    monkeypatch.setattr(
        _Recorder, "precompile_for_validators",
        lambda self, vals, stage="all", stop=None: 1 / 0)
    node = _node(monkeypatch, 2, fast_sync=True)
    assert node.switch.reactor("blockchain").request_when.wait(5)


class _Peer:
    id = "src"

    def __init__(self):
        self.asked = []

    def receiving(self, ch_id):
        return 0

    def try_send(self, ch_id, msg):
        self.asked.append(BM.decode_msg(msg).height)
        return True


class _Switch:
    def __init__(self, peer):
        self.peer = peer

    def peers(self):
        return [self.peer]

    def get_peer(self, pid):
        return self.peer

    def broadcast(self, ch_id, msg):
        pass


def _reactor(tip):
    pv = PrivValidator(PrivKey(b"\x21" * 32))
    gen = GenesisDoc(chain_id="gate", genesis_time_ns=1, validators=[
        GenesisValidator(pv.pub_key.bytes_, 10)])
    bc = BlockchainReactor(
        get_state(MemDB(), gen),
        ClientCreator("kvstore").new_app_conns().consensus,
        BlockStore(MemDB()), fast_sync=True)
    peer = _Peer()
    bc.set_switch(_Switch(peer))
    bc.pool.set_peer_height(peer.id, tip)
    return bc, peer


def _until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)
    return pred()


def test_the_sync_requests_nothing_until_its_gate_opens():
    bc, peer = _reactor(tip=30)
    bc.request_when = threading.Event()
    bc.start()
    try:
        time.sleep(0.3)                       # 30 ticks of the routine
        assert peer.asked == [] and bc.pool.status()["in_flight"] == 0
        bc.request_when.set()
        assert _until(lambda: len(peer.asked) >= 29)
        assert sorted(peer.asked)[:3] == [1, 2, 3]
    finally:
        bc.stop()


def test_a_node_at_the_tip_hands_over_behind_a_shut_gate():
    """The gate holds requests only: a node that restarts at the tip has
    nothing to ask for, and does not wait for the warm-up to say so."""
    bc, _peer = _reactor(tip=1)
    bc.request_when = threading.Event()
    handed = []
    bc.on_caught_up = handed.append
    bc.start()
    try:
        assert _until(lambda: handed == [bc.state])
    finally:
        bc.stop()


def test_a_reactor_without_a_gate_asks_at_once():
    bc, peer = _reactor(tip=30)
    assert bc.request_when is None
    bc.start()
    try:
        assert _until(lambda: len(peer.asked) >= 29)
    finally:
        bc.stop()


@pytest.mark.parametrize("n_vals,fast_sync", [(2, False), (1, True)])
def test_a_node_that_does_not_fast_sync_warms_all_at_boot(
        monkeypatch, n_vals, fast_sync):
    node = _node(monkeypatch, n_vals, fast_sync)
    assert node.switch.reactor("blockchain") is None
    assert _wait_warmed(1) == [("all", n_vals, "crypto-precompile")]
