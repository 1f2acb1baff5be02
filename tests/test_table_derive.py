"""A changed validator set's comb table is assembled from the resident
table that shares most of its keys: only the keys that joined are built
(`TpuBackend._predecessor`, `_derive_tables`, `ops.ed25519.comb_columns`).

The first part holds a derived table to the whole build byte for byte,
`ok` included, with the real programs at V bucket 16 (a build runs ~11 s
there on the CPU backend: one whole build of the base set serves every
case, and each case pays its own reference).  The second part is the
backend's bookkeeping with the build stubbed: which sets take the whole
build, what is recorded, when the derive's programs are loaded, and that
a backend or a node that meets ONE set loads nothing and starts nothing
for the derive (PERF.md §6, PR 37 / 38: a derive warmed in the boot cost
a plain chain a minute of set-up)."""

import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.crypto import pure_ed25519 as ref
from tendermint_tpu.ops import ed25519 as dev
from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.metrics import REGISTRY

N = 12                              # padded to V bucket 16 by 4 columns
ONE = COMB_WINDOWS * COMB_DIGITS * 16 * 96      # a 16-column table, bytes


def _pubs(tags, salt=7) -> np.ndarray:
    keys = [ref.pubkey_from_seed(bytes([salt, t]) + b"\x00" * 30)
            for t in tags]
    return np.frombuffer(b"".join(keys), np.uint8).reshape(-1, 32).copy()


def _since(t0: float, prefix: str) -> list[dict]:
    return [s for s in tracing.RECORDER.since(t0)
            if s["name"].startswith(prefix) and s["ts"] >= t0]


def _whole(pubs: np.ndarray) -> tuple:
    """`build_neg_comb_jit` on the whole set, padded as the backend pads."""
    vb = cb._bucket(len(pubs))
    padded = np.concatenate([pubs, np.repeat(pubs[:1], vb - len(pubs), 0)])
    tbl, ok = dev.build_neg_comb_jit(jnp.asarray(padded))
    return np.asarray(tbl), np.asarray(ok)


# a y that is on no curve point: a key whose column is not `ok`
_INVALID = np.frombuffer((2).to_bytes(32, "little"), np.uint8)


def _one_device(mp) -> None:
    """A backend made from here on sees one device, as on one chip:
    conftest gives the CPU eight, and on a mesh a table is replicated
    and always built whole."""
    import jax
    real = jax.devices
    mp.setattr(jax, "devices", lambda *a, **kw: real(*a, **kw)[:1])


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    _one_device(monkeypatch)


@pytest.fixture(scope="module")
def base():
    """The base set's entry, built whole once, and its bytes."""
    pubs = _pubs(range(1, N + 1))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TM_TABLE_CACHE_DIR", "")
        _one_device(mp)
        ent = cb.TpuBackend()._set_tables(b"base", pubs)
    assert np.asarray(ent[1]).all()
    return pubs, ent, np.asarray(ent[0]).copy(), np.asarray(ent[1]).copy()


def _swap(pubs: np.ndarray, out: int, key: np.ndarray, at: int) -> np.ndarray:
    """`pubs` less member `out`, with `key` put in at position `at`: a
    set is ordered by address, so a new member lands anywhere."""
    rest = np.delete(pubs, out, 0)
    return np.concatenate([rest[:at], key[None], rest[at:]])


def _case_sets(pubs: np.ndarray) -> dict:
    new = _pubs([101, 102], salt=9)
    far = _swap(pubs, 2, new[0], 10)
    return {
        # the new key sorts far from the old one: columns 2..9 shift by one
        "far_swap": [far],
        # the first member of a padded set: the four padding columns change
        "first_member_of_a_padded_set": [_swap(pubs, 0, new[0], 0)],
        # k = 0: nothing is built, a pure gather; 5 padding columns now
        "a_member_only_leaves": [np.delete(pubs, 5, 0)],
        # derived from a derived table; the second key is no curve point
        "two_swaps_in_a_row": [far, _swap(far, 0, _INVALID, 4)],
    }


@pytest.mark.parametrize("case", ["far_swap", "first_member_of_a_padded_set",
                                  "a_member_only_leaves",
                                  "two_swaps_in_a_row"])
def test_a_derived_table_is_the_whole_build_byte_for_byte(
        base, case, monkeypatch):
    pubs, ent, tbl0, ok0 = base
    monkeypatch.setenv("TM_TABLE_CACHE_DIR", "")
    be = cb.TpuBackend()
    be._tables[b"base"] = ent
    derives0 = REGISTRY.table_derives.value
    t0 = tracing.now_epoch()
    sets = _case_sets(pubs)[case]
    for i, s in enumerate(sets):
        got = be._set_tables(b"set-%d" % i, s)
        # the predecessor, and every table before it, is as it was
        assert list(be._tables)[:i + 1] == [b"base"] + [
            b"set-%d" % j for j in range(i)]
        assert be._tables[b"base"][0] is ent[0]
        assert np.array_equal(np.asarray(ent[0]), tbl0)
        assert np.array_equal(np.asarray(ent[1]), ok0)
    want_tbl, want_ok = _whole(sets[-1])
    assert got[0].shape == (COMB_WINDOWS, COMB_DIGITS, 16, 3, 32)
    assert got[2] == len(sets[-1])
    assert np.array_equal(np.asarray(got[0]), want_tbl)
    assert np.array_equal(np.asarray(got[1]), want_ok)
    assert np.array_equal(np.asarray(got[3]), got[4])       # staged keys
    joined = {"a_member_only_leaves": [0], "two_swaps_in_a_row": [1, 1]}.get(
        case, [1])
    recs = _since(t0, "tables.")
    assert [r["args"] for r in recs if r["name"] == "tables.derive"] == [
        {"v": len(s), "joined": k, "bytes": ONE}
        for s, k in zip(sets, joined)]
    # one `tables.build` a set, the derive nested in it; no whole build's
    # program was asked for
    builds = [r for r in recs if r["name"] == "tables.build"]
    derives = [r for r in recs if r["name"] == "tables.derive"]
    assert len(builds) == len(sets)
    for b, d in zip(builds, derives):
        assert b["ts"] <= d["ts"] and \
            d["ts"] + d["dur"] <= b["ts"] + b["dur"] + 1e-6
        assert "cat" not in d
    assert not [r for r in recs if r["name"] == "tables.build.load"]
    assert REGISTRY.table_derives.value - derives0 == len(sets)
    if case == "two_swaps_in_a_row":
        assert want_ok.tolist() == [True] * 4 + [False] + [True] * 11


# -- the bookkeeping, with the build stubbed ---------------------------------

def _zero_tables(pubs):
    return (jnp.zeros((COMB_WINDOWS, COMB_DIGITS, len(pubs), 3, 32),
                      jnp.uint8), jnp.ones((len(pubs),), bool))


@pytest.fixture()
def stub_build(monkeypatch):
    """`build_neg_comb_jit` replaced where the backend looks it up by
    tables of zeros, which notes the columns it was asked for; the
    gather is the real one."""
    asked = []

    def build(pubs):
        asked.append(len(pubs))
        return _zero_tables(pubs)

    monkeypatch.setattr(dev, "build_neg_comb_jit", build)
    return asked


def _refusals():
    """name -> (the sets met first, the set that must be built whole)."""
    a12, a20 = _pubs(range(1, 13)), _pubs(range(1, 21))
    other = _pubs(range(1, 13), salt=11)
    swapped = _swap(a12, 3, _pubs([77], salt=9)[0], 8)
    return {
        # 17 keys joined a set of 20: more than one small build holds
        "more_joined_than_a_small_build": (
            [a20], np.concatenate([a20[:3], _pubs(range(30, 47))])),
        # the set grew past its bucket: no resident table of 32 columns
        "another_v_bucket": ([a12], np.concatenate(
            [a12, _pubs(range(30, 35))])),
        # the FIFO holds one table: `other` dropped the predecessor
        "the_predecessor_was_evicted": ([a12, other], swapped),
        # a set that shares no key with anything resident
        "no_key_shared": ([a12], other),
    }


@pytest.mark.parametrize("name", ["more_joined_than_a_small_build",
                                  "another_v_bucket",
                                  "the_predecessor_was_evicted",
                                  "no_key_shared"])
def test_a_set_that_cannot_be_derived_takes_the_whole_build(
        stub_build, monkeypatch, name):
    first, then = _refusals()[name]
    if name == "the_predecessor_was_evicted":
        monkeypatch.setattr(cb.TpuBackend, "TABLE_CACHE_BYTES", ONE)
    be = cb.TpuBackend()
    for i, s in enumerate(first):
        be._set_tables(b"first-%d" % i, s)
    derives0 = REGISTRY.table_derives.value
    del stub_build[:]
    t0 = tracing.now_epoch()
    ent = be._set_tables(b"then", then)
    assert stub_build == [cb._bucket(len(then))]      # every column
    names = [r["name"] for r in _since(t0, "tables.")
             if r["name"] != "tables.evict"]
    assert names == ["tables.build.load", "tables.build"]
    assert REGISTRY.table_derives.value == derives0
    assert be._derive_programs == {}
    assert np.array_equal(ent[4][:len(then)], then)


def test_a_table_the_disk_cache_holds_is_loaded_and_not_derived(stub_build):
    """conftest gives every test a table directory of its own."""
    a = _pubs(range(1, 13))
    b = _swap(a, 3, _pubs([77], salt=9)[0], 8)
    be = cb.TpuBackend()
    be._set_tables(b"a", a)
    be._set_tables(b"b", b)               # derived, and written to disk
    assert stub_build == [16, 16]
    again = cb.TpuBackend()               # a restart: both are on disk
    again._set_tables(b"a", a)
    derives0 = REGISTRY.table_derives.value
    t0 = tracing.now_epoch()
    ent = again._set_tables(b"b", b)
    assert [r["name"] for r in _since(t0, "tables.")] == ["tables.load"]
    assert REGISTRY.table_derives.value == derives0
    assert stub_build == [16, 16] and again._derive_programs == {}
    # a loaded table serves the next set as a predecessor
    again._set_tables(b"c", _swap(b, 0, _pubs([78], salt=9)[0], 2))
    assert [r["name"] for r in _since(t0, "tables.")] == [
        "tables.load", "tables.derive.load", "tables.derive", "tables.build"]
    assert stub_build == [16, 16, 16]
    assert np.array_equal(ent[4][:12], b)


def test_the_first_derive_of_a_backend_loads_its_programs_the_second_not(
        stub_build, monkeypatch):
    loaded = []
    real = cb._loaded
    monkeypatch.setattr(cb, "_loaded",
                        lambda fn, *a: loaded.append(fn) or real(fn, *a))
    a = _pubs(range(1, 13))
    b = _swap(a, 3, _pubs([77], salt=9)[0], 8)
    c = _swap(b, 0, _pubs([78], salt=9)[0], 11)
    be = cb.TpuBackend()
    be._set_tables(b"a", a)
    assert loaded == [dev.build_neg_comb_jit] and be._derive_programs == {}
    t0 = tracing.now_epoch()
    be._set_tables(b"b", b)
    assert loaded[1:] == [dev.build_neg_comb_jit, dev.comb_columns_jit]
    assert [(r["name"], r["args"]) for r in _since(t0, "tables.")] == [
        ("tables.derive.load", {"v": 12, "joined": 16}),
        ("tables.derive", {"v": 12, "joined": 1, "bytes": ONE}),
        ("tables.build", {"v": 12, "bytes": ONE})]
    t1 = tracing.now_epoch()
    be._set_tables(b"c", c)
    assert len(loaded) == 3
    assert [r["name"] for r in _since(t1, "tables.")] == [
        "tables.derive", "tables.build"]
    assert stub_build == [16, 16, 16]     # 16 columns a derive, not 16 + 16
    assert list(be._derive_programs) == [(16, 16)]


def test_a_sighted_set_change_loads_the_programs_on_a_warm_up_thread(
        stub_build, monkeypatch):
    """`warm_derive` (the fast-sync window cut at a set change calls it
    through `backend.valset_change_ahead`): one thread a V bucket, named
    as the node's warm-ups are; the derive that follows finds the
    programs and loads nothing."""
    started = []
    real_start = threading.Thread.start
    monkeypatch.setattr(threading.Thread, "start", lambda self: (
        started.append(self.name), real_start(self))[1])
    a = _pubs(range(1, 13))
    be = cb.TpuBackend()
    monkeypatch.setattr(cb, "_current", be)
    be._set_tables(b"a", a)
    t0 = tracing.now_epoch()
    del started[:]
    cb.valset_change_ahead(12)
    cb.valset_change_ahead(12)            # the sync thread cuts it again
    cb.valset_change_ahead(9)             # the same V bucket
    assert started == ["crypto-precompile"]
    deadline = time.monotonic() + 60
    while be._derive_programs.get((16, 16)) is None:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    be._set_tables(b"b", _swap(a, 3, _pubs([77], salt=9)[0], 8))
    recs = _since(t0, "tables.")
    assert [r["name"] for r in recs] == [
        "tables.derive.load", "tables.derive", "tables.build"]
    assert recs[0]["tid"] != recs[1]["tid"] == threading.get_ident()
    # a backend that derives nothing takes no notice
    monkeypatch.setattr(cb, "_current", cb.PythonBackend())
    cb.valset_change_ahead(12)
    assert started == ["crypto-precompile"]


# -- the guard: one set, nothing of the derive -------------------------------

def _host_check(pubs, msgs, sigs):
    from tendermint_tpu.crypto import native
    check = native.verify_one if native.AVAILABLE else ref.verify
    return jnp.asarray([check(p.tobytes(), m.tobytes(), s.tobytes())
                        for p, m, s in zip(np.asarray(pubs),
                                           np.asarray(msgs),
                                           np.asarray(sigs))])


def _host_verify(tbl, pub_ok, val_pubs, val_idx, tmpl_idx, templates, sigs,
                 base_tbl):
    return _host_check(np.asarray(val_pubs)[np.asarray(val_idx)],
                       np.asarray(templates)[np.asarray(tmpl_idx)], sigs)


def _host_verify_plain(tbl, pub_ok, val_idx, pubkeys, msgs, sigs, base_tbl):
    return _host_check(pubkeys, msgs, sigs)


@pytest.fixture()
def watched(stub_build, monkeypatch):
    """What the guard watches: every `_loaded` call's program and every
    thread started, with the device programs a node runs stubbed."""
    monkeypatch.setattr(dev, "verify_grouped_templated_jit", _host_verify)
    monkeypatch.setattr(dev, "verify_grouped_jit", _host_verify_plain)
    loaded, started = [], []
    real = cb._loaded
    monkeypatch.setattr(cb, "_loaded",
                        lambda fn, *a: loaded.append(fn) or real(fn, *a))
    real_start = threading.Thread.start
    monkeypatch.setattr(threading.Thread, "start", lambda self: (
        started.append((self.name, getattr(self, "_target", None))),
        real_start(self))[1])
    return loaded, started


def _nothing_of_the_derive(be, t0, derives0):
    assert be._derive_programs == {}
    assert not _since(t0, "tables.derive")
    assert REGISTRY.table_derives.value == derives0


def test_a_backend_that_meets_one_set_loads_what_the_parent_loads(watched):
    loaded, started = watched
    seeds = [bytes([5, i + 1]) + b"\x00" * 30 for i in range(4)]
    pubs = np.frombuffer(b"".join(ref.pubkey_from_seed(s) for s in seeds),
                         np.uint8).reshape(4, 32)
    templates = np.zeros((2, 96), np.uint8)
    idx = (np.arange(8) % 4).astype(np.int32)
    tmpl_idx = (np.arange(8) // 4).astype(np.int32)
    sigs = np.frombuffer(b"".join(
        ref.sign(seeds[v], templates[t].tobytes())
        for v, t in zip(idx, tmpl_idx)), np.uint8).reshape(8, 64)
    be = cb.TpuBackend()
    t0, derives0 = tracing.now_epoch(), REGISTRY.table_derives.value
    for _ in range(3):
        assert be.verify_grouped_templated(b"one", pubs, idx, tmpl_idx,
                                           templates, sigs).all()
    # the parent's two: the build, and the verify program loaded beside
    # it on `_warm_verify_if_cold`'s thread, the only thread there is
    assert sorted(map(id, loaded)) == sorted(map(id, [
        dev.build_neg_comb_jit, dev.verify_grouped_templated_jit]))
    assert [t for _n, t in started] == [cb._loaded]
    assert [r["name"] for r in _since(t0, "tables.")] == [
        "tables.build.load", "tables.build"]
    _nothing_of_the_derive(be, t0, derives0)


def test_a_node_on_a_plain_chain_loads_and_starts_nothing_for_the_derive(
        watched, monkeypatch):
    """A `Node` (`--crypto-backend tpu --fast-sync`) boots, syncs the
    plain chain `tests/chainutil` builds from one peer and hands over to
    consensus: its warm-up threads are the boot's and the hand-over's,
    its programs the build and the window's verify."""
    import chainutil
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.config import test_config as fast_config
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.p2p import connect_switches, make_switch
    from tendermint_tpu.proxy import ClientCreator
    from tendermint_tpu.state import execution
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.types import PrivKey, PrivValidator
    from tendermint_tpu.utils.db import MemDB
    loaded, started = watched
    chain_id, n_blocks = "plain-guard", 131
    privs, vs = chainutil.make_validators(4)
    gen = chainutil.make_genesis(chain_id, privs)
    chain = chainutil.build_chain(
        privs, vs, chain_id, n_blocks,
        app_hashes=chainutil.kvstore_app_hashes(n_blocks))
    src = BlockchainReactor(
        get_state(MemDB(), gen),
        ClientCreator("kvstore").new_app_conns().consensus,
        BlockStore(MemDB()), fast_sync=False)
    for block, ps, seen in chain:
        src.store.save_block(block, ps, seen)
        execution.apply_block(src.state, None, src.proxy, block, ps.header,
                              execution.MockMempool(),
                              check_last_commit=False)
    src_sw = make_switch(chain_id, {"blockchain": src})
    cfg = fast_config()
    cfg.base.chain_id = chain_id
    cfg.base.crypto_backend = "tpu"
    cfg.base.fast_sync = True
    cfg.crypto.supervised = False
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    was = cb._current
    t0, derives0 = tracing.now_epoch(), REGISTRY.table_derives.value
    node = Node(cfg, priv_validator=PrivValidator(PrivKey(b"\x44" * 32)),
                genesis_doc=gen)
    try:
        be = cb.get_backend()
        assert type(be) is cb.TpuBackend
        bc = node.switch.reactor("blockchain")
        assert bc.request_when.wait(60), "the boot warm-up"
        # the hand-over's stage: five programs nothing here runs
        be.precompile_for_validators = lambda *a, **kw: None
        node.start()
        src_sw.start()
        connect_switches(node.switch, src_sw)
        deadline = time.monotonic() + 120
        while not bc.handed_over and time.monotonic() < deadline:
            time.sleep(0.02)
        assert bc.handed_over, bc.pool.status()
        assert bc.state.last_block_height == n_blocks - 1
        assert len(_since(t0, "fastsync.window")) >= 2
        assert not _since(t0, "fastsync.valset_cut")
        assert sorted(map(id, loaded)) == sorted(map(id, [
            dev.build_neg_comb_jit, dev.verify_grouped_templated_jit]))
        warm_ups = [t for n, t in started if n == "crypto-precompile"]
        assert len(warm_ups) == 2         # the boot's and the hand-over's
        assert not [t for _n, t in started
                    if getattr(t, "__name__", "") == "_derive_programs_for"]
        assert [r["name"] for r in _since(t0, "tables.")] == [
            "tables.build.load", "tables.build"]
        _nothing_of_the_derive(be, t0, derives0)
    finally:
        node.stop()
        src_sw.stop()
        cb._current = was
