"""Differential tests for the grouped (fixed-key-set) verify path.

The grouped kernel is the fast-sync hot plane: comb tables are built once
per validator set (`ops.ed25519.build_neg_comb`) and every subsequent
verify is 32 mixed adds per scalar plus a batched encode — it must agree
with the golden bigint reference (`crypto.pure_ed25519.verify`) lane for
lane on valid AND adversarial inputs, exactly like the generic kernel
(reference semantics: one scalar verify per vote,
`types/vote_set.go:175`, `types/validator_set.go:247-264`).
"""

import secrets
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import backend as cb
from tendermint_tpu.crypto import pure_ed25519 as ref
from tendermint_tpu.ops import ed25519 as dev
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.metrics import REGISTRY

MSG_LEN = 96
V = 4


@pytest.fixture(scope="module")
def valset():
    seeds = [secrets.token_bytes(32) for _ in range(V)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    vp = np.frombuffer(b"".join(pubs), np.uint8).reshape(V, 32)
    tbl, ok = dev.build_neg_comb_jit(jnp.asarray(vp))
    assert np.asarray(ok).all()
    return seeds, pubs, vp, tbl, ok


def _run(valset, idx, msgs, sigs):
    _, _, vp, tbl, ok = valset
    n = len(idx)
    pad = 16 - n
    assert pad >= 0
    idx = np.asarray(list(idx) + [idx[0]] * pad, np.int32)
    msgs = list(msgs) + [msgs[0]] * pad
    sigs = list(sigs) + [sigs[0]] * pad
    ma = np.frombuffer(b"".join(msgs), np.uint8).reshape(-1, MSG_LEN)
    sa = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    got = dev.verify_grouped_jit(tbl, ok, jnp.asarray(idx),
                                 jnp.asarray(vp[idx]), jnp.asarray(ma),
                                 jnp.asarray(sa))
    return np.asarray(got)[:n]


def test_valid_batch(valset):
    seeds, pubs, _, _, _ = valset
    idx = [i % V for i in range(16)]
    msgs = [secrets.token_bytes(MSG_LEN) for _ in range(16)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(16)]
    assert _run(valset, idx, msgs, sigs).all()


def test_adversarial_lanes_match_golden(valset):
    seeds, pubs, _, _, _ = valset
    idx = [i % V for i in range(10)]
    msgs = [secrets.token_bytes(MSG_LEN) for _ in range(10)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(10)]
    # s' = s + L (malleability): must be rejected by the s < L check
    s_int = int.from_bytes(sigs[1][32:], "little")
    sigs[1] = sigs[1][:32] + (s_int + ref.L).to_bytes(32, "little")
    # non-canonical R encoding (y >= p)
    sigs[2] = (2**255 - 19).to_bytes(32, "little") + sigs[2][32:]
    # flipped message bit
    m = bytearray(msgs[3]); m[0] ^= 1; msgs[3] = bytes(m)
    # signature by the wrong validator of the right message
    sigs[4] = ref.sign(seeds[(idx[4] + 1) % V], msgs[4])
    # flipped sig bits in R and s halves
    s = bytearray(sigs[5]); s[5] ^= 0x10; sigs[5] = bytes(s)
    s = bytearray(sigs[6]); s[45] ^= 0x10; sigs[6] = bytes(s)
    # R = identity encoding with s = 0 (always-false unless k*A == 0)
    sigs[7] = (1).to_bytes(32, "little") + b"\x00" * 32
    got = _run(valset, idx, msgs, sigs)
    want = [ref.verify(pubs[idx[i]], msgs[i], sigs[i]) for i in range(10)]
    assert got.tolist() == want
    assert got.tolist() == [True, False, False, False, False, False,
                            False, False, True, True]


@pytest.mark.parametrize("w", [0, 1, 2, 13, 25])
def test_table_entries_are_the_references_multiples(valset, w):
    """Entry [w, j, v] is (y+x, y-x, 2d*x*y) of j * 2^(10w) * (-A_v) in
    canonical bytes, computed here by the bigint reference: the digits a
    row's add chain builds (0, 1, 2, 255), the first of each wide add
    (256, 512, 768) and its last (511, 1023), in the first windows, a
    middle one and the top one, whose base is 250 doublings from A."""
    _, pubs, _, tbl, _ = valset
    for v in (0, V - 1):
        neg_a = ref.pt_neg(ref.pt_decode(pubs[v]))
        for j in (0, 1, 2, 255, 256, 511, 512, 768, 1023):
            x, y, z, _ = ref.pt_mul(j << (10 * w), neg_a)
            zi = pow(z, ref.P - 2, ref.P)
            x, y = x * zi % ref.P, y * zi % ref.P
            want = [(y + x) % ref.P, (y - x) % ref.P,
                    2 * ref.D * x * y % ref.P]
            got = [int.from_bytes(bytes(np.asarray(tbl[w, j, v, k])),
                                  "little") for k in range(3)]
            assert got == want, (w, j, v)


def test_invalid_pubkey_in_set():
    """A non-decodable key in the set poisons only its own lanes."""
    seeds = [secrets.token_bytes(32) for _ in range(V)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    pubs[2] = (2**255 - 1).to_bytes(32, "little")    # y >= p: undecodable
    vp = np.frombuffer(b"".join(pubs), np.uint8).reshape(V, 32)
    tbl, ok = dev.build_neg_comb_jit(jnp.asarray(vp))
    assert np.asarray(ok).tolist() == [True, True, False, True]
    idx = np.asarray([0, 1, 2, 3] * 4, np.int32)
    msgs = [secrets.token_bytes(MSG_LEN) for _ in range(16)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(16)]
    ma = np.frombuffer(b"".join(msgs), np.uint8).reshape(-1, MSG_LEN)
    sa = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    got = np.asarray(dev.verify_grouped_jit(
        tbl, ok, jnp.asarray(idx), jnp.asarray(vp[idx]),
        jnp.asarray(ma), jnp.asarray(sa)))
    assert got.tolist() == [i % V != 2 for i in range(16)]


def test_backend_grouped_matches_batch_and_caches():
    """Under conftest's 8 virtual CPU devices this also exercises the
    MESH path: the backend shards lanes across all visible devices with
    replicated comb tables, and must agree lane-wise with the
    single-device kernel (verify_batch below).  The per-device lane
    threshold is forced down so the 16-lane batch rides the mesh."""
    import jax
    from tendermint_tpu.crypto import backend as cb
    be = cb.TpuBackend()
    assert len(jax.devices()) == 8
    assert be._mesh is not None and be._mesh.devices.size == 8
    be.MIN_LANES_PER_DEVICE = 2      # 16 lanes / 8 devices
    seeds = [secrets.token_bytes(32) for _ in range(V)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    vp = np.frombuffer(b"".join(pubs), np.uint8).reshape(V, 32)
    idx = (np.arange(16) % V).astype(np.int32)
    msgs = [secrets.token_bytes(MSG_LEN) for _ in range(16)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(16)]
    sigs[5] = sigs[6]                                 # one bad lane
    ma = np.frombuffer(b"".join(msgs), np.uint8).reshape(-1, MSG_LEN)
    sa = np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64)
    got = be.verify_grouped(b"set-a", vp, idx, ma, sa)
    want = be.verify_batch(vp[idx], ma, sa)
    assert got.tolist() == want.tolist()
    assert not got[5] and got[4]
    # second call hits the table cache (no rebuild)
    assert b"set-a" in be._tables
    n_tables = len(be._tables)
    be.verify_grouped(b"set-a", vp, idx, ma, sa)
    assert len(be._tables) == n_tables
    # reusing a set_key for a different-sized set is refused
    with pytest.raises(ValueError):
        be.verify_grouped(b"set-a", vp[:2], idx % 2, ma, sa)


def test_backend_templated_matches_plain():
    """Device-side message assembly (templates + indices) must agree
    lane-wise with the plain grouped path on valid and corrupted lanes,
    including lanes sharing vs owning templates."""
    from tendermint_tpu.crypto import backend as cb
    be = cb.TpuBackend()
    seeds = [secrets.token_bytes(32) for _ in range(V)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    vp = np.frombuffer(b"".join(pubs), np.uint8).reshape(V, 32)
    # 3 templates: lanes map unevenly; all lanes of a template sign it
    templates = np.frombuffer(
        b"".join(secrets.token_bytes(MSG_LEN) for _ in range(3)),
        np.uint8).reshape(3, MSG_LEN)
    tmpl_idx = np.asarray([0, 0, 1, 2, 2, 2, 0, 1] * 2, np.int32)
    idx = (np.arange(16) % V).astype(np.int32)
    sigs = [ref.sign(seeds[idx[i]], templates[tmpl_idx[i]].tobytes())
            for i in range(16)]
    sigs[4] = sigs[5]                     # corrupt one lane
    sa = np.frombuffer(b"".join(sigs), np.uint8).reshape(16, 64)
    got = be.verify_grouped_templated(b"tmpl-set", vp, idx, tmpl_idx,
                                      templates, sa)
    want = be.verify_grouped(b"tmpl-set", vp, idx,
                             templates[tmpl_idx], sa)
    assert got.tolist() == want.tolist()
    assert not got[4] and got[5]


def _zero_tables(pubs):
    """`build_neg_comb_jit`'s stand-in: tables of zeros of the real
    shape (a real build RUNS half a minute a set on the CPU backend)."""
    from tendermint_tpu.ops.curve import COMB_DIGITS, COMB_WINDOWS
    return (jnp.zeros((COMB_WINDOWS, COMB_DIGITS, len(pubs), 3, 32),
                      jnp.uint8), jnp.ones((len(pubs),), bool))


@pytest.fixture()
def host_kernels(monkeypatch):
    """The backend's two device programs replaced where it looks them up
    (`self._dev.<name>_jit`, at call time): a table of zeros of the real
    shape, and a templated verify that checks every lane it is handed on
    the host and notes the shapes it was handed.  Nothing compiles, so a
    lane count may cross a bucket edge for free; the kernel itself is
    `test_backend_templated_matches_plain`'s business."""
    from tendermint_tpu.crypto import native
    check = native.verify_one if native.AVAILABLE else ref.verify
    handed = []

    def verify(tbl, pub_ok, val_pubs, val_idx, tmpl_idx, templates, sigs,
               base_tbl):
        vp, tm = np.asarray(val_pubs), np.asarray(templates)
        vi, ti, sg = (np.asarray(x) for x in (val_idx, tmpl_idx, sigs))
        handed.append((len(vi), len(ti), len(sg), len(tm)))
        return jnp.asarray([check(vp[v].tobytes(), tm[t].tobytes(),
                                  s.tobytes())
                            for v, t, s in zip(vi, ti, sg)])

    monkeypatch.setattr(dev, "build_neg_comb_jit", _zero_tables)
    monkeypatch.setattr(dev, "verify_grouped_templated_jit", verify)
    return handed


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("n", [15, 16, 17, 31, 32, 33])
def test_the_templated_call_pads_trims_and_counts(host_kernels, n, t):
    """What `backend.verify_call_ms` and the benchmark's counters read
    (`benchmark/layers/backend.verify_call_ms.json`,
    `benchmark/lib/cell.py`): a call of n lanes is one `verify.dispatch`
    and one `verify.collect` with `lanes` n and `bucket` the padded
    size, the device sees whole buckets, the caller gets n verdicts in
    its own order, and the counters move by the real lanes only."""
    import threading
    from tendermint_tpu.crypto import backend as cb
    from tendermint_tpu.crypto import native
    from tendermint_tpu.utils import tracing
    from tendermint_tpu.utils.metrics import REGISTRY
    sign = native.sign_one if native.AVAILABLE else ref.sign
    seeds = [bytes([n, t, i + 1]) + b"\x00" * 29 for i in range(V)]
    vp = np.frombuffer(b"".join(ref.pubkey_from_seed(s) for s in seeds),
                       np.uint8).reshape(V, 32)
    templates = np.frombuffer(secrets.token_bytes(t * MSG_LEN),
                              np.uint8).reshape(t, MSG_LEN)
    idx = (np.arange(n) % V).astype(np.int32)
    tmpl_idx = (np.arange(n) % t).astype(np.int32)
    sigs = [sign(seeds[idx[i]], templates[tmpl_idx[i]].tobytes())
            for i in range(n)]
    # the last real lane is forged; every padding lane copies lane 0,
    # which is good: a verdict or a count taken from the padding shows
    sigs[-1] = sigs[-1][:63] + bytes([sigs[-1][63] ^ 0x01])
    sa = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)

    be = cb.TpuBackend()
    counters = (REGISTRY.sigs_requested, REGISTRY.sigs_verified,
                REGISTRY.verify_batches)
    before = [c.value for c in counters]
    t_start = tracing.now_epoch()
    out = be.verify_grouped_templated(b"pad-set", vp, idx, tmpl_idx,
                                      templates, sa)
    spans = [(s["name"], s["args"]) for s in tracing.RECORDER.since(t_start)
             if s["ts"] >= t_start and s["name"].startswith("verify.") and
             s["tid"] == threading.current_thread().ident]

    b = cb._bucket(n)
    assert out.dtype == bool and out.tolist() == [True] * (n - 1) + [False]
    assert host_kernels == [(b, b, b, cb._bucket(t))]
    assert spans == [("verify.dispatch", {"lanes": n, "bucket": b}),
                     ("verify.collect", {"lanes": n, "bucket": b})]
    assert [c.value - v for c, v in zip(counters, before)] == [n, n - 1, 1]


def test_table_cache_byte_bounded_keeps_small_sets(monkeypatch):
    """Regression for the multi-chain churn: one big validator set plus
    many small light-chain sets must ALL stay resident (the old count
    bound of 8 evicted small tables whenever big ones rotated in, and
    the streaming loop then paid full rebuilds mid-flight).

    What is under test is the backend's residency accounting, so the
    device build is stubbed with zero tables of the real shape: eleven
    real builds cost 4.5 minutes on the CPU backend — a third of the
    whole tier-1 budget — and the build itself is covered by the tests
    around this one."""
    from tendermint_tpu.crypto.backend import TpuBackend

    monkeypatch.setattr(dev, "build_neg_comb_jit", _zero_tables)
    be = TpuBackend()
    sigs = np.zeros((4, 64), np.uint8)
    msgs = np.zeros((4, 128), np.uint8)
    idx = np.zeros(4, np.int32)

    def pubs(tag, n):
        return np.frombuffer(
            b"".join(ref.pubkey_from_seed(bytes([tag, i + 1]) + b"\x00" * 30)
                     for i in range(n)), np.uint8).reshape(n, 32)

    # 10 small sets + 1 bigger set: > the old count cap of 8
    for tag in range(10):
        be.verify_grouped(b"small-%d" % tag, pubs(tag + 1, 2), idx,
                          msgs, sigs)
    be.verify_grouped(b"big-one", pubs(99, 16), idx, msgs, sigs)
    assert len(be._tables) == 11          # nothing evicted: all fit 4 GB
    total = sum(e[0].size for e in be._tables.values())
    assert total <= be.TABLE_CACHE_BYTES


def test_table_disk_cache_roundtrip(tmp_path, monkeypatch):
    """Disk-persisted comb tables: a fresh backend instance loads the
    tables a previous one built (content-addressed by set_key) and
    verifies identically — the warm node-restart path that skips the
    multi-second on-device rebuild."""
    import numpy as np
    from tendermint_tpu.crypto import native
    from tendermint_tpu.crypto import pure_ed25519 as ref
    from tendermint_tpu.crypto.backend import TpuBackend

    monkeypatch.setenv("TM_TABLE_CACHE_DIR", str(tmp_path / "tables"))
    seeds = [bytes([7, i + 1]) + b"\x00" * 30 for i in range(4)]
    pubs = np.frombuffer(
        b"".join(ref.pubkey_from_seed(s) for s in seeds),
        np.uint8).reshape(4, 32)
    msg = b"m" * 128
    sig = (native.sign_one(seeds[1], msg) if native.AVAILABLE
           else ref.sign(seeds[1], msg))
    idx = np.array([1], np.int32)
    msgs = np.frombuffer(msg, np.uint8).reshape(1, 128)
    sigs = np.frombuffer(sig, np.uint8).reshape(1, 64)

    be1 = TpuBackend()
    assert be1.verify_grouped(b"disk-set", pubs, idx, msgs, sigs).all()
    files = list((tmp_path / "tables").iterdir())
    assert len(files) == 1 and files[0].suffix == ".npz"

    be2 = TpuBackend()          # fresh instance: must LOAD, not rebuild
    assert not be2.tables_cached(b"disk-set")
    assert be2.verify_grouped(b"disk-set", pubs, idx, msgs, sigs).all()
    assert be2.tables_cached(b"disk-set")
    # tampered signature still rejected through the loaded tables
    bad = sigs.copy(); bad[0, 0] ^= 1
    assert not be2.verify_grouped(b"disk-set", pubs, idx, msgs, bad).any()

    # corrupt cache file: silently rebuilt, not fatal
    files[0].write_bytes(b"garbage")
    be3 = TpuBackend()
    assert be3.verify_grouped(b"disk-set", pubs, idx, msgs, sigs).all()


# -- which program a call is padded into; what a table build records ---------
# (the device programs stubbed: `host_kernels`)

FULL = (256, 64)                 # a 4-validator window's (lanes, templates)


def _since(t0: float, name: str) -> list[dict]:
    return [s for s in tracing.RECORDER.since(t0)
            if s["name"] == name and s["ts"] >= t0]


def _signed_window(tag: int, blocks: int):
    """`blocks` templates, each signed by 4 keys, block-major as a window
    lays its commits out; the last lane forged."""
    from tendermint_tpu.crypto import native
    sign = native.sign_one if native.AVAILABLE else ref.sign
    seeds = [bytes([tag, i + 1]) + b"\x00" * 30 for i in range(V)]
    pubs = np.frombuffer(b"".join(ref.pubkey_from_seed(s) for s in seeds),
                         np.uint8).reshape(V, 32)
    templates = np.frombuffer(
        b"".join(bytes([tag, b]) * (MSG_LEN // 2) for b in range(blocks)),
        np.uint8).reshape(blocks, MSG_LEN)
    idx = np.tile(np.arange(V, dtype=np.int32), blocks)
    tmpl_idx = np.repeat(np.arange(blocks, dtype=np.int32), V)
    sigs = [sign(seeds[v], templates[t].tobytes())
            for v, t in zip(idx, tmpl_idx)]
    sigs[-1] = sigs[-1][:63] + bytes([sigs[-1][63] ^ 0x01])
    return pubs, idx, tmpl_idx, templates, np.frombuffer(
        b"".join(sigs), np.uint8).reshape(len(sigs), 64)


@pytest.mark.parametrize("blocks", [1, 2, 7, 8, 9, 31, 33, 63])
def test_a_window_of_any_size_is_padded_into_the_warm_full_one(
        host_kernels, blocks):
    """What the boot does, then a cut window: the warm-up compiles the
    full window's own bucket; a window of 1 to 63 blocks after it is
    handed to the device in that shape and no other, its verdicts come
    back trimmed and in order, and the counters move by its real lanes."""
    be = cb.TpuBackend()
    pubs, idx, tmpl_idx, templates, sigs = _signed_window(blocks, blocks)
    be.precompile(b"pad-%d" % blocks, pubs, [("templated",) + FULL],
                  MSG_LEN)
    assert host_kernels == [(FULL[0], FULL[0], FULL[0], FULL[1])]
    del host_kernels[:]
    counters = (REGISTRY.sigs_requested, REGISTRY.sigs_verified,
                REGISTRY.verify_batches)
    before = [c.value for c in counters]
    t0 = tracing.now_epoch()
    out = be.verify_grouped_templated(b"pad-%d" % blocks, pubs, idx,
                                      tmpl_idx, templates, sigs)
    n = blocks * V
    assert host_kernels == [(FULL[0], FULL[0], FULL[0], FULL[1])]
    assert out.dtype == bool and out.tolist() == [True] * (n - 1) + [False]
    assert [c.value - v for c, v in zip(counters, before)] == [n, n - 1, 1]
    mine = threading.current_thread().ident
    assert [(s["name"], s["args"]) for s in tracing.RECORDER.since(t0)
            if s["ts"] >= t0 and s["tid"] == mine and
            s["name"].startswith("verify.")] == [
        ("verify.dispatch", {"lanes": n, "bucket": FULL[0]}),
        ("verify.collect", {"lanes": n, "bucket": FULL[0]})]


def test_a_call_compiles_its_own_bucket_only_where_no_warm_one_fits(
        host_kernels):
    be = cb.TpuBackend()
    pubs, idx, tmpl_idx, templates, sigs = _signed_window(101, 8)
    key = b"pad-own"
    # nothing warm: the call's own (32, 16)
    assert be.verify_grouped_templated(key, pubs, idx, tmpl_idx, templates,
                                       sigs).tolist() == [True] * 31 + [False]
    # a smaller call fits it; a wider one (17 templates) does not
    be.verify_grouped_templated(key, pubs, idx[:4], tmpl_idx[:4],
                                templates[:1], sigs[:4])
    pubs2, idx2, tmpl2, templates2, sigs2 = _signed_window(102, 17)
    be.verify_grouped_templated(key, pubs, idx2[:32], tmpl2[:32] * 2,
                                templates2, sigs2[:32])
    # the warm-up asks for each bucket's own program, whatever is warm
    be.verify_grouped_templated(key, pubs, idx[:4], tmpl_idx[:4],
                                templates[:1], sigs[:4], exact_bucket=True)
    # and the smallest warm fit is taken, not the first or the largest
    be.verify_grouped_templated(key, pubs, idx[:4], tmpl_idx[:4],
                                templates[:1], sigs[:4])
    assert host_kernels == [(32, 32, 32, 16), (32, 32, 32, 16),
                            (32, 32, 32, 32), (16, 16, 16, 16),
                            (16, 16, 16, 16)]
    # another set size or message length shares no program with these
    assert be._warm_shape(V, MSG_LEN + 1, 16, 16) is None
    assert be._warm_shape(17, MSG_LEN, 16, 16) is None
    assert be._warm_shape(V, MSG_LEN, 16, 16) == (16, 16)
    assert be._warm_shape(V, MSG_LEN, 32, 17) == (32, 32)
    assert be._warm_shape(V, MSG_LEN, 33, 16) is None


def test_tables_are_built_once_a_set_and_the_fifo_drops_the_oldest(
        host_kernels, monkeypatch):
    """(d) `tables.build` once a set reached, `tables.evict` and the
    resident bytes with the cache bounded at two tables; a table the
    disk cache holds is `tables.load`, and counts as no build."""
    one = 26 * 1024 * 16 * 96            # V bucket 16, uint8
    monkeypatch.setattr(cb.TpuBackend, "TABLE_CACHE_BYTES", 2 * one)
    be = cb.TpuBackend()
    builds0, evicted0 = (REGISTRY.table_builds.value,
                         REGISTRY.table_evictions.value)
    t0 = tracing.now_epoch()
    sets = [_signed_window(110 + i, 1) for i in range(3)]

    def call(i):
        pubs, idx, tmpl_idx, templates, sigs = sets[i]
        be.verify_grouped_templated(b"set-%d" % i, pubs, idx, tmpl_idx,
                                    templates, sigs)

    call(0)
    call(0)                               # resident: no second build
    assert len(_since(t0, "tables.build")) == 1
    assert REGISTRY.tables_resident_bytes.value == one
    call(1)
    assert REGISTRY.tables_resident_bytes.value == 2 * one
    assert not _since(t0, "tables.evict")
    call(2)                               # the third drops the first
    builds = _since(t0, "tables.build")
    assert [b["args"] for b in builds] == [{"v": V, "bytes": one}] * 3
    assert all(b["dur"] > 0 and "cat" not in b for b in builds)
    assert [e["args"] for e in _since(t0, "tables.evict")] == [
        {"bytes": one}]
    assert REGISTRY.tables_resident_bytes.value == 2 * one
    assert list(be._tables) == [b"set-1", b"set-2"]
    assert REGISTRY.table_builds.value - builds0 == 3
    assert REGISTRY.table_evictions.value - evicted0 == 1
    # the dropped set comes back from the disk cache the first build
    # wrote (conftest gives every test a directory of its own)
    call(0)
    assert len(_since(t0, "tables.build")) == 3
    assert [b["args"] for b in _since(t0, "tables.load")] == [
        {"v": V, "bytes": one}]
    assert REGISTRY.table_builds.value - builds0 == 3
    assert REGISTRY.table_evictions.value - evicted0 == 2
    from tendermint_tpu.utils import metrics
    text = metrics.prometheus_text()
    assert f"tendermint_tables_resident_bytes {2 * one}" in text
    assert "# TYPE tendermint_table_builds counter" in text
    assert "# TYPE tendermint_table_evictions counter" in text
