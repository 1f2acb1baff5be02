"""Differential tests: TPU batch ed25519 verifier vs the golden reference.

Mirrors the reference's crypto trust chain (reference `types/vote_set.go:175`
uses go-crypto ed25519); here the chain is pure_ed25519 (bigint, obviously
correct) -> ops.ed25519 (batched device kernel), exercised on valid,
corrupted, and adversarial inputs in one batch.
"""

import secrets

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.crypto import pure_ed25519 as ref
from tendermint_tpu.ops import ed25519 as dev
from tendermint_tpu.ops import scalar as sc

MSG_LEN = 96


def _mk(n, msg_len=MSG_LEN):
    seeds = [secrets.token_bytes(32) for _ in range(n)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    msgs = [secrets.token_bytes(msg_len) for _ in range(n)]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
    return pubs, msgs, sigs


def _arr(rows, width):
    return jnp.asarray(
        np.frombuffer(b"".join(rows), dtype=np.uint8).reshape(-1, width))


def _run(pubs, msgs, sigs):
    # pad every batch to 16 lanes so the whole file shares one compile
    n = len(pubs)
    pad = 16 - n
    assert pad >= 0
    pubs = list(pubs) + [pubs[0]] * pad
    msgs = list(msgs) + [msgs[0]] * pad
    sigs = list(sigs) + [sigs[0]] * pad
    got = dev.verify_batch(_arr(pubs, 32), _arr(msgs, MSG_LEN), _arr(sigs, 64))
    return np.asarray(got)[:n]


def test_valid_batch():
    pubs, msgs, sigs = _mk(16)
    assert _run(pubs, msgs, sigs).all()


def test_rejects_mutations():
    pubs, msgs, sigs = _mk(8)
    cases = []
    # flip one bit in: message, sig R, sig s, pubkey
    m = bytearray(msgs[0]); m[0] ^= 1
    cases.append((pubs[0], bytes(m), sigs[0]))
    s = bytearray(sigs[1]); s[0] ^= 1
    cases.append((pubs[1], msgs[1], bytes(s)))
    s = bytearray(sigs[2]); s[40] ^= 1
    cases.append((pubs[2], msgs[2], bytes(s)))
    p = bytearray(pubs[3]); p[0] ^= 1
    cases.append((bytes(p), msgs[3], sigs[3]))
    # wrong key for message
    cases.append((pubs[4], msgs[5], sigs[5]))
    cp, cm, cs = zip(*cases)
    got = _run(list(cp), list(cm), list(cs))
    want = [ref.verify(p, m, s) for p, m, s in cases]
    assert list(got) == want
    assert not got.any()


def test_malleability_and_edge_encodings():
    pubs, msgs, sigs = _mk(6)
    cases = []
    # s' = s + L: same point equation, must be rejected by s < L check
    s_int = int.from_bytes(sigs[0][32:], "little")
    smal = sigs[0][:32] + (s_int + ref.L).to_bytes(32, "little")
    cases.append((pubs[0], msgs[0], smal))
    # non-canonical R encoding (y >= p)
    bad_r = (2**255 - 19).to_bytes(32, "little")
    cases.append((pubs[1], msgs[1], bad_r + sigs[1][32:]))
    # pubkey that does not decode (y >= p)
    cases.append(((2**255 - 1).to_bytes(32, "little"), msgs[2], sigs[2]))
    # identity pubkey (x=0,y=1) with a zero signature: R=identity enc, s=0
    ident_pub = (1).to_bytes(32, "little")
    zero_sig = (1).to_bytes(32, "little") + b"\x00" * 32
    cases.append((ident_pub, msgs[3], zero_sig))
    cp, cm, cs = zip(*cases)
    got = _run(list(cp), list(cm), list(cs))
    want = [ref.verify(p, m, s) for p, m, s in cases]
    assert list(got) == want


def test_mixed_batch_matches_reference_lanewise():
    pubs, msgs, sigs = _mk(8)
    # corrupt half the lanes in assorted ways
    sigs = list(sigs)
    msgs = list(msgs)
    m = bytearray(msgs[1]); m[-1] ^= 0x80; msgs[1] = bytes(m)
    s = bytearray(sigs[3]); s[31] ^= 0x40; sigs[3] = bytes(s)
    s = bytearray(sigs[5]); s[63] ^= 0x02; sigs[5] = bytes(s)
    pubs = list(pubs)
    pubs[7] = ref.pubkey_from_seed(secrets.token_bytes(32))
    got = _run(pubs, msgs, sigs)
    want = [ref.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert list(got) == want
    assert got.sum() == 4


def test_reduce512_matches_bigint():
    rng = np.random.default_rng(1)
    h = rng.integers(0, 256, (32, 64), dtype=np.uint8)
    out = np.asarray(sc.reduce512(jnp.asarray(h)))
    for row, lim in zip(h, out):
        assert sc.limbs_to_int(lim) == int.from_bytes(bytes(row), "little") % sc.L
