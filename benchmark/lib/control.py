"""The verdict control: what lets `correct` fail when the arithmetic is
degraded.

After the window closes, one batch goes through the entry the timed path
uses (`batchplane.verify_grouped_templated`, as `verify_commits_batched`
calls it), in the window's own (lanes, templates) bucket: 64 templates x
V lanes each, laid out as a reactor window lays out its commits, so no
new program is compiled.  Seeded lanes are forged, one kind each of the
five the smoke uses: R, s, s >= L, wrong template, wrong signer.  Every
lane's expected verdict is OpenSSL's own (`cryptography`), never the
construction's, and the device has to agree lane for lane.
"""

from __future__ import annotations

import hashlib

import numpy as np
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey, Ed25519PublicKey)

L = 2**252 + 27742317777372353535851937790883648493
KINDS = ("forged_R", "forged_s", "s_ge_L", "wrong_template", "wrong_signer")


def build(seed: int, val_seeds: list[bytes], window_blocks: int) -> dict:
    """The control batch for a validator set (seeds in set order)."""
    from tendermint_tpu.types import canonical
    n_vals, t = len(val_seeds), window_blocks
    n = n_vals * t
    rng = np.random.default_rng(seed)
    keys = [Ed25519PrivateKey.from_private_bytes(s) for s in val_seeds]
    pubs = [k.public_key() for k in keys]
    templates = [canonical.sign_bytes(
        f"tm-bench-control-{seed}", canonical.TYPE_PRECOMMIT, h + 1, 0,
        block_hash=hashlib.sha256(b"cb%d/%d" % (seed, h)).digest(),
        parts_hash=hashlib.sha256(b"cp%d/%d" % (seed, h)).digest(),
        parts_total=1) for h in range(t)]
    val_idx = np.tile(np.arange(n_vals, dtype=np.int32), t)
    tmpl_idx = np.repeat(np.arange(t, dtype=np.int32), n_vals)
    sigs = np.frombuffer(b"".join(
        keys[v].sign(templates[b]) for b in range(t) for v in range(n_vals)),
        np.uint8).reshape(n, 64).copy()
    k = max(1, n // 64)                         # lanes per forged kind
    bad = rng.choice(n, len(KINDS) * k, replace=False).reshape(len(KINDS), k)
    sigs[bad[0], 3] ^= 0x01
    sigs[bad[1], 40] ^= 0x01
    for i in bad[2]:
        s = int.from_bytes(sigs[i, 32:].tobytes(), "little") + L
        sigs[i, 32:] = np.frombuffer(s.to_bytes(32, "little"), np.uint8)
    if t > 1:
        tmpl_idx[bad[3]] = (tmpl_idx[bad[3]] + 1) % t
    if n_vals > 1:
        val_idx[bad[4]] = (val_idx[bad[4]] + 1) % n_vals
    expect = np.zeros(n, bool)
    for i in range(n):
        try:
            pubs[val_idx[i]].verify(sigs[i].tobytes(), templates[tmpl_idx[i]])
            expect[i] = True
        except (InvalidSignature, ValueError):
            pass
    return {"val_idx": val_idx, "tmpl_idx": tmpl_idx,
            "templates": np.frombuffer(b"".join(templates), np.uint8)
            .reshape(t, -1).copy(),
            "sigs": sigs, "expect": expect, "forged": int(bad.size)}


def device_verdicts(vals, batch: dict) -> np.ndarray:
    """The batch through the timed path's own entry, against the node's
    validator set (its comb tables are resident: no build, no compile)."""
    from tendermint_tpu import batchplane
    return np.asarray(batchplane.verify_grouped_templated(
        vals.set_key(), vals.pubs_matrix(), batch["val_idx"],
        batch["tmpl_idx"], batch["templates"], batch["sigs"],
        producer="fastsync", klass=batchplane.CLASS_FASTSYNC))


def mismatches(got: np.ndarray, batch: dict) -> int:
    """Lanes where the device's verdict is not OpenSSL's.  A batch that
    OpenSSL finds all-valid or that lost its forged lanes proves nothing
    and counts every lane."""
    expect = batch["expect"]
    if got.shape != expect.shape or int((~expect).sum()) != batch["forged"]:
        return int(expect.size)
    return int((got.astype(bool) != expect).sum())
