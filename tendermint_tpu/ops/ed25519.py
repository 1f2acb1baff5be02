"""Batched ed25519 signature verification on TPU — the crypto hot plane.

Replaces the reference's one-scalar-verify-per-vote
(`types/vote_set.go:175`, `types/validator_set.go:247-264`): thousands of
(message, pubkey, signature) triples are verified in one jitted call, with
the SHA-512 challenge, the mod-L reduction, both scalar multiplications and
the final point comparison all on device.

Semantics are cofactorless verification — enc([s]B - [k]A) == R — matching
`crypto.pure_ed25519.verify` (the golden reference) bit-for-bit on valid
and adversarial inputs, plus the s < L malleability check.

Messages in one batch must share a static byte length; the consensus
sign-bytes layout is fixed-width for exactly this reason
(`tendermint_tpu.types.canonical`).  Heterogeneous batches are handled by
callers bucketing per length (see `crypto.backend`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.ops import curve
from tendermint_tpu.ops import scalar as sc
from tendermint_tpu.ops import sha512 as s512


def verify_core(pubkeys: jnp.ndarray, sigs: jnp.ndarray,
                k_scalars: jnp.ndarray) -> jnp.ndarray:
    """Verification with a precomputed challenge scalar.

    pubkeys uint8[..., 32], sigs uint8[..., 64], k int32/uint8[..., 32]
    (k = H(R||A||M) mod L) -> bool[...].
    """
    A, ok_a = curve.decompress(pubkeys)
    R, ok_r = curve.decompress(sigs[..., :32])
    s_bytes = sigs[..., 32:]
    ok_s = sc.lt_L(s_bytes)
    sB = curve.scalar_mul_base(s_bytes)
    kA = curve.scalar_mul(k_scalars, curve.pt_neg(A))
    Rprime = curve.pt_add(sB, kA)
    return ok_a & ok_r & ok_s & curve.pt_eq(Rprime, R)


def verify(pubkeys: jnp.ndarray, msgs: jnp.ndarray,
           sigs: jnp.ndarray) -> jnp.ndarray:
    """Full batched verify: uint8 pubkeys[..., 32], msgs[..., M] (M static),
    sigs[..., 64] -> bool[...]."""
    challenge = jnp.concatenate(
        [sigs[..., :32], pubkeys, msgs], axis=-1)
    k = sc.reduce512(s512.sha512(challenge))
    return verify_core(pubkeys, sigs, k)


verify_batch = jax.jit(verify)
"""jitted entry point; jax caches one executable per (batch, msg_len) shape."""


def build_neg_comb(pubkeys: jnp.ndarray) -> tuple:
    """Decompress V pubkeys and build packed affine comb tables of THEIR
    NEGATIONS (verification needs [k](-A)).
    Returns (table uint8[26, 1024, V, 3, 32], ok bool[V]).

    One device call per validator set; the tables then serve every
    subsequent verify against that set (see `crypto.backend`'s cache).
    This is the amortization the reference cannot express — its scalar
    loop re-does the full ladder per vote (`types/validator_set.go:247`).
    """
    A, ok = curve.decompress(pubkeys)
    tbl, tbl_ok = curve.build_affine_comb(curve.pt_neg(A))
    return tbl, ok & tbl_ok


build_neg_comb_jit = jax.jit(build_neg_comb)


def comb_columns(tbls: tuple, oks: tuple, src: jnp.ndarray) -> tuple:
    """A comb table assembled from the columns of others: with the
    tables of `tbls` (each uint8[26, 1024, V_i, 3, 32], `oks` their
    bool[V_i]) laid side by side along the column axis, column j of the
    result is column src[j].  A column is a function of its own public
    key and of nothing else (`build_neg_comb`), so a set that shares
    keys with a resident table takes those columns from it and builds
    only the keys that joined (`crypto.backend.TpuBackend`).  The result
    is a new array; no argument is donated.

    One window at a time (`lax.map` over the 26): the columns laid side
    by side are then one window's (14 MB at 144 columns) and never the
    whole table's, which compiled whole is 471 MiB of temporaries at 128
    columns and 1,958 at 512, and is none this way.
    """
    def window(rows):
        row = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
        return jnp.take(row, src, axis=1, mode="clip")

    ok = oks[0] if len(oks) == 1 else jnp.concatenate(oks)
    return lax.map(window, tbls), jnp.take(ok, src, mode="clip")


comb_columns_jit = jax.jit(comb_columns)


def verify_grouped(tables: jnp.ndarray, pub_ok: jnp.ndarray,
                   val_idx: jnp.ndarray, pubkeys: jnp.ndarray,
                   msgs: jnp.ndarray, sigs: jnp.ndarray,
                   base_tbl: jnp.ndarray | None = None) -> jnp.ndarray:
    """Grouped verify: lane i checks sig[i] by validator val_idx[i] using
    cached affine comb tables — ~8x fewer field muls than `verify`:

      * no per-lane pubkey decompress (tables carry the group element),
      * no variable-base ladder (32 gathered mixed adds, ~224 muls),
      * no per-lane R decompress: the check is enc([s]B + [k](-A)) ==
        R_bytes with the encode's inversion batched over all lanes
        (`curve.encode_batch`, ~5 muls/lane).

    The byte comparison is EXACTLY the golden semantics
    (`crypto.pure_ed25519.verify`: enc([s]B - [k]A) == R): a
    non-canonical or off-curve R encoding can never equal the canonical
    encoding of an on-curve point, which is precisely when the golden
    pt_decode rejects.

    pubkeys[N, 32] are the PER-LANE keys (only for the challenge hash
    k = H(R||A||M); group math comes from the tables).
    """
    challenge = jnp.concatenate([sigs[..., :32], pubkeys, msgs], axis=-1)
    k = sc.reduce512(s512.sha512(challenge))
    s_bytes = sigs[..., 32:]
    ok_s = sc.lt_L(s_bytes)
    # [s]B and [k](-A) stay SEPARATE scans on purpose: the two comb
    # chains are independent, so the device overlaps them — a merged
    # single-accumulator scan measured ~40% slower at 64k lanes
    sB = curve.scalar_mul_base(s_bytes, base_tbl)
    kA = curve.scalar_mul_comb(tables, val_idx, k)
    enc, ok_z = curve.encode_batch(curve.pt_add(sB, kA))
    ok_r = jnp.all(enc == sigs[..., :32], axis=-1)
    return pub_ok[val_idx] & ok_s & ok_r & ok_z


verify_grouped_jit = jax.jit(verify_grouped)


def verify_grouped_templated(tables: jnp.ndarray, pub_ok: jnp.ndarray,
                             val_pubs: jnp.ndarray, val_idx: jnp.ndarray,
                             tmpl_idx: jnp.ndarray,
                             templates: jnp.ndarray, sigs: jnp.ndarray,
                             base_tbl: jnp.ndarray | None = None
                             ) -> jnp.ndarray:
    """Grouped verify with DEVICE-side message/pubkey assembly.

    Vote sign-bytes exclude the signer, so every lane of a commit that
    votes the same block signs the IDENTICAL fixed 128-byte message
    (`types/canonical.py` layout) — a window of K blocks has ~K distinct
    messages.  The host therefore ships only templates[T, 128] plus a
    per-lane template index, and per-lane pubkeys come from the small
    [V, 32] key matrix already resident with the comb tables: per-lane
    transfer drops from 228 B (msg+pub+sig) to 72 B (sig+two indices) —
    a 3x cut in the PCIe/interconnect cost of the verification grid.
    """
    msgs = jnp.take(templates, tmpl_idx, axis=0)
    pubkeys = jnp.take(val_pubs, val_idx, axis=0)
    return verify_grouped(tables, pub_ok, val_idx, pubkeys, msgs, sigs,
                          base_tbl)


verify_grouped_templated_jit = jax.jit(verify_grouped_templated)
