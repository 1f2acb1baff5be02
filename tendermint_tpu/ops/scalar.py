"""Batched arithmetic mod the ed25519 group order L on TPU.

L = 2^252 + 27742317777372353535851937790883648493.  The verifier needs two
scalar ops per signature (reference scalar path: one per vote,
`types/vote_set.go:175`):
  * `reduce512` — fold the 64-byte SHA-512 challenge H(R||A||M) to k mod L,
  * `lt_L`       — the malleability check s < L on the signature's s half.

Values are little-endian radix-2^8 limbs in int32 lanes (byte == limb), the
same representation `tendermint_tpu.ops.field` uses, so signature bytes feed
straight in.  The fold uses the signed identity 2^256 = -16c (mod L) with
c = L - 2^252: three folds take 512 bits to < 2^257, then a binary chain of
conditional subtractions {16L..L} lands in [0, L).  Everything is exact int32
with static shapes — jit/vmap friendly.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

L = 2**252 + 27742317777372353535851937790883648493
_C = L - 2**252            # 125 bits
_C16 = 16 * _C             # 129 bits -> 17 limbs


def _int_to_limbs(x: int, n: int) -> np.ndarray:
    assert 0 <= x < 1 << (8 * n)
    return np.array([(x >> (8 * i)) & 0xFF for i in range(n)], dtype=np.int32)


_C16_LIMBS = _int_to_limbs(_C16, 17)
L_LIMBS = _int_to_limbs(L, 33)
# Binary csub ladder: value < 32L after the folds, so 16L..L suffices.
_KL_LIMBS = [_int_to_limbs(k * L, 33) for k in (16, 8, 4, 2, 1)]


def _carry(x: jnp.ndarray) -> jnp.ndarray:
    """Signed exact carry: limbs -> [0,255] plus an appended top limb.

    Exact for any int32 limbs with |limb| < 2^23; only the final limb may
    be negative (it absorbs the net overflow/underflow).  Fully parallel:
    4 shift-and-fold passes leave body limbs in [-1, 256],
    a +1-per-limb lift makes them nonnegative for the Kogge-Stone exact
    normalize, and a borrow-lookahead subtraction takes the lift back out
    — ~20 vector ops instead of an n-step sequential chain.
    """
    from tendermint_tpu.ops.field import ks_normalize, ks_sub_const

    body, top = x, jnp.zeros_like(x[..., 0])
    for _ in range(4):
        c = body >> 8
        body = (body & 0xFF).at[..., 1:].add(c[..., :-1])
        top = top + c[..., -1]
    # body in [-1, 256]: lift by +1, normalize, subtract the lift (the
    # lookahead conditions live in ONE place — field.ks_normalize /
    # ks_sub_const)
    b, t1 = ks_normalize(body + 1)
    r, t2 = ks_sub_const(b, jnp.ones_like(b))
    return jnp.concatenate([r, (top + t1 - t2)[..., None]], axis=-1)


def _mul_const(a: jnp.ndarray, const: np.ndarray) -> jnp.ndarray:
    """Schoolbook product of limb vector `a` with a small numpy constant."""
    na, nb = a.shape[-1], len(const)
    acc = jnp.zeros(a.shape[:-1] + (na + nb - 1,), dtype=jnp.int32)
    for i in range(nb):
        acc = acc.at[..., i:i + na].add(a * int(const[i]))
    return acc


def _fold(x: jnp.ndarray) -> jnp.ndarray:
    """One application of  hi*2^256 + lo  ->  lo - 16c*hi  (mod L)."""
    lo, hi = x[..., :32], x[..., 32:]
    prod = _mul_const(hi, _C16_LIMBS)
    n = max(32, prod.shape[-1])
    lo_p = jnp.pad(lo, [(0, 0)] * (lo.ndim - 1) + [(0, n - 32)])
    prod_p = jnp.pad(prod, [(0, 0)] * (prod.ndim - 1) + [(0, n - prod.shape[-1])])
    return _carry(lo_p - prod_p)


def _csub(x: jnp.ndarray, const: np.ndarray) -> jnp.ndarray:
    """x - const if that is >= 0 else x, via borrow lookahead (33 limbs)."""
    from tendermint_tpu.ops.field import ks_sub_const

    diff, borrow = ks_sub_const(x, jnp.asarray(const))
    return jnp.where((borrow == 0)[..., None], diff, x)


def reduce512(h: jnp.ndarray) -> jnp.ndarray:
    """SHA-512 digest uint8[..., 64] (little-endian) -> (h mod L) int32[..., 32]."""
    x = h.astype(jnp.int32)
    x = _fold(x)            # 49 limbs, |value| < 2^406
    x = _fold(x)            # 34 limbs, |value| < 2^260
    x = _fold(x)            # 33+1 limbs, value in (-2^134, 2^256)
    # drop known-zero top limbs down to 33, then make positive by adding L
    x = _carry(x[..., :33] + jnp.asarray(L_LIMBS))[..., :33]
    for kl in _KL_LIMBS:
        x = _csub(x, kl)
    return x[..., :32]


def lt_const(b: jnp.ndarray, const_limbs: np.ndarray) -> jnp.ndarray:
    """Little-endian bytes/limbs [..., N] < constant -> bool[...]
    (borrow lookahead: only the final borrow is needed)."""
    from tendermint_tpu.ops.field import ks_sub_const

    _, borrow = ks_sub_const(b.astype(jnp.int32), jnp.asarray(const_limbs))
    return borrow == 1


def lt_L(s: jnp.ndarray) -> jnp.ndarray:
    """Malleability check: uint8[..., 32] little-endian value < L -> bool[...]."""
    return lt_const(s, L_LIMBS[:32])


def nibbles(s: jnp.ndarray) -> jnp.ndarray:
    """Limbs/bytes [..., 32] -> 64 little-endian 4-bit windows int32[..., 64]."""
    x = s.astype(jnp.int32)
    lo = x & 0xF
    hi = (x >> 4) & 0xF
    return jnp.stack([lo, hi], axis=-1).reshape(s.shape[:-1] + (64,))


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs)
    return sum(int(arr[..., i]) << (8 * i) for i in range(arr.shape[-1]))
