"""Batched GF(2^255-19) arithmetic for TPU in radix-2^8 int32 limbs.

TPU has no native 64-bit integer multiply, so field elements are held as 32
little-endian limbs of 8 bits each in an int32 lane (shape `[..., 32]`).

Representation invariant ("normalized"): |limb| <= 512.  Carry propagation
is done with *parallel* vector passes (shift the carry vector by one limb,
fold the 2^256 overflow back with x38) instead of a 32-step sequential
chain — interval analysis (executable: tests/test_field.py
`test_carry_pass_counts_preserve_invariant`) shows 4 passes re-establish
the invariant after a schoolbook product (columns <= 32*512^2*39 < 2^31,
exact in int32) and 2 passes after add/sub.  This
keeps both the XLA graph and the critical path shallow.

All functions are shape-polymorphic over leading batch dims and jit/vmap
friendly (static shapes, no data-dependent control flow).

This is the substrate for the batch ed25519 verifier that replaces the
reference's scalar per-vote verify (reference `types/vote_set.go:175`,
`types/validator_set.go:247-249`).
"""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

NLIMBS = 32
RADIX = 8
MASK = (1 << RADIX) - 1

P = 2**255 - 19
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)


def int_to_limbs(x: int) -> np.ndarray:
    """Python int (0 <= x < 2^256) -> np.int32[32] little-endian limbs."""
    assert 0 <= x < 2**256
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMBS)],
                    dtype=np.int32)


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs)
    return sum(int(arr[..., i]) << (RADIX * i) for i in range(NLIMBS))


def const(x: int) -> jnp.ndarray:
    return jnp.asarray(int_to_limbs(x))


# 8p in a 32-limb representation with small limbs (8p >= 2^256 so the
# canonical byte representation does not exist; limbs [104, 255.., 1023]
# sum to exactly 2^258 - 152).  Added before subtraction so the value stays
# nonnegative for any normalized subtrahend.
_EIGHT_P = np.full(NLIMBS, 255, dtype=np.int32)
_EIGHT_P[0] = 104
_EIGHT_P[31] = 1023
assert sum(int(v) << (8 * i) for i, v in enumerate(_EIGHT_P)) == 8 * P

_P_LIMBS = int_to_limbs(P)
# 2^256 - p = 2^255 + 19: the complement used for parallel conditional
# subtraction (x >= p  <=>  x + (2^256 - p) carries out of limb 31).
_NEG_P = np.zeros(NLIMBS, dtype=np.int32)
_NEG_P[0] = 19
_NEG_P[31] = 128


def carry(x: jnp.ndarray, passes: int = 4) -> jnp.ndarray:
    """Parallel carry: `passes` rounds of  x -> (x & 255) + shift(x >> 8),
    with the limb-31 carry folded into limb 0 via 2^256 = 38 (mod p).

    Exact for |limb| < 2^31 / 39; arithmetic right shift gives floor
    division so negative limbs are handled.  Re-establishes |limb| <= 512
    given enough passes for the input bound (4 covers a schoolbook product,
    2 covers one add/sub of normalized values).
    """
    for _ in range(passes):
        c = x >> RADIX
        x = x & MASK
        x = x.at[..., 1:].add(c[..., :-1])
        x = x.at[..., 0].add(c[..., -1] * 38)
    return x


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return carry(a + b, passes=2)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return carry(a - b + jnp.asarray(_EIGHT_P), passes=2)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return carry(jnp.asarray(_EIGHT_P) - a, passes=2)


def _fold_carry(acc: jnp.ndarray) -> jnp.ndarray:
    """Fold product columns 32..62 by 38 (2^256 = 38 mod p) and carry."""
    lo = acc[..., :NLIMBS]
    hi = acc[..., NLIMBS:]
    lo = lo.at[..., :NLIMBS - 1].add(hi * 38)
    return carry(lo, passes=4)


# Fixed anti-diagonal scatter: column k of M sums outer-product entries
# (i, j) with i + j == k, turning the limb product into one MXU matmul.
_ADIAG = np.zeros((NLIMBS * NLIMBS, 2 * NLIMBS - 1), np.float32)
for _i in range(NLIMBS):
    for _j in range(NLIMBS):
        _ADIAG[_i * NLIMBS + _j, _i + _j] = 1.0


def mul_basic(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product as outer-product + one f32 matmul — the
    compile-cheap path.

    The elementwise outer [..., 32, 32] (entries <= 512^2, f32-exact) is
    contracted against the fixed 0/1 anti-diagonal matrix on the MXU with
    Precision.HIGHEST (full f32: column sums <= 32*512^2 < 2^24 stay
    exact; the TPU default bf16 passes would truncate).  XLA compiles a
    plain dot quickly where a padded-row formulation (32 pads + stack +
    sum per mul) balloons chain graphs, which is what the long
    table-build chains need.  Works for any rank.

    Seen on jax 0.9.0 / libtpu 0.0.34, TPU v5 lite (PERF.md, PR 21): at
    8,192 lanes this form and the conv form in `mul` agree limb for limb
    and with Python ints on the +-512 extremes; one isolated product
    compiles in 4.3 s (conv: 6.8 s) and both run in 0.44 ms, i.e. at the
    dispatch floor — which form is faster inside the verify graph has
    not been measured on this stack.
    """
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    af = jnp.broadcast_to(a, shape).astype(jnp.float32)
    bf = jnp.broadcast_to(b, shape).astype(jnp.float32)
    outer = (af[..., :, None] * bf[..., None, :]).reshape(
        shape[:-1] + (NLIMBS * NLIMBS,))
    prod = jax.lax.dot_general(
        outer, jnp.asarray(_ADIAG), (((outer.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return _fold_carry(prod.astype(jnp.int32))


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 32x32 limb product with fold of columns 32..62 by 38.

    For flat batches the product is ONE batch-grouped convolution in f32
    (every lane convolves with its own 32-tap filter): with both operands
    under the |limb| <= 512 invariant every column sum is below
    32*512*512 < 2^24, so f32 accumulation is exact, and
    `Precision.HIGHEST` pins the TPU conv to f32-faithful passes.  The
    conv serves only the flat hot-path shapes (2-d, >= 4096 lanes: the
    verify kernel's lane batches); small batches — table-build chains
    over V validators, recursion totals — and shapes deeper than 2-d take
    `mul_basic`.

    Seen on jax 0.9.0 / libtpu 0.0.34, TPU v5 lite (PERF.md, PR 21): the
    conv compiles and is exact (see `mul_basic`), and the verify graphs
    built on it agree with OpenSSL lane for lane at 8,192 and 65,536
    lanes.  The >2-d restriction dates from a compiler build that
    aborted on conv+reshape there; on this stack a [64, 128, 32] operand
    compiled and ran, alone and inside a `lax.scan` body, with results
    equal to the 2-d form.  The split is kept as it was: lifting it
    changes the table-build and inversion graphs and is a perf_opt
    issue's to measure.  On the CPU XLA backend the grouped conv is
    pathological (33 s per call at 8,192 groups); the test suite never
    reaches 4,096 lanes.
    """
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    flat = 1
    for d in shape[:-1]:
        flat *= d
    if len(shape) > 2 or flat < 4096:
        return mul_basic(a, b)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    n = 1
    for d in shape[:-1]:
        n *= d
    lhs = a.astype(jnp.float32).reshape(n, 1, NLIMBS)
    rhs = jnp.flip(b.astype(jnp.float32), -1).reshape(n, 1, NLIMBS)
    out = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,),
        padding=[(NLIMBS - 1, NLIMBS - 1)],
        batch_group_count=n, precision=jax.lax.Precision.HIGHEST)
    return _fold_carry(out.reshape(shape[:-1] + (2 * NLIMBS - 1,))
                       .astype(jnp.int32))


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    return mul(a, a)


def mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """Multiply by a small constant (normalized a, k <= 4)."""
    assert 1 <= k <= 4
    return carry(a * k, passes=2)


def _pow_const(z: jnp.ndarray, exp: int) -> jnp.ndarray:
    """z^exp via one square-and-multiply scan over the static bit string.

    ~2x the multiplies of the ref10 addition chain (508 vs 265 for p-2),
    but the whole ladder is ONE two-mul scan body for XLA — the chain's
    ~30 distinct mul/fori sites were several seconds of compile at every
    ladder call site (decompress, batch inversion), and ladders run
    either on tiny shapes (V keys, recursion totals) or once per batch,
    so the extra multiplies are noise at runtime.
    """
    bits = jnp.asarray(np.array([int(b) for b in bin(exp)[3:]], np.bool_))

    def body(acc, bit):
        acc = mul_basic(acc, acc)
        return jnp.where(bit, mul_basic(acc, z), acc), None

    acc, _ = jax.lax.scan(body, z, bits)
    return acc


def inv(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2) = z^(2^255 - 21)."""
    return _pow_const(z, P - 2)


def pow22523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3)."""
    return _pow_const(z, (P - 5) // 8)


def _batch_inv_nonzero(z: jnp.ndarray) -> jnp.ndarray:
    """Blocked Montgomery inversion of NONZERO [N, 32] values.

    Reshapes to [K, C] columns and runs two lax.scan product sweeps whose
    body is a single `mul` — the traced graph stays tiny regardless of N
    (a log-depth associative_scan here made XLA compile for minutes) —
    then recurses on the C column totals until a small unrolled base.
    Work is still ~5 muls per lane; sequential depth is ~2*sqrt pieces.
    """
    n = z.shape[0]
    one = jnp.asarray(int_to_limbs(1))
    if n <= 8:
        # unrolled exclusive prefix/suffix products + one inversion ladder
        pre, acc = [], jnp.broadcast_to(one, z.shape[-1:])
        for i in range(n):
            pre.append(acc)
            acc = mul_basic(acc, z[i]) if i < n - 1 else acc
        suf, acc = [None] * n, jnp.broadcast_to(one, z.shape[-1:])
        for i in range(n - 1, -1, -1):
            suf[i] = acc
            acc = mul_basic(acc, z[i])
        tinv = inv(acc)          # acc == product of all lanes
        return jnp.stack([mul_basic(mul_basic(pre[i], suf[i]), tinv)
                          for i in range(n)])
    c = 1 << (max(n, 4).bit_length() // 2)       # columns ~ sqrt(n)
    k = -(-n // c)
    pad = k * c - n
    zs = jnp.concatenate(
        [z, jnp.broadcast_to(one, (pad, NLIMBS))]) if pad else z
    cols = zs.reshape(k, c, NLIMBS)

    def fwd(carry, row):
        return mul_basic(carry, row), carry      # ys = EXCLUSIVE prefix
    ones_c = jnp.broadcast_to(one, (c, NLIMBS))
    total, pre_ex = jax.lax.scan(fwd, ones_c, cols)
    _, suf_ex_rev = jax.lax.scan(fwd, ones_c, cols[::-1])
    suf_ex = suf_ex_rev[::-1]
    tinv = _batch_inv_nonzero(total)             # recurse on [C] totals
    zi = mul_basic(mul_basic(pre_ex, suf_ex), tinv[None, :, :])
    return zi.reshape(k * c, NLIMBS)[:n]


def batch_inv(z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Montgomery batch inversion over the leading axis.

    z int32[N, 32] -> (z^-1 int32[N, 32], nonzero bool[N]).  One ~265-mul
    inversion ladder amortizes over the whole batch; per-lane cost is ~5
    muls.  Lanes with z == 0 (no inverse) return 0 and are flagged False —
    they are masked to 1 internally so they cannot zero a running product
    and poison the rest of the batch.
    """
    nz = ~is_zero(z)
    one = jnp.asarray(int_to_limbs(1))
    zs = jnp.where(nz[..., None], z, one)
    zi = _batch_inv_nonzero(zs)
    return jnp.where(nz[..., None], zi, 0), nz


def ks_prefix(g: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Kogge-Stone scan of the carry-lookahead monoid over the limb axis.

    g[i] = limb i generates a carry on its own; p[i] = limb i propagates
    an incoming carry.  Returns G[i] = carry OUT of limb i given carry-in
    0 to limb 0 — log2(n) parallel steps instead of an n-step chain.
    """
    n = g.shape[-1]
    G, Pp = g, p
    sh = 1
    while sh < n:
        pad = [(0, 0)] * (g.ndim - 1) + [(sh, 0)]
        Gs = jnp.pad(G[..., :-sh], pad)
        Ps = jnp.pad(Pp[..., :-sh], pad)
        G = G | (Pp & Gs)
        Pp = Pp & Ps
        sh *= 2
    return G


def _carry_in(G: jnp.ndarray) -> jnp.ndarray:
    """Carry INTO each limb from the inclusive carry-out scan."""
    pad = [(0, 0)] * (G.ndim - 1) + [(1, 0)]
    return jnp.pad(G[..., :-1], pad)


def ks_normalize(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact byte normalization of limbs in [0, 510] via carry lookahead.

    Returns (bytes in [0,255], carry_out in {0,1}).  Limbs <= 510 keep
    every carry in {0,1}: generate iff limb >= 256, propagate iff
    limb >= 255.
    """
    G = ks_prefix(x >= 256, x >= 255)
    r = (x + _carry_in(G).astype(x.dtype)) & MASK
    return r, G[..., -1].astype(x.dtype)


def ks_sub_const(x: jnp.ndarray, c: jnp.ndarray) -> tuple:
    """(x - c) per byte limb with borrow lookahead.

    x limbs in [0, 255+eps], c limbs in [0, 255].  Returns (diff bytes,
    borrow_out in {0,1}): borrow generates iff x_i < c_i, propagates iff
    x_i <= c_i.
    """
    B = ks_prefix(x < c, x <= c)
    r = (x - c - _carry_in(B).astype(x.dtype)) & MASK
    return r, B[..., -1].astype(x.dtype)


_E40 = 40  # per-limb lift clearing the [-39, +] residual range


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Fully reduce to the canonical representative in [0, p), limbs [0,255].

    Fully parallel (a sequential 64-step carry chain here was a fifth of
    the grouped-verify step): parallel carry passes leave
    limbs in [-39, 333]; lifting by +40 per limb makes them nonnegative
    for an exact Kogge-Stone normalize, a borrow-lookahead subtraction
    takes the lift back out, the net 2^256 wrap folds by 38, and two
    complement-add rounds conditionally subtract p.  Requires value >= 0
    (all library ops preserve nonnegative values).
    """
    x = carry(x, passes=4)                 # limbs [-39, 333], value < 1.5*2^256
    b, t1 = ks_normalize(x + _E40)         # bytes of value + 40*(2^256-1)/255
    r, t2 = ks_sub_const(b, jnp.full_like(b, _E40))
    x = r.at[..., 0].add((t1 - t2) * 38)   # net wrap in {0,1}: fold 2^256 = 38
    b2, t = ks_normalize(x)                # round 2 clears the +38 on limb 0
    x = b2.at[..., 0].add(t * 38)
    # value < 2^256 < 2p + 39: conditionally subtract p twice via the
    # complement: x >= p  <=>  x + (2^256 - p) carries out of limb 31
    neg_p = jnp.asarray(_NEG_P)
    for _ in range(2):
        s, t3 = ks_normalize(x + neg_p)
        x = jnp.where((t3 == 1)[..., None], s, x)
    return x


def is_zero(x: jnp.ndarray) -> jnp.ndarray:
    """Boolean [...,] mask: x == 0 mod p."""
    return jnp.all(canonical(x) == 0, axis=-1)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return is_zero(sub(a, b))


def parity(x: jnp.ndarray) -> jnp.ndarray:
    """LSB of the canonical representative (the ed25519 sign bit source)."""
    return canonical(x)[..., 0] & 1


def to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """Canonical little-endian 32-byte encoding, uint8[..., 32]."""
    return canonical(x).astype(jnp.uint8)


def from_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., 32] -> limbs (radix 2^8 means bytes are the limbs)."""
    return b.astype(jnp.int32)
