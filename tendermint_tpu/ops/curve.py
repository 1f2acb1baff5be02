"""Batched edwards25519 group operations on TPU.

Points live in extended homogeneous coordinates (X, Y, Z, T) with XY = ZT —
each coordinate a radix-2^8 limb array `[..., 32]` from
`tendermint_tpu.ops.field`.  All ops broadcast over leading batch dims and
are built from static-shape primitives (lax.scan/fori_loop for ladders), so
a single jit handles any batch size without graph blowup.

This is the group layer under the batch ed25519 verifier that replaces the
reference's scalar per-vote verify (reference `types/vote_set.go:175`,
`types/validator_set.go:247-249`).  Formulas: add-2008-hwcd-3 /
dbl-2008-hwcd for a=-1 twisted Edwards, the same shapes the reference-era
Go ed25519 uses internally.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.ops import field as fe
from tendermint_tpu.ops import scalar as sc
from tendermint_tpu.crypto import pure_ed25519 as ref

# Module-level constant limb arrays (device-cached by jit as needed).
_D2 = fe.int_to_limbs(fe.D2)
_SQRT_M1 = fe.int_to_limbs(fe.SQRT_M1)
_D = fe.int_to_limbs(fe.D)
_ONE = fe.int_to_limbs(1)
_ZERO = np.zeros(fe.NLIMBS, dtype=np.int32)


def identity(batch_shape=()) -> tuple:
    z = jnp.broadcast_to(jnp.asarray(_ZERO), batch_shape + (fe.NLIMBS,))
    o = jnp.broadcast_to(jnp.asarray(_ONE), batch_shape + (fe.NLIMBS,))
    return (z, o, o, z)


def pt_add(Q, R):
    """Complete extended addition (add-2008-hwcd-3, a=-1): 9 field muls."""
    x1, y1, z1, t1 = Q
    x2, y2, z2, t2 = R
    a = fe.mul(fe.sub(y1, x1), fe.sub(y2, x2))
    b = fe.mul(fe.add(y1, x1), fe.add(y2, x2))
    c = fe.mul(fe.mul(t1, t2), jnp.asarray(_D2))
    d = fe.mul_small(fe.mul(z1, z2), 2)
    e, f = fe.sub(b, a), fe.sub(d, c)
    g, h = fe.add(d, c), fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def pt_add_affine(Q, aff):
    """Mixed addition with a precomputed (y+x, y-x, 2d*x*y) entry: 7 muls.

    The (1, 1, 0) entry acts as the identity, so window tables need no
    special case for digit 0.

    Operands are kept fully carried (the |limb| <= 512 invariant) between
    steps: `fe.mul`'s f32 convolution needs every column sum below 2^24,
    which the invariant guarantees (tests/test_field.py
    `test_mixed_add_interval_bounds` proves it by exact per-limb interval
    propagation).
    """
    x1, y1, z1, t1 = Q
    yplusx, yminusx, xy2d = aff
    a = fe.mul(fe.sub(y1, x1), yminusx)
    b = fe.mul(fe.add(y1, x1), yplusx)
    c = fe.mul(t1, xy2d)
    d = fe.mul_small(z1, 2)
    e, f = fe.sub(b, a), fe.sub(d, c)
    g, h = fe.add(d, c), fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def pt_dbl(Q):
    """Dedicated doubling (dbl-2008-hwcd, a=-1): 4 sqr + 4 mul."""
    x1, y1, z1, _ = Q
    a = fe.sqr(x1)
    b = fe.sqr(y1)
    c = fe.mul_small(fe.sqr(z1), 2)
    e = fe.sub(fe.sub(fe.sqr(fe.add(x1, y1)), a), b)   # 2*x*y
    g = fe.sub(b, a)          # a*A + B with a=-1
    f = fe.sub(g, c)
    h = fe.neg(fe.add(a, b))  # a*A - B
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def pt_neg(Q):
    x, y, z, t = Q
    return (fe.neg(x), y, z, fe.neg(t))


def pt_eq(Q, R) -> jnp.ndarray:
    """Projective equality mask: X1*Z2 == X2*Z1 and Y1*Z2 == Y2*Z1."""
    x1, y1, z1, _ = Q
    x2, y2, z2, _ = R
    ex = fe.eq(fe.mul(x1, z2), fe.mul(x2, z1))
    ey = fe.eq(fe.mul(y1, z2), fe.mul(y2, z1))
    return ex & ey


def pt_select(mask, Q, R):
    """Elementwise select: mask[...] ? Q : R."""
    m = mask[..., None]
    return tuple(jnp.where(m, q, r) for q, r in zip(Q, R))


def pt_on_curve(Q) -> jnp.ndarray:
    """-x^2 + y^2 == z^2 + d*t^2  and  x*y == z*t (extended-coords check)."""
    x, y, z, t = Q
    lhs = fe.sub(fe.sqr(y), fe.sqr(x))
    rhs = fe.add(fe.sqr(z), fe.mul(fe.sqr(t), jnp.asarray(_D)))
    return fe.eq(lhs, rhs) & fe.eq(fe.mul(x, y), fe.mul(z, t))


def _lt_p(b: jnp.ndarray) -> jnp.ndarray:
    """Canonical-encoding check: little-endian bytes [..., 32] < p."""
    return sc.lt_const(b, fe._P_LIMBS)


def decompress(b: jnp.ndarray) -> tuple:
    """uint8[..., 32] -> (point, ok_mask).

    Matches `crypto.pure_ed25519.pt_decode` on every input: rejects y >= p,
    non-residue x^2, and x == 0 with sign bit set.  On rejected lanes the
    returned point is garbage and must be masked by `ok`.
    """
    sign = (b[..., 31] >> 7).astype(jnp.int32)
    y_bytes = b.at[..., 31].set(b[..., 31] & 0x7F)
    ok = _lt_p(y_bytes)
    y = fe.from_bytes(y_bytes)
    y2 = fe.sqr(y)
    u = fe.sub(y2, jnp.asarray(_ONE))
    v = fe.add(fe.mul(y2, jnp.asarray(_D)), jnp.asarray(_ONE))
    v3 = fe.mul(fe.sqr(v), v)
    v7 = fe.mul(fe.sqr(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow22523(fe.mul(u, v7)))
    vx2 = fe.mul(v, fe.sqr(x))
    root1 = fe.eq(vx2, u)
    root2 = fe.eq(vx2, fe.neg(u))
    x = jnp.where(root2[..., None], fe.mul(x, jnp.asarray(_SQRT_M1)), x)
    ok = ok & (root1 | root2)
    # x == 0 (i.e. u == 0) with sign bit set is invalid
    ok = ok & ~(fe.is_zero(u) & (sign == 1))
    flip = fe.parity(x) != sign
    x = jnp.where(flip[..., None], fe.neg(x), x)
    one = jnp.broadcast_to(jnp.asarray(_ONE), y.shape)
    return (x, y, one, fe.mul(x, y)), ok


def encode(Q) -> jnp.ndarray:
    """Point -> canonical uint8[..., 32] (y with sign-of-x top bit)."""
    x, y, z, _ = Q
    zi = fe.inv(z)
    xb = fe.parity(fe.mul(x, zi))
    yb = fe.to_bytes(fe.mul(y, zi))
    return yb.at[..., 31].set(yb[..., 31] | (xb << 7).astype(jnp.uint8))


def encode_batch(Q) -> tuple:
    """Flat-batched encode: coords [N, 32] -> (uint8[N, 32], ok[N]).

    One Montgomery batch inversion (`field.batch_inv`) replaces the
    per-lane ~265-mul inversion ladder `encode` pays — ~5 muls/lane.
    This is what lets the verifier check enc([s]B + [k](-A)) == R_bytes
    instead of decompressing R per lane (~270 muls).  ok is False where
    Z == 0 (not a projective point; garbage lanes from masked failures).
    """
    x, y, z, _ = Q
    zi, nz = fe.batch_inv(z)
    xb = fe.parity(fe.mul(x, zi))
    yb = fe.to_bytes(fe.mul(y, zi))
    return (yb.at[..., 31].set(yb[..., 31] | (xb << 7).astype(jnp.uint8)),
            nz)


# --- scalar multiplication ------------------------------------------------

def _build_window_table(Q):
    """[..., 16, 32] per coordinate: T[j] = j*Q via 15 chained adds."""
    def step(acc, _):
        nxt = pt_add(acc, Q)
        return nxt, acc
    _, rows = lax.scan(step, identity(Q[0].shape[:-1]), None, length=16)
    # rows: [16, ..., 32] per coord; move table axis next to limbs
    return tuple(jnp.moveaxis(r, 0, -2) for r in rows)


def scalar_mul(s: jnp.ndarray, Q) -> tuple:
    """[s]Q for s = little-endian bytes/limbs [..., 32]; 4-bit windows.

    256 doublings + 64 table adds + 15 setup adds, all under lax.scan so the
    traced graph stays O(one window body).
    """
    tbl = _build_window_table(Q)
    wins = sc.nibbles(s)                       # [..., 64] LSB-first
    wins_t = jnp.moveaxis(wins, -1, 0)[::-1]   # [64, ...] MSB-first

    def body(acc, w):
        acc = lax.fori_loop(0, 4, lambda _, p: pt_dbl(p), acc)
        sel = tuple(
            jnp.take_along_axis(t, w[..., None, None], axis=-2)[..., 0, :]
            for t in tbl)
        return pt_add(acc, sel), None

    acc, _ = lax.scan(body, identity(Q[0].shape[:-1]), wins_t)
    return acc


COMB_WBITS = 10                       # per-validator comb window width
COMB_WINDOWS = -(-256 // COMB_WBITS)  # 26 windows cover 256 bits
COMB_DIGITS = 1 << COMB_WBITS


def _comb_row(Q) -> tuple:
    """One window's digit rows j*Q for j in [0, 1024): a 256-step add scan
    builds digits < 256, then three WIDE adds of 256Q/512Q/768Q extend to
    1024 (not a 1024-step scan).  Coords [1024, ..., V, 32] per coord."""
    def add_step(acc, _):
        nxt = pt_add(acc, Q)
        return nxt, acc
    p256, row_lo = lax.scan(add_step, identity(Q[0].shape[:-1]), None,
                            length=256)
    p256w = tuple(jnp.broadcast_to(c, q.shape)
                  for c, q in zip(p256, row_lo))

    def quarter_step(q, _):             # j0 + 256, j0 + 512, j0 + 768
        nxt = pt_add(q, p256w)
        return nxt, nxt

    _, rest = lax.scan(quarter_step, row_lo, None, length=3)
    return tuple(
        jnp.concatenate(
            [row_lo[i], rest[i].reshape((-1,) + rest[i].shape[2:])], axis=0)
        for i in range(4))


def build_affine_comb(Q) -> tuple:
    """Per-point 10-bit comb tables, built ON DEVICE as packed affine.

    Q: point with coords [..., V, 32] (V points, e.g. one per validator).
    Returns (packed uint8[26, 1024, V, 3, 32], ok bool[V]) where entry
    [w, j, v] = (y+x, y-x, 2d*x*y) of j * 2^(10w) * Q_v in canonical
    bytes — so [k]Q needs 26 gathered mixed adds (`pt_add_affine`,
    7 muls) and ZERO doublings; uint8 storage quarters the hot loop's
    gather traffic, and the (1, 1, 0) identity entries make digit 0 a
    no-op.  10-bit windows trade 4x table memory for 6 fewer adds per
    lane vs an 8-bit comb.

    Why fused: per window the extended row converts to affine bytes
    INSIDE the scan body (one Montgomery batch inversion per window), so
    only the uint8 output and one extended row ever live on device — a
    two-phase build materializes all 26 windows in int32 extended
    coordinates (~1.7 GB at V=128) plus inversion temporaries, which
    OOMs a 16 GB chip.

    What a window carries to the next is its BASE POINT 2^(10w) * Q_v
    (V points), not its row: each row is built from its base by adds
    (`_comb_row`: one add an entry), where shifting a whole row up a
    window is ten doublings an entry and most of a build (on a v5e
    5.53 s at V bucket 128 and 30.6 at 512, against 1.46 and 5.75;
    PERF.md §6, PR 39).  Canonical affine bytes do not depend on which
    projective form reached them, so either way gives the same table
    byte for byte.  Fast-sync then amortizes the build over thousands
    of commits against the same set.
    """
    def window_step(base, _):
        packed, ok = _affine_pack(_comb_row(base))
        # x1024 = the next window's base; fori keeps ONE doubling body
        # in the graph (10 inline copies of the 12-mul dbl are ten times
        # its share of the build's XLA compile)
        nxt = lax.fori_loop(0, COMB_WBITS, lambda _, p: pt_dbl(p), base)
        return nxt, (packed, ok)

    _, (tbl, oks) = lax.scan(window_step, Q, None, length=COMB_WINDOWS)
    return tbl, jnp.all(oks, axis=(0, 1))


def _affine_pack(row) -> tuple:
    """One window's extended coords [1024, ..., V, 32] -> packed affine
    uint8[1024, ..., V, 3, 32] + per-entry nonzero mask.  One batch
    inversion normalizes the whole window; Z == 0 lanes (garbage chains
    from an invalid input point) are flagged False."""
    x, y, z, _ = row
    shape = z.shape
    zi, nz = fe.batch_inv(z.reshape(-1, fe.NLIMBS))
    zi = zi.reshape(shape)
    xa, ya = fe.mul(x, zi), fe.mul(y, zi)
    packed = jnp.stack([
        fe.to_bytes(fe.add(ya, xa)),
        fe.to_bytes(fe.sub(ya, xa)),
        fe.to_bytes(fe.mul(fe.mul(xa, ya), jnp.asarray(_D2))),
    ], axis=-2)
    return packed, nz.reshape(shape[:-1])


# Static layout for 10-bit digit extraction: window w covers bits
# [10w, 10w+10) — always two bytes (offset 0/2/4/6); the top window has
# only 6 real bits (masked hi byte).
_D10_LO = np.array([(COMB_WBITS * w) // 8 for w in range(COMB_WINDOWS)])
_D10_SH = np.array([(COMB_WBITS * w) % 8 for w in range(COMB_WINDOWS)])
_D10_HI = np.minimum(_D10_LO + 1, fe.NLIMBS - 1)
_D10_HI_OK = (_D10_LO + 1 <= fe.NLIMBS - 1).astype(np.int32)


def digits10(s: jnp.ndarray) -> jnp.ndarray:
    """Bytes/limbs [..., 32] -> 26 little-endian 10-bit digits [..., 26]."""
    x = s.astype(jnp.int32)
    lo = jnp.take(x, jnp.asarray(_D10_LO), axis=-1)
    hi = jnp.take(x, jnp.asarray(_D10_HI), axis=-1) * jnp.asarray(_D10_HI_OK)
    sh = jnp.asarray(_D10_SH)
    return ((lo >> sh) | (hi << (8 - sh))) & (COMB_DIGITS - 1)


def scalar_mul_comb(tbl: jnp.ndarray, val_idx: jnp.ndarray,
                    s: jnp.ndarray) -> tuple:
    """[s] * Q_{val_idx} from packed affine comb tables.

    tbl: `build_affine_comb` output uint8[26, 1024, V, 3, 32];
    val_idx int32 [N]; s bytes/limbs [N, 32] -> point coords [N, 32].
    26 gathered mixed adds, no doublings: ~182 field muls per lane vs
    ~2760 for the cold variable-base ladder in `scalar_mul`.
    """
    V = tbl.shape[2]
    digits = jnp.moveaxis(digits10(s), -1, 0)           # [26, N]

    def body(acc, xs):
        digit, tw = xs                   # tw: [1024, V, 3, 32] uint8
        flat = tw.reshape(COMB_DIGITS * V, 3, fe.NLIMBS)
        sel = jnp.take(flat, digit * V + val_idx, axis=0).astype(jnp.int32)
        aff = (sel[..., 0, :], sel[..., 1, :], sel[..., 2, :])
        return pt_add_affine(acc, aff), None

    acc, _ = lax.scan(body, identity(s.shape[:-1]), (digits, tbl))
    return acc


BASE_WBITS = 12                      # fixed-base comb window width
BASE_WINDOWS = -(-256 // BASE_WBITS)  # 22 windows cover 256 bits


@functools.lru_cache(maxsize=None)
def _base_table() -> np.ndarray:
    """np.uint8[22, 4096, 3, 32]: window w, digit j -> affine precomp of
    j * 2^(12w) * B as (y+x, y-x, 2d*x*y) canonical byte rows.

    12-bit windows: 22 mixed adds per [s]B instead of
    the 8-bit comb's 32 — the ~8.6 MB table stays device-resident.  Built
    once host-side from the golden bigint reference (~90k bigint adds,
    well under a second) and lru-cached for the process.
    """
    nwin, ndig = BASE_WINDOWS, 1 << BASE_WBITS
    pts = []
    P = ref.BASE
    for w in range(nwin):
        acc = ref.IDENT
        for _ in range(ndig):
            pts.append(acc)
            acc = ref.pt_add(acc, P)
        P = acc  # acc == 2^BASE_WBITS * P == 2^(12(w+1)) * B
    # Montgomery batch inversion: one modexp for all Z coordinates.
    prefix, run = [], 1
    for p in pts:
        prefix.append(run)
        run = run * p[2] % ref.P
    run_inv = pow(run, ref.P - 2, ref.P)
    tbl = np.zeros((nwin, ndig, 3, fe.NLIMBS), dtype=np.uint8)
    for idx in range(len(pts) - 1, -1, -1):
        x, y, z, _ = pts[idx]
        zi = run_inv * prefix[idx] % ref.P
        run_inv = run_inv * z % ref.P
        xa, ya = x * zi % ref.P, y * zi % ref.P
        w, j = divmod(idx, ndig)
        tbl[w, j, 0] = fe.int_to_limbs((ya + xa) % ref.P)
        tbl[w, j, 1] = fe.int_to_limbs((ya - xa) % ref.P)
        tbl[w, j, 2] = fe.int_to_limbs(2 * fe.D * xa * ya % ref.P)
    return tbl


# Static per-window byte/shift layout for 12-bit digit extraction: window
# w covers bits [12w, 12w+12), i.e. bytes lo=3w//2 (shifted by 0 or 4)
# and lo+1; the top window only has 4 real bits (masked hi byte).
_D12_LO = np.array([(12 * w) // 8 for w in range(BASE_WINDOWS)])
_D12_ODD = np.array([(12 * w) % 8 == 4 for w in range(BASE_WINDOWS)])
_D12_HI = np.minimum(_D12_LO + 1, fe.NLIMBS - 1)
_D12_HI_OK = (_D12_LO + 1 <= fe.NLIMBS - 1).astype(np.int32)


def digits12(s: jnp.ndarray) -> jnp.ndarray:
    """Bytes/limbs [..., 32] -> 22 little-endian 12-bit digits [..., 22]."""
    x = s.astype(jnp.int32)
    lo = jnp.take(x, jnp.asarray(_D12_LO), axis=-1)
    hi = jnp.take(x, jnp.asarray(_D12_HI), axis=-1) * jnp.asarray(_D12_HI_OK)
    even = lo + ((hi & 0xF) << 8)
    odd = (lo >> 4) + (hi << 4)
    return jnp.where(jnp.asarray(_D12_ODD), odd, even)


def scalar_mul_base(s: jnp.ndarray, tbl: jnp.ndarray | None = None) -> tuple:
    """[s]B via the 12-bit fixed-base comb: 22 mixed adds, zero doublings.

    Pass the table (`_base_table()` uploaded once) as `tbl` from jitted
    entry points: baked in as a graph literal the 8.6 MB constant adds
    seconds of XLA compile to every executable that carries it."""
    if tbl is None:
        tbl = jnp.asarray(_base_table())       # [22, 4096, 3, 32]
    digits = jnp.moveaxis(digits12(s), -1, 0)  # [22, ...]

    def body(acc, xs):
        digit, tblw = xs
        sel = jnp.take(tblw, digit, axis=0).astype(jnp.int32)  # [..., 3, 32]
        aff = (sel[..., 0, :], sel[..., 1, :], sel[..., 2, :])
        return pt_add_affine(acc, aff), None

    acc, _ = lax.scan(body, identity(s.shape[:-1]), (digits, tbl))
    return acc


