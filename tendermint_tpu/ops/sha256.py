"""Batched SHA-256 on TPU (uint32 lanes, static shapes).

Replaces the reference's scalar Merkle/part hashing (reference
`types/part_set.go:32-41`, `types/tx.go:29-43` — RIPEMD-160 in that era; this
framework standardizes on SHA-256, see `tendermint_tpu.types.merkle`).
Message length must be static; the whole batch is hashed in lockstep, one
compression round loop shared across the batch — exactly the shape the VPU
wants.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2], dtype=np.uint32)

_H0 = np.array([0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19], dtype=np.uint32)


def _rotr(x, n):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def pad(nbytes: int) -> np.ndarray:
    """The static SHA-256 padding suffix for an nbytes message (uint8[...])."""
    padlen = (56 - (nbytes + 1)) % 64
    tail = np.zeros(1 + padlen + 8, dtype=np.uint8)
    tail[0] = 0x80
    bits = nbytes * 8
    for i in range(8):
        tail[-1 - i] = (bits >> (8 * i)) & 0xFF
    return tail


_UNROLL = 16      # rounds per scan step: graph size vs carry traffic knob


def _compress(state, w16):
    """One compression: lax.scan over round groups, _UNROLL rounds
    unrolled per step, with the message schedule as a ROLLING 16-word
    window in the carry.

    The window trick removes the [..., 64] schedule array and its
    per-round dynamic indexing along the vector lane dim (the original
    HBM-bound formulation); the partial unroll keeps the traced graph
    small enough for XLA's CPU backend to compile in seconds (a fully
    unrolled 64-round body took minutes of LLVM time) while the carry
    (8 state + 16 window words) round-trips only once per 16 rounds.
    At round i the window holds w[i..i+15]: consume window[0], generate
    w[i+16] = w[i] + s0(w[i+1]) + w[i+9] + s1(w[i+14]), shift.
    """
    ks = jnp.asarray(_K.reshape(64 // _UNROLL, _UNROLL))

    def step(carry, k):
        a, b, c, d, e, f, g, h = carry[:8]
        w = list(carry[8:])
        for j in range(_UNROLL):
            wi = w[0]
            ws0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ (w[1] >> np.uint32(3))
            ws1 = (_rotr(w[14], 17) ^ _rotr(w[14], 19)
                   ^ (w[14] >> np.uint32(10)))
            w = w[1:] + [w[0] + ws0 + w[9] + ws1]
            s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + k[j] + wi
            s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            a, b, c, d, e, f, g, h = (t1 + s0 + maj, a, b, c,
                                      d + t1, e, f, g)
        return (a, b, c, d, e, f, g, h) + tuple(w), None

    init = tuple(state) + tuple(w16[..., i] for i in range(16))
    out, _ = jax.lax.scan(step, init, ks)
    return tuple(s + n for s, n in zip(state, out[:8]))


# Messages longer than this many 64-byte blocks scan over blocks instead
# of unrolling them: a 64 KiB block part is 1,025 blocks, and unrolled
# that is 1,025 copies of the compression scan for XLA to compile (the
# CPU backend was still lowering it after 10 minutes).  Short messages
# (tree nodes, tx leaves: 1-3 blocks) keep the unrolled form.
_SCAN_BLOCKS = 8


def sha256_blocks(blocks: jnp.ndarray) -> jnp.ndarray:
    """Hash pre-padded big-endian words uint32[B, nblocks, 16] -> uint32[B, 8]."""
    nblocks = blocks.shape[-2]
    state = tuple(jnp.broadcast_to(jnp.uint32(h), blocks.shape[:-2])
                  for h in _H0)
    if nblocks <= _SCAN_BLOCKS:
        for i in range(nblocks):
            state = _compress(state, blocks[..., i, :])
    else:
        state, _ = jax.lax.scan(
            lambda st, w16: (_compress(st, w16), None), state,
            jnp.moveaxis(blocks, -2, 0))
    return jnp.stack(state, axis=-1)


def bytes_to_words(msg: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., 64*n] -> big-endian uint32[..., n, 16]."""
    n = msg.shape[-1] // 64
    b = msg.reshape(msg.shape[:-1] + (n, 16, 4)).astype(jnp.uint32)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


def words_to_bytes(w: jnp.ndarray) -> jnp.ndarray:
    """Big-endian uint32[..., 8] -> uint8[..., 32]."""
    parts = [(w >> np.uint32(s)).astype(jnp.uint8) for s in (24, 16, 8, 0)]
    return jnp.stack(parts, axis=-1).reshape(w.shape[:-1] + (32,))


def sha256(msg: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., N] (N static) -> digest uint8[..., 32]."""
    n = msg.shape[-1]
    tail = jnp.broadcast_to(jnp.asarray(pad(n)), msg.shape[:-1] + (len(pad(n)),))
    padded = jnp.concatenate([msg, tail], axis=-1)
    return words_to_bytes(sha256_blocks(bytes_to_words(padded)))
