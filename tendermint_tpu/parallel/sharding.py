"""Multi-chip sharding of the crypto plane over a jax.sharding.Mesh.

The reference scales by gossiping to more peers over TCP (`p2p/`); the
TPU framework scales the *verification grid* instead: batches of
(pubkey, sign-bytes, signature, power) tuples are sharded across devices
on a 1-D mesh, each chip verifies its shard with the batch kernel, and
the voting-power tally reduces over ICI (XLA inserts the psum from the
sharding annotations — the scaling-book recipe: pick a mesh, annotate,
let the compiler place collectives).

Works identically on a real TPU pod slice and on the CPU backend with
`--xla_force_host_platform_device_count=N` (how the test suite and the
driver's dry-run exercise multi-chip paths without hardware).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.ops import ed25519 as _ed
from tendermint_tpu.ops import merkle as _merkle

# -- per-device utilization bookkeeping --------------------------------------
# A 1-D mesh splits lanes evenly, so one sharded call marks every mesh
# device busy for the call's duration; utilization is accumulated busy
# time over elapsed time since the first sharded call.  Device-LEVEL
# imbalance (one slow chip) shows up in an XPlane capture, not here —
# this answers the cheaper always-on question "are the extra chips
# earning their keep at all".
_usage_lock = threading.Lock()
_usage_busy: dict[str, float] = {}
_usage_t0: float | None = None


def device_label(d) -> str:
    return f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"


def note_sharded_call(mesh: Mesh, dur_s: float, lanes: int) -> None:
    """Fold one sharded verify call into the per-device utilization
    gauges (`tendermint_device_util{device=...}`) and lane counters."""
    from tendermint_tpu.utils.metrics import REGISTRY
    global _usage_t0
    devs = list(mesh.devices.flat)
    if not devs:
        return
    per_dev = lanes // len(devs)
    now = time.perf_counter()
    with _usage_lock:
        if _usage_t0 is None:
            _usage_t0 = now - max(dur_s, 1e-9)
        elapsed = max(now - _usage_t0, 1e-9)
        for d in devs:
            label = device_label(d)
            _usage_busy[label] = _usage_busy.get(label, 0.0) + dur_s
            REGISTRY.device_util.labels(label).set(
                min(1.0, _usage_busy[label] / elapsed))
            REGISTRY.device_lanes.labels(label).inc(per_dev)


def make_mesh(n_devices: int | None = None, axis: str = "batch",
              platform: str | None = None) -> Mesh:
    """1-D device mesh.  `platform` pins a backend (e.g. "cpu" for the
    virtual-device dry run under --xla_force_host_platform_device_count);
    default: the default platform, erroring rather than silently falling
    back when it has too few devices."""
    devs = jax.devices(platform) if platform else jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(
            f"need {n} devices, have {len(devs)}"
            + ("" if platform else
               ' (pass platform="cpu" for a virtual mesh under '
               "--xla_force_host_platform_device_count)"))
    return Mesh(np.array(devs[:n]), (axis,))


def verify_tally(pubkeys, msgs, sigs, powers):
    """Batch-verify and tally voting power of the valid lanes.

    Under a sharded jit, the elementwise verify stays local to each chip
    and the sum lowers to an all-reduce over ICI.
    """
    ok = _ed.verify(pubkeys, msgs, sigs)
    tallied = jnp.sum(jnp.where(ok, powers, 0))
    return ok, tallied


def sharded_verify_fn(mesh: Mesh, msg_len: int, axis: str = "batch"):
    """jitted verify_tally with batch-dim sharding over `mesh`.

    Returns fn(pubkeys[N,32], msgs[N,msg_len], sigs[N,64], powers[N])
    -> (ok[N] bool, tallied int64); N must divide by mesh size.
    """
    shard = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    return jax.jit(
        verify_tally,
        in_shardings=(shard, shard, shard, shard),
        out_shardings=(shard, replicated))


def sharded_merkle_fn(mesh: Mesh, axis: str = "batch"):
    """jitted per-tree merkle roots, trees sharded across the mesh.

    fn(leaves[B, n, L]) -> roots[B, 32], B divisible by mesh size.
    """
    shard = NamedSharding(mesh, P(axis))
    return jax.jit(_merkle.roots, in_shardings=(shard,),
                   out_shardings=shard)


def training_step_fn(mesh: Mesh, msg_len: int, axis: str = "batch"):
    """The framework's full 'training step' analog: one fused device step
    of fast-sync replay — verify a grid of commit signatures, tally power
    per block, and recompute the blocks' merkle data roots.

    fn(pubkeys[B,V,32], msgs[B,V,msg_len], sigs[B,V,64], powers[B,V],
       leaves[B,T,L])
      -> (block_ok[B] bool, tallied[B] int64, roots[B,32])
    with the block dim sharded across the mesh: dp-style grid sharding,
    collective-free per block, ICI only for the final gather.
    """
    shard = NamedSharding(mesh, P(axis))

    def step(pubkeys, msgs, sigs, powers, leaves, total_power):
        ok = _ed.verify(pubkeys, msgs, sigs)          # [B, V]
        tallied = jnp.sum(jnp.where(ok, powers, 0), axis=-1)   # [B]
        sig_ok = jnp.all(ok | (powers == 0), axis=-1)
        block_ok = sig_ok & (tallied * 3 > total_power * 2)
        roots = _merkle.roots(leaves)                  # [B, 32]
        return block_ok, tallied, roots

    return jax.jit(
        step,
        in_shardings=(shard, shard, shard, shard, shard, None),
        out_shardings=(shard, shard, shard))


def sharded_grouped_verify_fn(mesh: Mesh, axis: str = "batch"):
    """Grouped verify over a mesh: lanes sharded, comb tables replicated.

    The table for a validator set is identical on every chip (the fixed
    keys), so only the (val_idx, pubkeys, msgs, sigs) lanes split across
    the mesh — each chip runs the 26-add comb path on its shard with NO
    collectives in the hot loop (the bool gather at the end rides ICI).
    Tables arrive as ARGUMENTS (already replicated/committed at build
    time by the backend) so one jitted fn per shape serves every
    validator set, and the fixed-base comb table rides as a replicated
    argument too (baked in as a graph constant the 8.6 MB literal adds
    ~5s of XLA compile per executable).  This is how
    `crypto.backend.TpuBackend` scales the verification grid when more
    than one device is visible — the framework's analog of the reference
    scaling by gossiping to more peers.

    The kernel runs under `shard_map`, NOT a GSPMD-partitioned jit: the
    device body is the plain single-device `verify_grouped` over the
    local lane shard.  This is load-bearing for correctness, not a
    style choice — `curve.encode_batch`'s Montgomery batch inversion
    chains a prefix product ACROSS lanes, and letting the partitioner
    slice that sequential chain over the mesh produced wrong inverses
    (every lane read as False).  Per shard the amortization math is
    unchanged (batch inversion is valid over any lane subset), so each
    chip runs the whole kernel locally and only the output gather
    touches ICI.
    """
    fn = jax.shard_map(
        _ed.verify_grouped, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=P(axis), check_vma=False)
    return jax.jit(fn)
