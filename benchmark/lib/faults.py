"""Faults put UNDER the timed path, to show that the check can fail.

Used by the control (`benchmark/control_run.py`, on the chip) and by the
tests of the benchmark; never by `run.py`.  Each breaks one guarantee
the configurations state, the way a too-eager optimisation would.
"""

from __future__ import annotations

FAULTS = ("accept_all",)


def install(name: str) -> None:
    if name == "accept_all":
        # "every commit's +2/3 verified on the device before a block is
        # applied": the kernel runs, its verdicts are thrown away and
        # every lane is reported valid.  A sound chain still syncs to the
        # right hashes; only the verdict control can tell.
        import jax.numpy as jnp
        from tendermint_tpu.ops import ed25519 as dev
        real = dev.verify_grouped_templated_jit

        def accept_all(*args):
            return jnp.ones_like(real(*args))

        dev.verify_grouped_templated_jit = accept_all
        return
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
