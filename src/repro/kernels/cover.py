"""Pallas masked earliest-cover reduction (the ``max_b min_r`` kernel).

The vectorized cluster backends spend their inner loop on one reduction:
mask a padded ``(B_pad, r_pad)`` replica grid to the candidate's (B, r) and
take the earliest-cover time ``max_b min_r`` (`repro.core.simulator
.gang_cover_times`).  XLA fuses the two reductions well on CPU; this module
carries the fused Pallas formulation so the masked mask+min+max runs as one
VMEM pass per rep tile on TPU, plus the measurement hook that decides
whether routing the frontier kernel through it is worth it on the current
backend.

Measurement (recorded by ``bench_masked_cover``): on the CPU the kernel
runs only under ``interpret=True``, where it loses to the XLA fusion --
~10x at 16k reps and ~60x at 64k reps x (16, 16) grids (interpret overhead
scales with the grid).  Compiled on one TPU v5e chip it equals the fusion
and is still slower, ~1.6x at (16384, 16, 16) with (B, r) = (8, 2).  So
:func:`repro.cluster.vectorized` keeps the jnp path unless
``REPRO_PALLAS_COVER=1`` is set *and* a TPU backend is present.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["masked_cover_times", "bench_masked_cover", "pallas_cover_wins"]


def _kernel(b_ref, r_ref, d_ref, o_ref):
    d = d_ref[...]  # (rows, B_pad, r_pad)
    b, r = b_ref[0], r_ref[0]  # scalar-prefetched candidate (SMEM)
    masked = jnp.where(jax.lax.broadcasted_iota(jnp.int32, d.shape, 2) < r, d, jnp.inf)
    t_batch = jnp.min(masked, axis=-1)  # (rows, B_pad)
    b_mask = jax.lax.broadcasted_iota(jnp.int32, t_batch.shape, 1) < b
    t_batch = jnp.where(b_mask, t_batch, -jnp.inf)
    o_ref[...] = jnp.max(t_batch, axis=-1, keepdims=True)  # (rows, 1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def masked_cover_times(
    draws: jax.Array,  # (reps, B_pad, r_pad) replica durations
    n_batches: jax.Array,  # scalar B (traced ok)
    replication: jax.Array,  # scalar r
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused masked ``max_b min_r`` over a padded replica grid.

    Semantically identical to ``gang_cover_times(draws, n_batches,
    replication)``; one VMEM pass per ``block_rows`` tile of reps.  B and r
    ride in as scalar-prefetch operands (SMEM), and the output is a
    ``(reps, 1)`` column so its block keeps the TPU's 2-D tiling.  Pass
    ``interpret=True`` to run the kernel body off-TPU (differential tests).
    """
    reps, b_pad, r_pad = draws.shape
    br = min(block_rows, max(reps, 1))
    pad = (-reps) % br
    if pad:
        draws = jnp.pad(draws, ((0, pad), (0, 0), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((reps + pad) // br,),
            in_specs=[pl.BlockSpec((br, b_pad, r_pad), lambda i, b, r: (i, 0, 0))],
            out_specs=pl.BlockSpec((br, 1), lambda i, b, r: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((reps + pad, 1), draws.dtype),
        interpret=interpret,
    )(
        jnp.asarray(n_batches, jnp.int32).reshape(1),
        jnp.asarray(replication, jnp.int32).reshape(1),
        draws,
    )
    return out[:reps, 0]


def pallas_cover_wins() -> bool:
    """Should the frontier kernel route through the Pallas cover reduction?

    Only when a TPU backend can compile it natively -- interpret mode on
    CPU loses to the XLA fusion by orders of magnitude (see module note).
    """
    import os

    if os.environ.get("REPRO_PALLAS_COVER") != "1":
        return False
    return jax.default_backend() == "tpu"


def bench_masked_cover(reps: int = 4096, b_pad: int = 8, r_pad: int = 8, iters: int = 5):
    """Wall-clock the Pallas cover kernel against the XLA jnp fusion.

    Returns ``{"pallas_seconds", "jnp_seconds", "pallas_wins"}`` -- the
    measurement the tentpole asked for, runnable on any backend (interpret
    mode off-TPU).
    """
    from ..core.simulator import gang_cover_times

    key = jax.random.key(0)
    draws = jax.random.exponential(key, (reps, b_pad, r_pad))
    b = jnp.asarray(b_pad // 2, jnp.int32)
    r = jnp.asarray(r_pad // 2, jnp.int32)
    interpret = jax.default_backend() != "tpu"
    oracle = jax.jit(gang_cover_times)

    jax.block_until_ready(masked_cover_times(draws, b, r, interpret=interpret))
    jax.block_until_ready(oracle(draws, b, r))
    t_pallas, t_jnp = [], []
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(masked_cover_times(draws, b, r, interpret=interpret))
        t_pallas.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(oracle(draws, b, r))
        t_jnp.append(time.time() - t0)
    out = {
        "pallas_seconds": float(np.min(t_pallas)),
        "jnp_seconds": float(np.min(t_jnp)),
        "interpret": interpret,
    }
    out["pallas_wins"] = out["pallas_seconds"] < out["jnp_seconds"]
    return out
