"""Pallas TPU fused RMSNorm kernel.

One HBM pass per row tile: mean-square, rsqrt and scale are fused in VMEM
(the unfused jnp version reads x twice and materializes the normalized
intermediate in HBM).  Rows tile over a parallel grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps: float, plus_one: bool):
    x = x_ref[...].astype(jnp.float32)  # (rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    o_ref[...] = (y * w).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "plus_one", "block_rows", "interpret")
)
def rms_norm_fused(
    x: jax.Array,  # (..., d)
    weight: jax.Array,  # (d,)
    eps: float = 1e-6,
    plus_one: bool = False,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    br = min(block_rows, n)
    pad = (-n) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = ((n + pad) // br,)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, plus_one=plus_one),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, weight)
    return out[:n].reshape(orig_shape)
