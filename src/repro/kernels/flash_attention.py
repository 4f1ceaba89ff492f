"""Pallas TPU flash-attention forward kernel (causal / sliding-window, GQA).

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * tiling targets VMEM, not shared memory: BlockSpecs stage (bq, hd) query
    tiles and (bk, hd) KV tiles HBM->VMEM; hd (128/256) and the 128-multiple
    block sizes keep the MXU systolic array fully fed;
  * the softmax running max/sum lives in fp32 VMEM scratch across the
    "arbitrary" (sequential) KV grid dimension -- the TPU analogue of keeping
    the accumulator in registers across the SM inner loop;
  * fully-masked KV tiles are skipped with ``pl.when`` predication (the
    block-causal skip), which on TPU removes both the MXU work and the HBM
    reads for those tiles.

Grid: (batch, q_heads, Sq/bq, Sk/bk), last dim sequential.
Layout: (B, H, S, hd) -- ops.py transposes from the model's (B, S, H, hd).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min / 2)


def _kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,  # output tile
    m_ref, l_ref, acc_ref,  # fp32 scratch
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    seq_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # block-level relevance: skip tiles that are entirely masked out
    relevant = True
    if causal:
        relevant = q_start + block_q - 1 >= k_start  # some i >= j in tile
    if window is not None:
        relevant = jnp.logical_and(
            relevant, q_start - (k_start + block_k - 1) < window
        )

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k  # padding tail
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / lsum[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KH, Sk, hd)
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, hd = q.shape
    kh, sk = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (sq + pad_q) // bq
    nk = (sk + pad_k) // bk
    g = h // kh  # query heads per kv head

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=bq,
        block_k=bk,
        seq_k=sk,
    )
    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda ib, ih, iq, ik: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
