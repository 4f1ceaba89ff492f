"""Decoder / encoder / MoE / VLM transformer with scan-over-layers.

Tensor-parallel head layout
---------------------------
To shard attention over a TP axis of size ``pad_heads_to`` we use the
standard TP-GQA construction: KV heads are *repeated* ``R = K_pad/K`` times
(exact semantics, redundant storage -- the repeated copies shard over the
axis), and query heads are laid out kv-copy-major with per-copy group size
``G_pad = ceil(G/R)``; slots beyond the true head count are masked to zero so
the math is bit-identical to the unpadded model.  ``HeadLayout`` centralizes
this.  With ``pad_heads_to=0`` (smoke tests) everything degenerates to plain
GQA.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..distributed.axes import shard
from .common import cast_for_compute, cross_entropy_loss, dense_init
from .layers import (
    apply_mrope,
    apply_rope,
    flash_attention,
    gated_mlp,
    init_gated_mlp,
    init_mlp,
    layer_norm,
    mlp,
    rms_norm,
)
from .moe import init_moe, moe_ffn

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# head layout for TP sharding
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadLayout:
    n_heads: int  # true H
    n_kv: int  # true K
    repeat: int  # R: kv repetition factor
    g_pad: int  # query slots per repeated kv head
    h_pad: int  # K_pad * g_pad total query slots

    @property
    def k_pad(self) -> int:
        return self.n_kv * self.repeat

    @staticmethod
    def make(n_heads: int, n_kv: int, pad_to: int = 0) -> "HeadLayout":
        g = n_heads // n_kv
        if pad_to <= 0:
            return HeadLayout(n_heads, n_kv, 1, g, n_heads)
        # repeat kv so K_pad = lcm(K, pad_to) is shardable over the TP axis
        r = math.lcm(n_kv, pad_to) // n_kv
        k_pad = n_kv * r
        g_pad = math.ceil(g / r)
        # ensure total query slots divisible by pad_to
        while (k_pad * g_pad) % pad_to:
            g_pad += 1
        return HeadLayout(n_heads, n_kv, r, g_pad, k_pad * g_pad)

    def head_mask(self) -> jax.Array:
        """(H_pad,) float mask: 1 for real query slots, 0 for padding.

        Slot h = (t*R + c) * G_pad + g is real iff c*G_pad + g < G (true group
        size) -- q heads of true kv t are packed across its R copies.
        """
        g_true = self.n_heads // self.n_kv
        idx = jnp.arange(self.h_pad)
        kc = idx // self.g_pad  # repeated-kv index
        g = idx % self.g_pad
        c = kc % self.repeat
        return (c * self.g_pad + g < g_true).astype(jnp.float32)


def repeat_kv(x: jax.Array, r: int) -> jax.Array:
    """(B,S,K,hd) -> (B,S,K*r,hd) with contiguous copies per true head."""
    if r == 1:
        return x
    return jnp.repeat(x, r, axis=2)


# --------------------------------------------------------------------------
# attention layer
# --------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, layout: HeadLayout, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, layout.h_pad * hd), d, dtype),
        "wk": dense_init(ks[1], (d, layout.n_kv * hd), d, dtype),
        "wv": dense_init(ks[2], (d, layout.n_kv * hd), d, dtype),
        "wo": dense_init(ks[3], (layout.h_pad * hd, d), layout.n_heads * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((layout.h_pad * hd,), dtype)
        p["bk"] = jnp.zeros((layout.n_kv * hd,), dtype)
        p["bv"] = jnp.zeros((layout.n_kv * hd,), dtype)
    return p


def attention_apply(
    p: Params,
    cfg: ArchConfig,
    layout: HeadLayout,
    x: jax.Array,  # (B,S,d)
    positions: jax.Array,  # (B,S) int32
    mrope_positions: Optional[jax.Array] = None,  # (B,S,3) for vlm
    cache: Optional[Params] = None,  # {"k","v": (B,W,K_pad,hd), "pos": (W,)}
    window: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q.reshape(b, s, layout.h_pad, hd), "batch", None, "model", None)
    k = k.reshape(b, s, layout.n_kv, hd)
    v = v.reshape(b, s, layout.n_kv, hd)
    if mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None and "ks" in cache:
        # sequence-sharded TRUE-KV cache mode (no xR head repetition)
        if s == 1:  # decode: shard_map partial-softmax combine
            t = positions[0, 0]
            o, new_cache = _seq_sharded_decode(cfg, layout, q, k, v, cache, t)
            if layout.h_pad != layout.n_heads:
                o = o * layout.head_mask()[None, None, :, None].astype(o.dtype)
            out = o.reshape(b, s, layout.h_pad * hd) @ p["wo"]
            return shard(out, "batch", "residual", None), new_cache
        # prefill: write the true-KV ring; attend over the activations (the
        # empty-cache contents are exactly k/v, so this is equivalent)
        w = cache["ks"].shape[1]
        keep = min(s, w)
        pos_tail = positions[0, s - keep :]
        slots = pos_tail % w
        new_cache = {
            "ks": cache["ks"].at[:, slots].set(k[:, s - keep :]),
            "vs": cache["vs"].at[:, slots].set(v[:, s - keep :]),
            "poss": cache["poss"].at[slots].set(pos_tail.astype(jnp.int32)),
        }
        o = flash_attention(
            q, k, v, positions, positions,
            causal=cfg.is_causal, window=window, block_k=cfg.attn_block_k,
        )
        if layout.h_pad != layout.n_heads:
            o = o * layout.head_mask()[None, None, :, None].astype(o.dtype)
        out = o.reshape(b, s, layout.h_pad * hd) @ p["wo"]
        return shard(out, "batch", "residual", None), new_cache

    k = shard(repeat_kv(k, layout.repeat), "batch", None, "model", None)
    v = shard(repeat_kv(v, layout.repeat), "batch", None, "model", None)

    if cache is not None:
        w = cache["k"].shape[1]
        if s == 1:  # decode: ring-buffer write at t % W
            t = positions[0, 0]
            slot = t % w
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], t[None].astype(jnp.int32), slot, axis=0
            )
        else:  # prefill: write the last W positions (slots form a permutation)
            keep = min(s, w)
            src_k, src_v = k[:, s - keep :], v[:, s - keep :]
            pos_tail = positions[0, s - keep :]
            slots = pos_tail % w
            ck = cache["k"].at[:, slots].set(src_k)
            cv = cache["v"].at[:, slots].set(src_v)
            cpos = cache["pos"].at[slots].set(pos_tail.astype(jnp.int32))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        k_att, v_att = ck, cv
        kv_pos = jnp.broadcast_to(cpos[None, :], (b, w))
    else:
        k_att, v_att = k, v
        kv_pos = positions

    o = flash_attention(
        q,
        k_att,
        v_att,
        positions,
        kv_pos,
        causal=cfg.is_causal,
        window=window,
        block_k=cfg.attn_block_k,
    )
    if layout.h_pad != layout.n_heads:
        o = o * layout.head_mask()[None, None, :, None].astype(o.dtype)
    out = o.reshape(b, s, layout.h_pad * hd) @ p["wo"]
    return shard(out, "batch", "residual", None), new_cache


# --------------------------------------------------------------------------
# sequence-sharded KV decode (shard_map partial-softmax combine)
# --------------------------------------------------------------------------


def _seq_sharded_decode(
    cfg: ArchConfig,
    layout: HeadLayout,
    q: jax.Array,  # (B,1,H_pad,hd), replicated over model
    k_new: jax.Array,  # (B,1,K_true,hd)
    v_new: jax.Array,
    cache: Params,  # {"ks","vs": (B,W,K_true,hd) seq-sharded, "poss": (W,)}
    t: jax.Array,  # scalar int32 position
):
    """Decode attention over a sequence-sharded true-KV cache.

    Each TP rank holds a W/TP chunk of the ring buffer (TRUE kv heads -- no
    xR repetition), writes the new token if its slot lands locally, computes
    the partial flash statistics over its chunk, and the ranks combine with
    a max/sum reduction: o = psum(acc*exp(m-M)) / psum(l*exp(m-M)).
    """
    from ..distributed import axes as _axes

    ctx = _axes.current()
    b, _, h_pad, hd = q.shape
    k_true = layout.n_kv
    gp = layout.repeat * layout.g_pad  # query slots per TRUE kv head
    scale = 1.0 / math.sqrt(hd)
    w_total = cache["ks"].shape[1]

    def _attend(qg, ck, cv, pos, t_):
        """Partial flash stats over one chunk.  Returns (m, l, acc)."""
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, ck, preferred_element_type=jnp.float32
        ) * scale  # (B,K,G',1,wl)
        valid = (pos >= 0) & (pos <= t_)
        s = jnp.where(valid[None, None, None, None, :], s, float(jnp.finfo(jnp.float32).min / 2))
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        lsum = p.sum(axis=-1)
        acc = jnp.einsum("bkgqs,bskd->bkgqd", p, cv, preferred_element_type=jnp.float32)
        return m, lsum, acc

    def _write(ck, cv, pos, kn, vn, slot_local, active):
        cur_k = jax.lax.dynamic_slice_in_dim(ck, slot_local, 1, 1)
        cur_v = jax.lax.dynamic_slice_in_dim(cv, slot_local, 1, 1)
        cur_p = jax.lax.dynamic_slice_in_dim(pos, slot_local, 1, 0)
        ck = jax.lax.dynamic_update_slice_in_dim(
            ck, jnp.where(active, kn, cur_k), slot_local, 1
        )
        cv = jax.lax.dynamic_update_slice_in_dim(
            cv, jnp.where(active, vn, cur_v), slot_local, 1
        )
        pos = jax.lax.dynamic_update_slice_in_dim(
            pos, jnp.where(active, t.astype(jnp.int32)[None], cur_p), slot_local, 0
        )
        return ck, cv, pos

    if ctx is None or not ctx.model or w_total % ctx.axis_size(ctx.model):
        # single-device / unsharded fallback: same math, whole buffer local
        ck, cv, pos = _write(
            cache["ks"], cache["vs"], cache["poss"], k_new, v_new, t % w_total, True
        )
        qg = q.reshape(b, 1, k_true, gp, hd)
        m, lsum, acc = _attend(qg, ck, cv, pos, t)
        o = acc / jnp.maximum(lsum[..., None], 1e-30)
        o = o.reshape(b, 1, h_pad, hd).astype(q.dtype)
        return o, {"ks": ck, "vs": cv, "poss": pos}

    from jax.sharding import PartitionSpec as P

    ax = ctx.model
    bt = tuple(ctx.batch) if ctx.batch else None

    def body(q_l, kn_l, vn_l, ck, cv, pos):
        wl = ck.shape[1]
        idx = jax.lax.axis_index(ax)
        slot = (t % w_total).astype(jnp.int32)
        lo = idx * wl
        active = jnp.logical_and(slot >= lo, slot < lo + wl)
        slot_local = jnp.clip(slot - lo, 0, wl - 1)
        ck, cv, pos = _write(ck, cv, pos, kn_l, vn_l, slot_local, active)
        qg = q_l.reshape(q_l.shape[0], 1, k_true, gp, hd)
        m, lsum, acc = _attend(qg, ck, cv, pos, t)
        # flash combine across seq shards
        m_g = jax.lax.pmax(m, ax)
        alpha = jnp.exp(m - m_g)
        l_g = jax.lax.psum(lsum * alpha, ax)
        o = jax.lax.psum(acc * alpha[..., None], ax) / jnp.maximum(l_g[..., None], 1e-30)
        o = o.reshape(q_l.shape[0], 1, h_pad, hd).astype(q_l.dtype)
        return o, ck, cv, pos

    rep = P(bt, None, None, None)
    seq = P(bt, ax, None, None)
    o, ck, cv, pos = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(rep, rep, rep, seq, seq, P(ax)),
        out_specs=(rep, seq, seq, P(ax)),
        check_vma=False,
    )(q, k_new, v_new, cache["ks"], cache["vs"], cache["poss"])
    return o, {"ks": ck, "vs": cv, "poss": pos}


# --------------------------------------------------------------------------
# transformer block (attention + FFN/MoE) for dense / moe / vlm / encoder
# --------------------------------------------------------------------------


def _norm(p, cfg: ArchConfig, x, name: str):
    if cfg.norm_type == "rms":
        return rms_norm(x, p[name], plus_one=cfg.norm_plus_one)
    return layer_norm(x, p[name + "_w"], p[name + "_b"])


def init_norm(cfg: ArchConfig, d: int, dtype, name: str) -> Params:
    if cfg.norm_type == "rms":
        init = jnp.zeros if cfg.norm_plus_one else jnp.ones
        return {name: init((d,), dtype)}
    return {name + "_w": jnp.ones((d,), dtype), name + "_b": jnp.zeros((d,), dtype)}


def init_block(key, cfg: ArchConfig, layout: HeadLayout, dtype) -> Params:
    ks = jax.random.split(key, 2)
    p: Params = {"attn": init_attention(ks[0], cfg, layout, dtype)}
    p.update(init_norm(cfg, cfg.d_model, dtype, "norm1"))
    p.update(init_norm(cfg, cfg.d_model, dtype, "norm2"))
    if cfg.is_moe:
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts, dtype)
    elif cfg.gated_mlp:
        p["mlp"] = init_gated_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype, bias=cfg.mlp_bias)
    return p


def block_apply(
    p: Params,
    cfg: ArchConfig,
    layout: HeadLayout,
    x: jax.Array,
    positions: jax.Array,
    mrope_positions=None,
    cache=None,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    h, new_cache = attention_apply(
        p["attn"], cfg, layout, _norm(p, cfg, x, "norm1"), positions, mrope_positions,
        cache, cfg.window,
    )
    h = jax.ad_checkpoint.checkpoint_name(h, "block_out")
    x = x + h
    y_in = _norm(p, cfg, x, "norm2")
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        y, aux = moe_ffn(p["moe"], y_in, cfg.n_experts_per_tok, cfg.capacity_factor, cfg.act)
    elif cfg.gated_mlp:
        y = gated_mlp(p["mlp"], y_in, cfg.act)
    else:
        y = mlp(p["mlp"], y_in, cfg.act)
    y = jax.ad_checkpoint.checkpoint_name(y, "block_out")
    return x + y, new_cache, aux


# --------------------------------------------------------------------------
# full model
# --------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig) -> Params:
    dtype = cfg.dtype("param")
    layout = HeadLayout.make(cfg.n_heads, cfg.n_kv_heads, cfg.pad_heads_to)
    ks = jax.random.split(key, cfg.n_layers + 3)
    params: Params = {
        "embed": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model), cfg.d_model, dtype)
    }
    if cfg.scan_layers:
        layers = [init_block(ks[1 + i], cfg, layout, dtype) for i in range(cfg.n_layers)]
        params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    else:
        params["layers"] = [
            init_block(ks[1 + i], cfg, layout, dtype) for i in range(cfg.n_layers)
        ]
    params.update(init_norm(cfg, cfg.d_model, dtype, "final_norm"))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            ks[-1], (cfg.d_model, cfg.padded_vocab), cfg.d_model, dtype
        )
    return params


def _embed(params, cfg: ArchConfig, tokens=None, embeds=None) -> jax.Array:
    if embeds is None:
        embeds = params["embed"][tokens]
    x = embeds.astype(cfg.dtype("compute"))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return shard(x, "batch", "residual", None)


def _unembed(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = _norm(params, cfg, x, "final_norm")
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    return shard(logits, "batch", None, "model")


def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (logits fp32, new_cache, moe_aux)."""
    layout = HeadLayout.make(cfg.n_heads, cfg.n_kv_heads, cfg.pad_heads_to)
    x = _embed(params, cfg, tokens, embeds)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body_fn(x, layer_p, layer_cache):
        layer_p = cast_for_compute(layer_p, cfg.dtype("compute"))
        return block_apply(layer_p, cfg, layout, x, positions, mrope_positions, layer_cache)

    if cfg.remat:
        if cfg.remat_policy == "block_outs":
            # keep the post-psum block outputs: the backward recompute then
            # stops at the saved values instead of re-running the collectives
            policy = jax.checkpoint_policies.save_only_these_names("block_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        body_fn = jax.checkpoint(body_fn, policy=policy)

    if cfg.scan_layers:
        def scan_body(carry, xs):
            x = carry
            layer_p, layer_cache = xs
            x, new_cache, aux = body_fn(x, layer_p, layer_cache)
            return x, (new_cache, aux)

        if cache is None:
            # dummy per-layer cache of Nones is not scannable; use a unit array
            xs = (params["layers"], jnp.zeros((cfg.n_layers,), jnp.float32))

            def scan_body_nc(carry, xs):
                x = carry
                layer_p, _ = xs
                x, _, aux = body_fn(x, layer_p, None)
                return x, aux

            x, auxs = jax.lax.scan(scan_body_nc, x, xs)
            new_cache = None
        elif cfg.cache_in_carry:
            # cache lives in the scan carry: ring-buffer updates are in-place
            # dynamic-update-slices on ONE buffer (aliases under donation)
            # instead of the xs->ys double-buffer (see EXPERIMENTS §Perf).
            def scan_body_carry(carry, layer_p):
                x, cache_st, i = carry
                layer_cache = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
                    cache_st,
                )
                x, nc, aux = body_fn(x, layer_p, layer_cache)
                cache_st = jax.tree.map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), i, 0
                    ),
                    cache_st,
                    nc,
                )
                return (x, cache_st, i + 1), aux

            (x, new_cache, _), auxs = jax.lax.scan(
                scan_body_carry, (x, cache, jnp.zeros((), jnp.int32)), params["layers"]
            )
        else:
            x, (new_cache, auxs) = jax.lax.scan(scan_body, x, (params["layers"], cache))
        aux = auxs.sum()
    else:
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for i, layer_p in enumerate(params["layers"]):
            layer_cache = None if cache is None else cache[i]
            x, nc, a = body_fn(x, layer_p, layer_cache)
            new_caches.append(nc)
            aux = aux + a
        new_cache = new_caches if cache is not None else None

    logits = _unembed(params, cfg, x)
    return logits, new_cache, aux


# --------------------------------------------------------------------------
# cache init
# --------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    layout = HeadLayout.make(cfg.n_heads, cfg.n_kv_heads, cfg.pad_heads_to)
    w = min(max_len, cfg.window) if cfg.window else max_len
    dtype = cfg.dtype("compute")
    if cfg.decode_kv_seq_sharded and not cfg.window:
        # true kv heads, ring buffer seq-sharded over the TP axis
        one = {
            "ks": jnp.zeros((batch, w, layout.n_kv, cfg.head_dim), dtype),
            "vs": jnp.zeros((batch, w, layout.n_kv, cfg.head_dim), dtype),
            "poss": jnp.full((w,), -1, jnp.int32),
        }
        if cfg.scan_layers:
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), one
            )
        return [jax.tree.map(jnp.copy, one) for _ in range(cfg.n_layers)]
    one = {
        "k": jnp.zeros((batch, w, layout.k_pad, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, w, layout.k_pad, cfg.head_dim), dtype),
        "pos": jnp.full((w,), -1, jnp.int32),
    }
    if cfg.scan_layers:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), one
        )
    return [jax.tree.map(jnp.copy, one) for _ in range(cfg.n_layers)]


# --------------------------------------------------------------------------
# losses / steps (train, prefill, decode)
# --------------------------------------------------------------------------


def train_loss(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array]):
    """batch: tokens/embeds, labels, loss_mask [, mrope_positions]."""
    logits, _, aux = forward(
        params,
        cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        mrope_positions=batch.get("mrope_positions"),
    )
    loss = cross_entropy_loss(
        logits, batch["labels"], batch.get("loss_mask"), real_vocab=cfg.vocab_size
    )
    total = loss + cfg.router_aux_loss * aux if cfg.is_moe else loss
    return total, {"loss": loss, "moe_aux": aux}


def prefill(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], max_len: int):
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    cache = init_cache(cfg, b, max_len)
    logits, cache, _ = forward(
        params, cfg, tokens=tokens, embeds=embeds,
        mrope_positions=batch.get("mrope_positions"), cache=cache,
    )
    return logits[:, -1], cache, jnp.asarray(s, jnp.int32)


def decode_step(
    params: Params,
    cfg: ArchConfig,
    cache: Params,
    tokens: jax.Array,  # (B,1)
    t: jax.Array,  # scalar int32 current position
):
    b = tokens.shape[0]
    positions = jnp.broadcast_to(t[None, None], (b, 1)).astype(jnp.int32)
    mrope = None
    if cfg.family == "vlm":
        mrope = jnp.broadcast_to(t[None, None, None], (b, 1, 3)).astype(jnp.int32)
    logits, cache, _ = forward(
        params, cfg, tokens=tokens, positions=positions, mrope_positions=mrope, cache=cache
    )
    return logits[:, -1], cache, t + 1
