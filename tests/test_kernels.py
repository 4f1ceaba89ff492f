"""Pallas kernels vs pure-jnp oracles: shape/dtype sweep + properties.

All kernels run in interpret mode on CPU (the kernel body executes exactly
as it would inside the TPU grid).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # test extra not installed: seeded fallback engine
    from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ref import flash_attention_ref, rms_norm_ref
from repro.kernels.rmsnorm import rms_norm_fused


def _qkv(key, b, h, kh, sq, sk, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kh, sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kh, sk, hd), jnp.float32).astype(dtype)
    return q, k, v


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5), jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ---------------------------------------------------------------- flash attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kh,s,hd",
    [
        (1, 4, 4, 128, 64),   # MHA, one block
        (2, 4, 2, 256, 64),   # GQA 2:1, multiple blocks
        (1, 8, 1, 192, 128),  # MQA, ragged seq vs block
        (1, 2, 2, 64, 256),   # gemma-style head_dim 256
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_sweep(dtype, b, h, kh, s, hd, causal):
    q, k, v = _qkv(jax.random.key(0), b, h, kh, s, s, hd, dtype)
    got = flash_attention_fwd(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
    )
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **TOL[dtype]
    )


def test_flash_kernel_window():
    q, k, v = _qkv(jax.random.key(1), 1, 2, 1, 256, 256, 64, jnp.float32)
    got = flash_attention_fwd(
        q, k, v, causal=True, window=96, block_q=64, block_k=64, interpret=True
    )
    want = flash_attention_ref(q, k, v, causal=True, window=96)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_kernel_cross_attention_lengths():
    # Sq != Sk (e.g. chunked prefill append)
    q, k, v = _qkv(jax.random.key(2), 1, 2, 2, 64, 192, 64, jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    s=st.integers(16, 160),
    h=st.sampled_from([2, 4]),
    g=st.sampled_from([1, 2]),
    causal=st.booleans(),
)
def test_flash_kernel_property(s, h, g, causal):
    """Property: arbitrary (non-block-aligned) seq lengths match the oracle."""
    kh = h // g
    q, k, v = _qkv(jax.random.key(s), 1, h, kh, s, s, 32, jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_flash_matches_model_layer_path():
    """Kernel contract == the model's blockwise-jnp attention."""
    from repro.models.layers import flash_attention as jnp_flash

    b, s, h, kh, hd = 2, 96, 4, 2, 32
    q, k, v = _qkv(jax.random.key(3), b, h, kh, s, s, hd, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    got = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    want = jnp_flash(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        pos, pos, causal=True, block_k=32,
    )
    np.testing.assert_allclose(got, jnp.swapaxes(want, 1, 2), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- rmsnorm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 96, 64), (3, 128), (1, 7, 33)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rmsnorm_kernel_sweep(dtype, shape, plus_one):
    x = jax.random.normal(jax.random.key(0), shape, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.key(1), shape[-1:], jnp.float32).astype(dtype) * 0.1
    got = rms_norm_fused(x, w, plus_one=plus_one, block_rows=32, interpret=True)
    want = rms_norm_ref(x, w, plus_one=plus_one)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **TOL[dtype]
    )


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rms_norm

    x = jax.random.normal(jax.random.key(2), (4, 17, 48))
    w = jnp.ones((48,)) * 1.3
    got = rms_norm_fused(x, w, interpret=True)
    np.testing.assert_allclose(got, rms_norm(x, w), atol=1e-6, rtol=1e-6)


# ------------------------------------------------------------- masked cover


def test_masked_cover_matches_oracle():
    """Fused Pallas ``max_b min_r`` == gang_cover_times on a (B, r) sweep,
    including padded slots and non-divisible rep/block shapes."""
    from repro.core.simulator import gang_cover_times
    from repro.kernels.cover import bench_masked_cover, masked_cover_times

    draws = jax.random.exponential(jax.random.key(3), (37, 6, 4))
    for b, r in [(1, 1), (3, 2), (6, 4), (2, 4), (6, 1)]:
        got = masked_cover_times(
            draws, jnp.int32(b), jnp.int32(r), block_rows=16, interpret=True
        )
        want = gang_cover_times(draws, b, r)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # the measurement hook runs everywhere and reports honestly: interpret
    # mode off-TPU, where the XLA fusion is expected to keep winning
    m = bench_masked_cover(reps=256, iters=1)
    assert set(m) == {"pallas_seconds", "jnp_seconds", "interpret", "pallas_wins"}
    if jax.default_backend() != "tpu":
        assert m["interpret"]


def test_pallas_cover_routing_is_opt_in(monkeypatch):
    from repro.cluster import vectorized
    from repro.kernels.cover import pallas_cover_wins

    monkeypatch.delenv("REPRO_PALLAS_COVER", raising=False)
    assert not pallas_cover_wins()
    assert vectorized._cover_impl() is vectorized._frontier_cover
    monkeypatch.setenv("REPRO_PALLAS_COVER", "1")
    if jax.default_backend() != "tpu":
        assert not pallas_cover_wins()  # interpret mode loses: stay on XLA
