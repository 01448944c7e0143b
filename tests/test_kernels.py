"""Pallas kernel validation (interpret=True on CPU) against pure-jnp oracles,
swept over shapes / dtypes / masking variants.

(The hypothesis property tests live in test_properties.py, which skips
cleanly when hypothesis is not installed.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.wkv.ops import wkv6
from repro.models.linear_scan import wkv6_step

# ------------------------------------------------------------- attention

ATTN_SHAPES = [
    # (B, T, S, H, KV, hd, causal, window)
    (2, 128, 128, 4, 2, 64, True, 0),  # GQA causal
    (1, 256, 256, 4, 4, 64, True, 64),  # MHA sliding window
    (2, 128, 256, 8, 2, 32, False, 0),  # cross-ish (no mask), longer kv
    (1, 128, 128, 8, 1, 64, True, 0),  # MQA (paligemma-style)
    (1, 512, 512, 2, 2, 128, True, 128),  # long window
]


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype):
    b, t, s, h, kv, hd, causal, window = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64), (128, 128)])
def test_flash_attention_block_shape_invariance(bq, bk):
    """Output must not depend on the BlockSpec tiling."""
    b, t, h, hd = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_first_token_attends_only_to_itself():
    """Causal row 0 must equal v[0] (softmax over a single key)."""
    b, t, h, hd = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, h, hd))
    v = jax.random.normal(ks[2], (b, t, h, hd))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]), atol=1e-5)


# ------------------------------------------------------------------ wkv


def _wkv_inputs(b, t, h, k, v_dim, seed=0, decay_scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (b, t, h, k))
    kk = jax.random.normal(ks[1], (b, t, h, k))
    vv = jax.random.normal(ks[2], (b, t, h, v_dim))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, k)) * decay_scale))
    u = jax.random.normal(ks[4], (h, k)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, k, v_dim)) * 0.2
    return r, kk, vv, w, u, s0


def _wkv_naive(r, k, v, w, u, s0):
    s = s0
    ys = []
    for t in range(r.shape[1]):
        y, s = wkv6_step(r[:, t], k[:, t], v[:, t], w[:, t], u, s)
        ys.append(y)
    return jnp.stack(ys, 1), s


WKV_SHAPES = [
    (2, 128, 3, 16, 16),
    (1, 64, 2, 32, 32),
    (1, 256, 1, 64, 64),  # RWKV-6 real head size
    (4, 32, 2, 8, 8),
]


@pytest.mark.parametrize("shape", WKV_SHAPES, ids=str)
def test_wkv_kernel_matches_naive(shape):
    b, t, h, k, v_dim = shape
    r, kk, vv, w, u, s0 = _wkv_inputs(b, t, h, k, v_dim)
    y_ref, s_ref = _wkv_naive(r, kk, vv, w, u, s0)
    y, s = wkv6(r, kk, vv, w, u, s0, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_wkv_kernel_chunk_invariance(chunk):
    r, kk, vv, w, u, s0 = _wkv_inputs(2, 128, 2, 16, 16)
    y_ref, s_ref = _wkv_naive(r, kk, vv, w, u, s0)
    y, s = wkv6(r, kk, vv, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-3, rtol=2e-3)


def test_wkv_chunk_over_64_rejected():
    r, kk, vv, w, u, s0 = _wkv_inputs(1, 128, 1, 8, 8)
    with pytest.raises(ValueError, match="chunk must be <= 64"):
        wkv6(r, kk, vv, w, u, s0, chunk=128, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv_kernel_dtypes(dtype):
    r, kk, vv, w, u, s0 = _wkv_inputs(1, 64, 2, 16, 16)
    y_ref, _ = _wkv_naive(r, kk, vv, w, u, s0)
    y, _ = wkv6(
        r.astype(dtype), kk.astype(dtype), vv.astype(dtype), w.astype(jnp.float32),
        u, s0, chunk=32, interpret=True,
    )
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref), atol=tol, rtol=0.05)


def test_wkv_strong_decay_stability():
    """Strong decays (the f32-overflow regime for naive factorization) must
    stay finite and accurate thanks to the straddle-boundary factorization —
    on the Pallas kernel AND the pure-jnp reference path (which inherited the
    same fix; its old midpoint re-centering overflowed on same-side pairs)."""
    from repro.models.linear_scan import wkv6_chunked

    r, kk, vv, w, u, s0 = _wkv_inputs(1, 128, 1, 8, 8, decay_scale=1.0)
    y_ref, s_ref = _wkv_naive(r, kk, vv, w, u, s0)
    y, s = wkv6(r, kk, vv, w, u, s0, chunk=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-3, rtol=5e-3)
    yj, sj = wkv6_chunked(r, kk, vv, w, u, s0, chunk=64)
    assert bool(jnp.all(jnp.isfinite(yj))), "jnp reference path produced non-finite"
    np.testing.assert_allclose(np.asarray(yj), np.asarray(y_ref), atol=2e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(sj), np.asarray(s_ref), atol=2e-3, rtol=5e-3)
