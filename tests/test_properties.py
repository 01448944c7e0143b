"""Hypothesis property tests (aggregation masks, Pallas kernels).

Collected only when `hypothesis` is installed (the `dev` extra); the module
skips cleanly otherwise so the tier-1 suite never errors at collection.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (dev extra)")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import aggregation as agg
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.wkv.ops import wkv6

# ---------------- aggregation ----------------


@given(
    n=st.integers(2, 32),
    k=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_fastest_k_mask_has_exactly_k_ones(n, k, seed):
    k = min(k, n)
    times = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    mask = agg.fastest_k_mask(times, jnp.asarray(k))
    assert int(mask.sum()) == k
    # masked workers are exactly the k smallest times
    chosen = np.sort(np.asarray(times)[np.asarray(mask) > 0])
    all_sorted = np.sort(np.asarray(times))
    np.testing.assert_allclose(chosen, all_sorted[:k])


# ---------------- attention kernel ----------------


@given(
    t=st.sampled_from([64, 128]),
    h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    hd=st.sampled_from([32, 64]),
    seed=st.integers(0, 1000),
)
@settings(max_examples=8, deadline=None)
def test_flash_attention_property(t, h, g, hd, seed):
    kv = max(h // g, 1)
    h = kv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, t, h, hd))
    k = jax.random.normal(ks[1], (1, t, kv, hd))
    v = jax.random.normal(ks[2], (1, t, kv, hd))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------- wkv kernel ----------------


def _wkv_inputs(b, t, h, k, v_dim, seed=0, decay_scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (b, t, h, k))
    kk = jax.random.normal(ks[1], (b, t, h, k))
    vv = jax.random.normal(ks[2], (b, t, h, v_dim))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, k)) * decay_scale))
    u = jax.random.normal(ks[4], (h, k)) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, k, v_dim)) * 0.2
    return r, kk, vv, w, u, s0


@given(seed=st.integers(0, 10_000), chunk=st.sampled_from([16, 32]))
@settings(max_examples=6, deadline=None)
def test_wkv_property_state_consistency(seed, chunk):
    """Splitting the sequence and carrying state == one pass (renewal property)."""
    r, kk, vv, w, u, s0 = _wkv_inputs(1, 64, 2, 8, 8, seed=seed)
    y_all, s_all = wkv6(r, kk, vv, w, u, s0, chunk=chunk, interpret=True)
    y1, s1 = wkv6(r[:, :32], kk[:, :32], vv[:, :32], w[:, :32], u, s0, chunk=chunk,
                  interpret=True)
    y2, s2 = wkv6(r[:, 32:], kk[:, 32:], vv[:, 32:], w[:, 32:], u, s1, chunk=chunk,
                  interpret=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_all), atol=1e-3, rtol=2e-3
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), atol=1e-3, rtol=2e-3)
