"""Micro-benchmarks of the compute hot-spot layers: the jnp formulations the
Pallas kernels replace, timed on the default backend, with the ALGORITHMIC
byte/step ratios the kernels buy as the derived numbers.  No Pallas kernel
is timed here yet."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.attention.ref import attention_ref
from repro.models.linear_scan import wkv6_chunked


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    b, t, h, hd = 2, 1024, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, hd), jnp.float32)
    ref = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us = _time(ref, q, k, v)
    naive_bytes = b * h * t * t * 4 * 2  # scores + probs materialized
    flash_vmem = 128 * 128 * 4 * 2  # one (bq, bk) tile pair
    rows.append({
        "name": "attention_naive_vs_flash_tile",
        "us_per_call": us,
        "derived": f"naive_score_bytes={naive_bytes};flash_tile_bytes={flash_vmem};"
                   f"reduction={naive_bytes / flash_vmem:.0f}x",
    })

    kdim = 64
    r = jax.random.normal(ks[0], (b, t, h, kdim))
    kk = jax.random.normal(ks[1], (b, t, h, kdim))
    vv = jax.random.normal(ks[2], (b, t, h, kdim))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[0], (b, t, h, kdim)) * 0.3))
    u = jax.random.normal(ks[1], (h, kdim)) * 0.1
    chunked = jax.jit(lambda *a: wkv6_chunked(*a, chunk=32))
    us = _time(chunked, r, kk, vv, w, u)
    serial_steps = t
    chunk_steps = t // 32
    rows.append({
        "name": "wkv6_chunked_scan",
        "us_per_call": us,
        "derived": f"serial_steps={serial_steps};chunked_steps={chunk_steps};"
                   f"mxu_matmul_shape=32x{kdim}",
    })
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
