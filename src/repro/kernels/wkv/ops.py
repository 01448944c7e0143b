"""jit'd public wrapper for the wkv6 kernel: model layout (B,T,H,K) in/out.
``interpret=True`` runs the kernel body as jnp (the CPU tests); the default
compiles it for the TPU."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.wkv.kernel import wkv6_bhtk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,
    v: jax.Array,  # (B, T, H, V)
    w: jax.Array,  # (B, T, H, K)
    u: jax.Array,  # (H, K)
    s0: Optional[jax.Array] = None,  # (B, H, K, V)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, t, h, kdim = r.shape
    vdim = v.shape[-1]
    chunk = min(chunk, t)
    if chunk > 64:
        # The straddle-factorized intra-chunk scores (kernel.py) are exact at
        # any decay strength, but each extra chunk doubling adds a masked
        # (C,C) matmul level and grows the VMEM-resident score matrix; 64
        # keeps the kernel comfortably within scratch budget.  (Mamba2 moved
        # to scalar per-head decay to use the (C,C) pairwise-exact log-space
        # form directly — see linear_scan.ssm_chunked, exact at any chunk.)
        raise ValueError(f"wkv6 chunk must be <= 64 for f32 stability, got {chunk}")

    def fold(x):  # (B,T,H,D) -> (B*H, T, D)
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, x.shape[-1])

    if s0 is None:
        s0 = jnp.zeros((b, h, kdim, vdim), jnp.float32)
    y, s_final = wkv6_bhtk(
        fold(r), fold(k), fold(v), fold(w),
        u, s0.reshape(b * h, kdim, vdim),
        n_heads=h, chunk=chunk, interpret=interpret,
    )
    y = jnp.transpose(y.reshape(b, h, t, vdim), (0, 2, 1, 3))
    return y, s_final.reshape(b, h, kdim, vdim)
