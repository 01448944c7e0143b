"""jit'd public wrapper for the flash-attention kernel.

Accepts the model's (B, T, H, hd) layout, transposes to the kernel's
(B, H, T, hd) and picks MXU-aligned block sizes.  ``interpret=True`` runs
the kernel body as pure Python/jnp (how the CPU tests validate it); the
default compiles it for the TPU, so no backend is ever silently swapped."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_bhtd


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = flash_attention_bhtd(
        qt,
        kt,
        vt,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return jnp.transpose(out, (0, 2, 1, 3))
