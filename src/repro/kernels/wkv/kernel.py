"""RWKV-6 wkv chunked scan for TPU (Pallas).

TPU-native adaptation of the Finch recurrence: instead of a GPU-style
one-thread-per-channel serial scan, the sequence is processed in chunks.
The chunk axis is the sequential (last) grid dimension; the per-(batch, head)
state S in R^{K x V} lives in VMEM scratch and is carried across chunk steps.
Within a chunk everything is matmul-shaped for the MXU: a decay-weighted
(C x C) attention-like score matrix and (C,K)@(K,V) state applications.
Decays are handled in log space; the score matrix uses a straddle-boundary
factorization (one masked matmul per power-of-two level) whose exponents are
all <= 0, so it cannot overflow f32 at any decay strength.

Grid: (B*H, T // C).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref,  # (1, C, K)
    k_ref,  # (1, C, K)
    v_ref,  # (1, C, V)
    w_ref,  # (1, C, K)
    u_ref,  # (1, 1, K)
    s0_ref,  # (1, K, V)
    y_ref,  # (1, C, V)
    sT_ref,  # (1, K, V)
    s_scr,  # (K, V) f32 carried state
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, K)
    s = s_scr[...]

    logw = jnp.log(jnp.maximum(w, 1e-20))
    # Inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum lowering); HIGHEST keeps it at f32 accuracy.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (col <= row).astype(jnp.float32)
    li = jax.lax.dot_general(
        tril, logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # inclusive (C, K)
    le = li - logw  # exclusive
    lt = li[chunk - 1]  # (K,) chunk-total log decay

    # inter-chunk: y_t += (r_t * exp(le_t)) @ S
    y = jax.lax.dot_general(
        r * jnp.exp(le), s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, V)

    # intra-chunk: scores[t, tau] = sum_k r_t k_tau exp(le_t - li_tau), tau < t.
    # A factorized score exp(le_t - ref) * exp(ref - li_tau) cannot overflow
    # iff the reference lies *between* tau and t (both exponents are then
    # partial decay sums, hence <= 0).  A single midpoint reference only
    # guarantees that for pairs straddling the midpoint; under very strong
    # decays the same-side pairs overflow f32 (inf * 0 = NaN).  Instead,
    # every pair uses the unique power-of-two-aligned boundary it straddles
    # (the odd multiple of the largest possible 2^j in (tau, t]): one masked
    # (C,C) matmul per level, every factor <= 1, every product *exact*.
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)  # (C, 1)
    tpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    taupos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.zeros((chunk, chunk), jnp.float32)
    h = 1
    while h < chunk:
        blk = pos // h
        is_q = (blk % 2) == 1  # second half of its 2h-block -> query side
        # boundary m: the odd multiple of h covering/facing this position;
        # the reference row is li[m - 1].
        mref = jnp.where(is_q, blk * h, (blk + 1) * h) - 1  # (C, 1)
        sel = (taupos == mref).astype(jnp.float32)  # one-hot row selector
        li_ref = jax.lax.dot_general(
            sel, li, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (C, K)
        # exponents are <= 0 by construction for active rows; the minimum
        # guards inactive rows (their pairs are masked out below anyway).
        e_q = jnp.where(is_q, jnp.minimum(le - li_ref, 0.0), -jnp.inf)
        e_k = jnp.where(is_q, -jnp.inf, jnp.minimum(li_ref - li, 0.0))
        part = jax.lax.dot_general(
            r * jnp.exp(e_q), k * jnp.exp(e_k),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        t_blk, tau_blk = tpos // h, taupos // h
        pair_mask = (
            (t_blk // 2 == tau_blk // 2) & (t_blk % 2 == 1) & (tau_blk % 2 == 0)
        )
        scores = scores + jnp.where(pair_mask, part, 0.0)
        h *= 2
    y = y + jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # current-token bonus: u-weighted diagonal
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C, 1)
    y = y + bonus * v

    # state update: S' = exp(lt) S + sum_tau exp(lt - li_tau) k_tau v_tau^T
    k_carry = k * jnp.exp(lt[None, :] - li)
    s_new = jnp.exp(lt)[:, None] * s + jax.lax.dot_general(
        k_carry, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_scr[...] = s_new
    y_ref[0, :, :] = y.astype(y_ref.dtype)
    sT_ref[0, :, :] = s_new.astype(sT_ref.dtype)


def wkv6_bhtk(
    r: jax.Array,  # (BH, T, K)
    k: jax.Array,
    v: jax.Array,  # (BH, T, V)
    w: jax.Array,  # (BH, T, K) decays in (0,1)
    u: jax.Array,  # (H, K)
    s0: jax.Array,  # (BH, K, V)
    *,
    n_heads: int,
    chunk: int = 128,
    interpret: bool = False,
):
    bh, t, kdim = r.shape
    vdim = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, kdim), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kdim), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, vdim), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kdim), lambda b, c: (b, c, 0)),
            # u as (H, 1, K): a (1, 1, K) block keeps the last two block
            # dims equal to the array's, as the TPU tiling requires.
            pl.BlockSpec((1, 1, kdim), lambda b, c: (b % n_heads, 0, 0)),
            pl.BlockSpec((1, kdim, vdim), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, vdim), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, kdim, vdim), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, vdim), jnp.float32),
            jax.ShapeDtypeStruct((bh, kdim, vdim), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kdim, vdim), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(n_heads, 1, kdim), s0)
    return y, s_final
