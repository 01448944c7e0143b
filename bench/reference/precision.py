"""Matrix products at a named precision, the same on every backend.

``highest`` is a float32 product (``lax.Precision.HIGHEST``).  The lower
ones are built from float32 products of operands rounded on purpose, so
they mean the same on the CPU, where XLA ignores the precision flag, as on
the TPU:

* ``high``: three bfloat16 passes, ``a_hi b_hi + a_hi b_mid + a_mid b_hi``,
  what XLA:TPU runs for ``Precision.HIGH``;
* ``fp8``: operands scaled per tensor to float8 e4m3 and back.

The references compute at the configuration's precision; the controls
compute one step below it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high", "fp8")
_HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _emulated(spec: str, a, b, precision: str):
    dot = lambda x, y: jnp.einsum(spec, x, y, precision=_HI)  # noqa: E731
    if precision == "high":
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_mid, b_mid = _bf16(a - a_hi), _bf16(b - b_hi)
        return dot(a_hi, b_mid) + dot(a_mid, b_hi) + dot(a_hi, b_hi)
    return dot(_fp8(a), _fp8(b))


def _lowered(spec: str, precision: str):
    """The product at a lower precision, with its backward products (each
    operand's gradient is the product of the output's gradient with the
    other operand) at the same precision, as lower-precision training runs
    them."""
    ins, out = spec.replace(" ", "").split("->")
    in_a, in_b = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return _emulated(spec, a, b, precision)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return (_emulated(f"{out},{in_b}->{in_a}", g, b, precision),
                _emulated(f"{in_a},{out}->{in_b}", a, g, precision))

    f.defvjp(fwd, bwd)
    return f


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` in float32 at ``precision``; every index of
    each operand appears in the other operand or in the output."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; options {PRECISIONS}")
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    return _lowered(spec, precision)(a, b)


def matmul(a, b, precision: str):
    """``a @ b`` for a matrix or vector ``a`` and ``b`` at ``precision``."""
    if a.ndim == 1 and b.ndim == 1:
        return einsum("i,i->", a, b, precision)
    if b.ndim == 1:
        return einsum("...i,i->...", a, b, precision)
    return einsum("...i,ij->...j", a, b, precision)
