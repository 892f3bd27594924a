"""The matmul of the precision a control computes in: fp8, the nearest
below the bfloat16 both configurations state. It takes and returns float32;
the operands are rounded as fp8 would hold them (per-row scales for the
activations, per-column for the weights), the product is then exact. A
configuration that states another precision brings its control's matmul
with it."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _scaled(x, axis, top, cast):
    """x rounded as the low precision would hold it. Straight-through: the
    backward pass sees the identity, as quantization-aware training does (an
    unscaled fp8 backward would underflow every gradient to nought and fail
    for a reason no one would be tempted by)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jax.lax.stop_gradient(jnp.where(s > 0, s, 1.0))
    return x + jax.lax.stop_gradient(cast(x / s) * s - x)


def mm_fp8(x, w):
    c = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(_scaled(x, -1, 448.0, c), _scaled(w, 0, 448.0, c),
                      precision=HI)

