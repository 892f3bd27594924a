"""Rotary position embedding (RoPE).

Reference: phi fused_rope kernel (UNVERIFIED). On TPU the rotate+multiply
is bandwidth-bound elementwise work that XLA fuses into the surrounding
matmuls, so the jnp formulation IS the fused kernel; a bespoke Pallas kernel
buys nothing here (measured wisdom from the pallas guide: don't hand-write
what XLA already fuses)."""

from __future__ import annotations

import functools

import jax.numpy as jnp

__all__ = ["build_sin_cos", "apply_rope", "rope_reference",
           "rope_inv_freq"]


@functools.lru_cache(maxsize=32)
def _sin_cos_np(seq_len: int, dim: int, base: float):
    import numpy as np
    inv = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(t, inv)  # [S, D/2]
    return np.sin(freqs), np.cos(freqs)


def rope_inv_freq(dim, params):
    """(inv_freq [dim // 2] float32 numpy, attention_factor) of one rotary
    embedding as an HF ``rope_parameters`` group states it: ``rope_type``
    ``default`` (``theta ** (-2i / dim)``, factor 1) or ``yarn`` (HF's
    ``_compute_yarn_parameters``: the frequencies interpolated by
    ``factor`` below the correction range of ``beta_fast`` /
    ``beta_slow`` rotations over ``original_max_position_embeddings``,
    kept above it, a linear ramp between; cos and sin are multiplied by
    ``attention_factor``, ``0.1 ln(factor) + 1`` where not given)."""
    import math

    import numpy as np
    theta = float(params["rope_theta"])
    kind = params.get("rope_type", "default")
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: only default and yarn are "
                         f"built")
    factor = float(params["factor"])
    att = params.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    orig = float(params["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = correction_dim(params.get("beta_fast") or 32)
    high = correction_dim(params.get("beta_slow") or 1)
    if params.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: extrapolate (the fast dims)
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - keep) \
        + (1.0 / pos_freqs) * keep
    return inv.astype(np.float32), float(att)


def build_sin_cos(seq_len, dim, base=10000.0, dtype=jnp.float32):
    s, c = _sin_cos_np(int(seq_len), int(dim), float(base))
    return jnp.asarray(s, jnp.float32), jnp.asarray(c, jnp.float32)


def apply_rope(x, sin, cos, position_ids=None, neox=True):
    """x: [B, S, H, D]; sin/cos: [S, D/2] (fp32). Returns same dtype as x."""
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    if position_ids is not None:
        sin = jnp.take(sin, position_ids, axis=0)  # [B, S, D/2]
        cos = jnp.take(cos, position_ids, axis=0)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    else:
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    d2 = xf.shape[-1] // 2
    if neox:
        x1 = xf[..., :d2]
        x2 = xf[..., d2:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    else:
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(xf.shape)
    return out.astype(orig_dtype)


rope_reference = apply_rope
