"""Pallas TPU kernels — the role of PHI's hand-written CUDA fusion kernels
(SURVEY.md §2.1: fused_attention/flash-attn, rms_norm, fused_rope →
"Pallas kernels for flash-attn/rope/rms-norm").

Each module exposes a jnp reference implementation (used on CPU and as the
numerics oracle in tests) and a Pallas kernel used on TPU when
FLAGS_enable_pallas_kernels is set."""

from . import (flash_attention, linear_ce, ragged_paged_attention,
               rms_norm, rope, swiglu)

__all__ = ["flash_attention", "linear_ce", "ragged_paged_attention",
           "rms_norm", "rope", "swiglu"]
