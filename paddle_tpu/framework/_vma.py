"""Varying-manual-axes (vma) helper shared by the manual-collective
engines (pipeline scan carries, ring-attention scan carries).

Inside a shard_map region, jax tracks which named axes a value is
device-varying over; freshly created constants (zeros carries) start
invariant and must be explicitly marked before a ``lax.scan`` whose
outputs vary — otherwise the carry types mismatch. This helper is the
one place that knows how to read a value's current vma and cast it."""

from __future__ import annotations

import jax
from jax import lax

__all__ = ["pvary_missing"]


def pvary_missing(x, axes=(), like=None):
    """Mark ``x`` device-varying over ``axes`` plus every axis ``like``
    already varies on, skipping axes ``x`` is already varying over."""
    want = set(axes)
    if like is not None:
        try:
            want |= set(jax.typeof(like).vma)
        except Exception:
            pass
    try:
        want -= set(jax.typeof(x).vma)
    except Exception:
        pass
    if not want:
        return x
    return lax.pcast(x, tuple(want), to="varying")
