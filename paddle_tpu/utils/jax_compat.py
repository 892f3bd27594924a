"""The one import site of ``shard_map``.

Every call site (distributed/zero_bubble.py, distributed/pipeline.py,
fleet context_parallel, the EP MoE layer, the kernel wrappers under a
fleet mesh) imports :func:`shard_map` from here and writes
``axis_names=`` for a partial-manual region. pyproject.toml pins the
jax minor; there is no branch for another release.
"""

from __future__ import annotations

__all__ = ["shard_map"]

from jax import shard_map as _impl


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, **kw):
    """``jax.shard_map``; ``axis_names=None`` binds every mesh axis, a
    partial set leaves the other axes to the compiler."""
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return _impl(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                 **kw)
