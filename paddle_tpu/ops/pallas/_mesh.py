"""Pallas calls under a fleet mesh.

The SPMD partitioner cannot split a Mosaic kernel ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"), whatever its operands' shardings — so once ``fleet.init``
has built a mesh of more than one device, every kernel call site asks
:func:`kernel_placement`, a rule on what the trace can observe (never
an exception handler around the partitioner's error):

- no mesh, a one-device mesh, or a manual region that binds every mesh
  axis larger than one (the compiled pipeline / ring-attention /
  expert-parallel bodies) -> ``(True, None)``: operands are per-device
  values, the kernel is called as is;
- a GSPMD-partitioned program -> ``(True, mesh)``: the call is wrapped
  in a ``shard_map`` over the whole mesh (the ``sharded_*`` helpers) —
  batch-like dims over the data axes (``data`` x ``sharding``), heads /
  MLP columns over ``model`` — each where the axis divides the dim,
  replicated otherwise;
- a PARTIALLY manual region (some axes bound, others still automatic)
  -> ``(False, None)``: a nested shard_map there is not built yet, so
  the call site takes its jnp path. Logged once; ROADMAP carries it as
  an open Speed item.
"""

from __future__ import annotations

import math

from jax.sharding import PartitionSpec as P

from ...utils.jax_compat import shard_map

__all__ = ["kernel_placement", "sharded_heads", "sharded_rows",
           "sharded_cols"]

_DATA_AXES = ("data", "sharding")
_MODEL_AXIS = "model"


def kernel_placement():
    """``(use the kernel, mesh to shard_map it over or None)`` for a
    Pallas call traced right now (module docstring)."""
    from ...distributed.fleet import fleet
    hcg = fleet.get_hybrid_communicate_group()
    mesh = getattr(hcg, "global_mesh", None)
    if mesh is None or mesh.size == 1:
        return True, None
    from ...distributed.communication import axis_in_traced_region
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    bound = [a for a in wide if axis_in_traced_region(a)]
    if not bound:
        return True, mesh
    if len(bound) == len(wide):
        return True, None
    from ...profiler.trace import log_perf_event
    log_perf_event(
        "pallas/partial_manual_jnp",
        f"Pallas kernels take the jnp path inside a partially manual "
        f"region (bound {bound} of {wide})",
        once_key="pallas/partial_manual_jnp")
    return False, None


def _fit(mesh, axes, dim):
    """``axes`` (those wider than one device) if together they divide
    ``dim``, else None (replicated)."""
    axes = tuple(a for a in axes
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    n = math.prod(mesh.shape[a] for a in axes)
    if not axes or dim % n:
        return None
    return axes if len(axes) > 1 else axes[0]


def _smap(fn, mesh, in_specs, out_specs):
    # check_vma off: pallas_call has no varying-axes rule
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def sharded_heads(fn, mesh, q, k, v):
    """``fn(q, k, v)`` on ``[B, S, H, D]`` operands, batch over the data
    axes and heads over ``model`` (q AND kv head counts must divide).
    ``mesh=None`` (every ``sharded_*`` helper): a plain call."""
    if mesh is None:
        return fn(q, k, v)
    batch = _fit(mesh, _DATA_AXES, q.shape[0])
    heads = _fit(mesh, (_MODEL_AXIS,), math.gcd(q.shape[2], k.shape[2]))
    spec = P(batch, None, heads, None)
    return _smap(fn, mesh, (spec, spec, spec), spec)(q, k, v)


def sharded_rows(fn, mesh, *xs, replicated=(), n_out=1):
    """``fn(*xs, *replicated)`` for row-parallel kernels: every ``xs``
    operand (and every output) is ``[B, ..., d]`` with its leading dim
    over the data axes; ``replicated`` operands (norm weights) are
    whole on every device."""
    if mesh is None:
        return fn(*xs, *replicated)
    x = xs[0]
    spec = P(_fit(mesh, _DATA_AXES, x.shape[0]), *([None] * (x.ndim - 1)))
    in_specs = (spec,) * len(xs) + tuple(
        P(*([None] * r.ndim)) for r in replicated)
    out_specs = spec if n_out == 1 else (spec,) * n_out
    return _smap(fn, mesh, in_specs, out_specs)(*xs, *replicated)


def sharded_cols(fn, mesh, *xs):
    """``fn(*xs)`` for elementwise kernels on ``[B, ..., h]`` operands
    of one shape: leading dim over the data axes, last dim (the MLP's
    column-parallel width) over ``model``."""
    if mesh is None:
        return fn(*xs)
    x = xs[0]
    if x.ndim == 1:
        spec = P(_fit(mesh, (_MODEL_AXIS,), x.shape[0]))
    else:
        spec = P(_fit(mesh, _DATA_AXES, x.shape[0]),
                 *([None] * (x.ndim - 2)),
                 _fit(mesh, (_MODEL_AXIS,), x.shape[-1]))
    return _smap(fn, mesh, (spec,) * len(xs), spec)(*xs)
