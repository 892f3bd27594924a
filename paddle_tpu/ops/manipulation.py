"""Shape / layout / indexing manipulation ops
(paddle/tensor/manipulation.py parity, UNVERIFIED)."""

from __future__ import annotations

import builtins

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (Tensor, apply, to_jax_dtype, tape_alias,
                              tape_rebind)
from .common import as_tensor

__all__ = [
    "reshape", "reshape_", "transpose", "cast", "concat", "stack", "split",
    "chunk", "squeeze", "squeeze_", "unsqueeze", "unsqueeze_", "flatten",
    "flip", "roll", "tile", "expand", "expand_as", "broadcast_to",
    "broadcast_tensors", "gather", "gather_nd", "scatter", "scatter_nd",
    "scatter_nd_add", "index_select", "index_sample", "index_add", "index_put",
    "take_along_axis", "put_along_axis", "slice", "strided_slice", "unbind",
    "unstack", "tensordot", "moveaxis", "swapaxes", "rot90", "as_strided",
    "repeat_interleave", "masked_select", "masked_fill", "masked_scatter",
    "clone", "flatten_", "tolist", "unique", "unique_consecutive",
    "split_sections", "crop", "pad", "shard_index", "view", "view_as",
    "atleast_1d", "atleast_2d", "atleast_3d", "diff", "rot90",
    "tensor_split", "hsplit", "vsplit", "dsplit", "hstack", "vstack",
    "row_stack", "dstack", "column_stack", "unflatten", "unfold",
    "as_complex", "as_real", "diag_embed", "fill_diagonal_",
    "fill_diagonal_tensor", "fill_diagonal_tensor_", "select_scatter",
    "slice_scatter", "index_fill", "index_fill_", "masked_fill_",
    "masked_scatter_", "block_diag", "cartesian_prod", "combinations",
    "vander", "take",
]


def _norm_shape(shape):
    if isinstance(shape, Tensor):
        return tuple(int(v) for v in np.asarray(shape._data))
    out = []
    for s in shape:
        if isinstance(s, Tensor):
            out.append(int(s.item()))
        elif isinstance(s, (int, np.integer)):
            out.append(int(s))
        else:
            # symbolic dim (jax.export shape polymorphism) — pass through
            out.append(s)
    return tuple(out)


def reshape(x, shape, name=None):
    x = as_tensor(x)
    shape = _norm_shape(shape)
    return apply(lambda a: jnp.reshape(a, shape), x, name="reshape")


def reshape_(x, shape, name=None):
    return tape_rebind(x, reshape(tape_alias(x), shape))


def view(x, shape_or_dtype, name=None):
    if isinstance(shape_or_dtype, (list, tuple)):
        return reshape(x, shape_or_dtype)
    # dtype view is a BITCAST (paddle Tensor.view(dtype) reinterprets the
    # bytes), not a value cast; element count rescales by the width ratio
    x = as_tensor(x)
    jd = to_jax_dtype(shape_or_dtype)
    src_size = jnp.dtype(x.dtype).itemsize
    dst_size = jnp.dtype(jd).itemsize

    def fn(a):
        if src_size == dst_size:
            return jax.lax.bitcast_convert_type(a, jd)
        if src_size > dst_size:  # narrowing adds a trailing axis; fold it
            out = jax.lax.bitcast_convert_type(a, jd)
            return out.reshape(a.shape[:-1] +
                               (a.shape[-1] * (src_size // dst_size),))
        ratio = dst_size // src_size
        if a.shape[-1] % ratio:
            raise ValueError(
                f"view({jd}): last dim {a.shape[-1]} not divisible by "
                f"width ratio {ratio}")
        out = a.reshape(a.shape[:-1] + (a.shape[-1] // ratio, ratio))
        return jax.lax.bitcast_convert_type(out, jd)
    return apply(fn, x, name="view", differentiable=False)


def view_as(x, other, name=None):
    return reshape(x, other.shape)


def transpose(x, perm=None, name=None):
    x = as_tensor(x)
    if perm is None:
        perm = list(range(x.ndim))[::-1]
    perm = [int(p) for p in perm]
    return apply(lambda a: jnp.transpose(a, perm), x, name="transpose")


def moveaxis(x, source, destination, name=None):
    return apply(lambda a: jnp.moveaxis(a, source, destination), as_tensor(x),
                 name="moveaxis")


def swapaxes(x, axis0, axis1, name=None):
    return apply(lambda a: jnp.swapaxes(a, axis0, axis1), as_tensor(x),
                 name="swapaxes")


def rot90(x, k=1, axes=(0, 1), name=None):
    return apply(lambda a: jnp.rot90(a, k=k, axes=tuple(axes)), as_tensor(x),
                 name="rot90")


def cast(x, dtype, name=None):
    x = as_tensor(x)
    jd = to_jax_dtype(dtype)
    return apply(lambda a: a.astype(jd), x, name="cast")


def concat(x, axis=0, name=None):
    ts = [as_tensor(t) for t in x]
    if isinstance(axis, Tensor):
        axis = int(axis.item())
    return apply(lambda *xs: jnp.concatenate(xs, axis=int(axis)), *ts,
                 name="concat")


def stack(x, axis=0, name=None):
    ts = [as_tensor(t) for t in x]
    return apply(lambda *xs: jnp.stack(xs, axis=int(axis)), *ts, name="stack")


def split(x, num_or_sections, axis=0, name=None):
    x = as_tensor(x)
    if isinstance(axis, Tensor):
        axis = int(axis.item())
    axis = int(axis)
    dim = x.shape[axis]
    if isinstance(num_or_sections, int):
        n = num_or_sections
        if dim % n != 0:
            raise ValueError(
                f"split: dimension {axis} of size {dim} is not divisible "
                f"by num_or_sections={n}")
        sizes = [dim // n] * n
    else:
        sizes = [int(s.item()) if isinstance(s, Tensor) else int(s)
                 for s in num_or_sections]
        n_unknown = builtins.sum(1 for s in sizes if s in (-1,))
        if n_unknown:
            known = builtins.sum(s for s in sizes if s != -1)
            sizes = [dim - known if s == -1 else s for s in sizes]
    offsets = np.cumsum([0] + sizes)

    def fn(a):
        return tuple(jax.lax.slice_in_dim(a, int(offsets[i]),
                                          int(offsets[i + 1]), axis=axis)
                     for i in range(len(sizes)))
    outs = apply(fn, x, n_outputs=len(sizes), name="split")
    return list(outs)


split_sections = split


def chunk(x, chunks, axis=0, name=None):
    return split(x, chunks, axis)


def _norm_axes(axis, ndim):
    if axis is None:
        return None
    if isinstance(axis, Tensor):
        axis = axis.tolist()
    if isinstance(axis, (int, np.integer)):
        axis = [axis]
    return tuple(int(a) % ndim if int(a) >= 0 else int(a) for a in axis)


def squeeze(x, axis=None, name=None):
    x = as_tensor(x)
    ax = _norm_axes(axis, x.ndim)
    if ax is not None:
        ax = tuple(a for a in ax if x.shape[a] == 1)
        if not ax:
            return apply(lambda a: a, x, name="squeeze")
    return apply(lambda a: jnp.squeeze(a, axis=ax), x, name="squeeze")


def squeeze_(x, axis=None, name=None):
    return tape_rebind(x, squeeze(tape_alias(x), axis))


def unsqueeze(x, axis, name=None):
    x = as_tensor(x)
    if isinstance(axis, Tensor):
        axis = axis.tolist()
    axes = [axis] if isinstance(axis, (int, np.integer)) else list(axis)
    axes = [int(a) for a in axes]

    def fn(a):
        out = a
        for ax in axes:
            out = jnp.expand_dims(out, ax)
        return out
    return apply(fn, x, name="unsqueeze")


def unsqueeze_(x, axis, name=None):
    return tape_rebind(x, unsqueeze(tape_alias(x), axis))


def flatten(x, start_axis=0, stop_axis=-1, name=None):
    x = as_tensor(x)
    nd = x.ndim
    s = start_axis % nd if nd else 0
    e = stop_axis % nd if nd else 0
    new_shape = x.shape[:s] + [-1] + x.shape[e + 1:]
    if nd == 0:
        new_shape = [1]
    return apply(lambda a: jnp.reshape(a, new_shape), x, name="flatten")


def flatten_(x, start_axis=0, stop_axis=-1, name=None):
    return tape_rebind(x, flatten(tape_alias(x), start_axis, stop_axis))


def flip(x, axis, name=None):
    if isinstance(axis, (int, np.integer)):
        axis = [axis]
    axis = tuple(int(a) for a in axis)
    return apply(lambda a: jnp.flip(a, axis=axis), as_tensor(x), name="flip")


def roll(x, shifts, axis=None, name=None):
    if isinstance(shifts, Tensor):
        shifts = shifts.tolist()
    return apply(lambda a: jnp.roll(a, shifts, axis=axis), as_tensor(x),
                 name="roll")


def tile(x, repeat_times, name=None):
    repeat_times = _norm_shape(repeat_times)
    return apply(lambda a: jnp.tile(a, repeat_times), as_tensor(x),
                 name="tile")


def expand(x, shape, name=None):
    x = as_tensor(x)
    shape = _norm_shape(shape)
    tgt = []
    xshape = ([1] * (len(shape) - x.ndim)) + x.shape
    for s, xs in zip(shape, xshape):
        tgt.append(xs if s == -1 else s)
    return apply(lambda a: jnp.broadcast_to(a, tuple(tgt)), x, name="expand")


def expand_as(x, y, name=None):
    return expand(x, y.shape)


def broadcast_to(x, shape, name=None):
    return expand(x, shape)


def broadcast_tensors(inputs, name=None):
    ts = [as_tensor(t) for t in inputs]
    outs = apply(lambda *xs: tuple(jnp.broadcast_arrays(*xs)), *ts,
                 n_outputs=len(ts), name="broadcast_tensors")
    return list(outs)


def gather(x, index, axis=0, name=None):
    x, index = as_tensor(x), as_tensor(index)
    if isinstance(axis, Tensor):
        axis = int(axis.item())

    def fn(a, idx):
        return jnp.take(a, idx.reshape(-1) if idx.ndim > 1 else idx,
                        axis=int(axis))
    return apply(fn, x, index, name="gather")


def gather_nd(x, index, name=None):
    x, index = as_tensor(x), as_tensor(index)

    def fn(a, idx):
        k = idx.shape[-1]
        return a[tuple(jnp.moveaxis(idx, -1, 0))] if k == a.ndim else \
            a[tuple(jnp.moveaxis(idx, -1, 0))]
    return apply(fn, x, index, name="gather_nd")


def scatter(x, index, updates, overwrite=True, name=None):
    x, index, updates = as_tensor(x), as_tensor(index), as_tensor(updates)

    def fn(a, idx, upd):
        idx = idx.reshape(-1)
        if overwrite:
            return a.at[idx].set(upd)
        return a.at[idx].add(upd)
    return apply(fn, x, index, updates, name="scatter")


def scatter_nd(index, updates, shape, name=None):
    index, updates = as_tensor(index), as_tensor(updates)
    shape = _norm_shape(shape)

    def fn(idx, upd):
        zeros = jnp.zeros(shape, upd.dtype)
        return zeros.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)
    return apply(fn, index, updates, name="scatter_nd")


def scatter_nd_add(x, index, updates, name=None):
    x, index, updates = as_tensor(x), as_tensor(index), as_tensor(updates)

    def fn(a, idx, upd):
        return a.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)
    return apply(fn, x, index, updates, name="scatter_nd_add")


def index_select(x, index, axis=0, name=None):
    x, index = as_tensor(x), as_tensor(index)
    return apply(lambda a, i: jnp.take(a, i, axis=int(axis)), x, index,
                 name="index_select")


def index_sample(x, index, name=None):
    x, index = as_tensor(x), as_tensor(index)
    return apply(lambda a, i: jnp.take_along_axis(a, i, axis=1), x, index,
                 name="index_sample")


def index_add(x, index, axis, value, name=None):
    x, index, value = as_tensor(x), as_tensor(index), as_tensor(value)

    def fn(a, i, v):
        am = jnp.moveaxis(a, int(axis), 0)
        vm = jnp.moveaxis(v, int(axis), 0)
        return jnp.moveaxis(am.at[i].add(vm), 0, int(axis))
    return apply(fn, x, index, value, name="index_add")


def index_put(x, indices, value, accumulate=False, name=None):
    x = as_tensor(x)
    value = as_tensor(value)
    idx_ts = [as_tensor(i) for i in indices]

    def fn(a, v, *idx):
        if accumulate:
            return a.at[tuple(idx)].add(v)
        return a.at[tuple(idx)].set(v)
    return apply(fn, x, value, *idx_ts, name="index_put")


def take_along_axis(arr, indices, axis, broadcast=True, name=None):
    arr, indices = as_tensor(arr), as_tensor(indices)
    return apply(lambda a, i: jnp.take_along_axis(a, i, axis=int(axis)),
                 arr, indices, name="take_along_axis")


def put_along_axis(arr, indices, values, axis, reduce="assign",
                   include_self=True, broadcast=True, name=None):
    arr, indices = as_tensor(arr), as_tensor(indices)
    values = as_tensor(values)

    def fn(a, i, v):
        v = jnp.broadcast_to(v, i.shape) if broadcast else v
        if reduce == "add":
            return jnp.put_along_axis(
                a, i, jnp.take_along_axis(a, i, axis=int(axis)) + v,
                axis=int(axis), inplace=False)
        if reduce in ("mul", "multiply"):
            return _pala(a, i, jnp.take_along_axis(a, i, axis=int(axis)) * v,
                         int(axis), "assign")
        return _pala(a, i, v, int(axis), "assign")
    return apply(fn, arr, indices, values, name="put_along_axis")


def _pala(a, i, v, axis, mode):
    am = jnp.moveaxis(a, axis, 0)
    im = jnp.moveaxis(i, axis, 0)
    vm = jnp.moveaxis(jnp.broadcast_to(v, i.shape), axis, 0)
    grid = jnp.indices(im.shape)
    idx = (im,) + tuple(grid[k] for k in range(1, im.ndim))
    if mode == "add":
        out = am.at[idx].add(vm)
    else:
        out = am.at[idx].set(vm)
    return jnp.moveaxis(out, 0, axis)


def slice(input, axes, starts, ends, name=None):
    input = as_tensor(input)
    starts = [int(s.item()) if isinstance(s, Tensor) else int(s) for s in starts]
    ends = [int(e.item()) if isinstance(e, Tensor) else int(e) for e in ends]

    def fn(a):
        idx = [jnp.s_[:]] * a.ndim
        for ax, s, e in zip(axes, starts, ends):
            idx[ax] = jnp.s_[s:e]
        return a[tuple(idx)]
    return apply(fn, input, name="slice")


def strided_slice(x, axes, starts, ends, strides, name=None):
    x = as_tensor(x)

    def fn(a):
        idx = [jnp.s_[:]] * a.ndim
        for ax, s, e, st in zip(axes, starts, ends, strides):
            idx[ax] = jnp.s_[s:e:st]
        return a[tuple(idx)]
    return apply(fn, x, name="strided_slice")


def as_strided(x, shape, stride, offset=0, name=None):
    x = as_tensor(x)

    def fn(a):
        flat = a.reshape(-1)
        idx = np.zeros(tuple(shape), dtype=np.int64) + offset
        for d, (sh, st) in enumerate(zip(shape, stride)):
            ix = np.arange(sh) * st
            idx += ix.reshape([-1 if i == d else 1 for i in range(len(shape))])
        return flat[jnp.asarray(idx)]
    return apply(fn, x, name="as_strided")


def unbind(input, axis=0, name=None):
    input = as_tensor(input)
    n = input.shape[int(axis)]

    def fn(a):
        return tuple(jnp.squeeze(s, axis=int(axis))
                     for s in jnp.split(a, n, axis=int(axis)))
    return list(apply(fn, input, n_outputs=n, name="unbind"))


unstack = unbind


def tensordot(x, y, axes=2, name=None):
    def _conv(ax):
        if isinstance(ax, Tensor):
            return ax.tolist()
        return ax
    return apply(lambda a, b: jnp.tensordot(a, b, axes=_conv(axes)),
                 as_tensor(x), as_tensor(y), name="tensordot")


def repeat_interleave(x, repeats, axis=None, name=None):
    x = as_tensor(x)
    if isinstance(repeats, Tensor):
        # per-element repeats produce a data-dependent output shape; the
        # total must be concrete (jnp.repeat needs total_repeat_length
        # under tracing, which we cannot know) — eager-only, like paddle's
        # dynamic-shape ops under to_static (graph break)
        if isinstance(repeats._data, jax.core.Tracer):
            raise jax.errors.ConcretizationTypeError(
                repeats._data,
                "repeat_interleave with tensor repeats has a data-dependent "
                "output shape and cannot be traced; it falls back to eager "
                "under to_static")
        total = int(np.asarray(repeats._data).sum())
        return apply(lambda a, r: jnp.repeat(
            a, r, axis=axis, total_repeat_length=total),
            x, repeats, name="repeat_interleave")
    return apply(lambda a: jnp.repeat(a, repeats, axis=axis), x,
                 name="repeat_interleave")


def masked_select(x, mask, name=None):
    x, mask = as_tensor(x), as_tensor(mask)
    # dynamic shape: materialize on host (eager-only op, like paddle's)
    data = np.asarray(x._data)[np.asarray(mask._data)]
    return Tensor(jnp.asarray(data))


def masked_fill(x, mask, value, name=None):
    x, mask = as_tensor(x), as_tensor(mask)
    if isinstance(value, Tensor):
        return apply(lambda a, m, v: jnp.where(m, v.astype(a.dtype), a),
                     x, mask, value, name="masked_fill")
    return apply(lambda a, m: jnp.where(m, jnp.asarray(value, a.dtype), a),
                 x, mask, name="masked_fill")


def masked_scatter(x, mask, value, name=None):
    x, mask, value = as_tensor(x), as_tensor(mask), as_tensor(value)
    xd, md, vd = (np.asarray(t._data) for t in (x, mask, value))
    out = xd.copy()
    out[md] = vd.reshape(-1)[: int(md.sum())]
    return Tensor(jnp.asarray(out))


def unique(x, return_index=False, return_inverse=False, return_counts=False,
           axis=None, dtype="int64", name=None):
    x = as_tensor(x)
    res = np.unique(np.asarray(x._data), return_index=return_index,
                    return_inverse=return_inverse,
                    return_counts=return_counts, axis=axis)
    if not (return_index or return_inverse or return_counts):
        return Tensor(jnp.asarray(res))
    outs = [Tensor(jnp.asarray(r)) for r in res]
    return tuple(outs)


def unique_consecutive(x, return_inverse=False, return_counts=False,
                       axis=None, dtype="int64", name=None):
    x = np.asarray(as_tensor(x)._data)
    if axis is None:
        x = x.reshape(-1)
    keep = np.ones(x.shape[0], dtype=bool)
    keep[1:] = np.any(x[1:] != x[:-1], axis=tuple(range(1, x.ndim))) \
        if x.ndim > 1 else x[1:] != x[:-1]
    out = [Tensor(jnp.asarray(x[keep]))]
    if return_inverse:
        inv = np.cumsum(keep) - 1
        out.append(Tensor(jnp.asarray(inv.astype(np.int64))))
    if return_counts:
        idx = np.flatnonzero(keep)
        counts = np.diff(np.append(idx, x.shape[0]))
        out.append(Tensor(jnp.asarray(counts.astype(np.int64))))
    return out[0] if len(out) == 1 else tuple(out)


def crop(x, shape=None, offsets=None, name=None):
    x = as_tensor(x)
    shape = _norm_shape(shape)
    offsets = [0] * x.ndim if offsets is None else \
        [int(o.item()) if isinstance(o, Tensor) else int(o) for o in offsets]
    shape = [x.shape[i] - offsets[i] if s == -1 else s
             for i, s in enumerate(shape)]

    def fn(a):
        return jax.lax.slice(a, offsets,
                             [o + s for o, s in zip(offsets, shape)])
    return apply(fn, x, name="crop")


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    x = as_tensor(x)
    if isinstance(pad, Tensor):
        pad = pad.tolist()
    pad = [int(p) for p in pad]

    def fn(a):
        nd = a.ndim
        if len(pad) == 2 * nd:
            # full-rank paddle format: per-dim (before, after), dim order
            widths = [(pad[2 * i], pad[2 * i + 1]) for i in range(nd)]
        else:
            # partial spec applies to trailing spatial dims, reversed pairs
            k = len(pad) // 2
            widths = [(0, 0)] * nd
            if data_format.endswith("C") and nd >= 3:  # NHWC-style
                spatial = list(range(1, nd - 1))[-k:]
            else:
                spatial = list(range(nd))[-k:]
            for j, d in enumerate(reversed(spatial)):
                widths[d] = (pad[2 * j], pad[2 * j + 1])
        jmode = {"constant": "constant", "reflect": "reflect",
                 "replicate": "edge", "circular": "wrap"}[mode]
        if jmode == "constant":
            return jnp.pad(a, widths, mode=jmode, constant_values=value)
        return jnp.pad(a, widths, mode=jmode)
    return apply(fn, x, name="pad")


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    input = as_tensor(input)
    size = index_num // nshards

    def fn(a):
        shard = a // size
        return jnp.where(shard == shard_id, a % size, ignore_value)
    return apply(fn, input, name="shard_index")


def atleast_1d(*inputs, name=None):
    outs = [apply(jnp.atleast_1d, as_tensor(x), name="atleast_1d")
            for x in inputs]
    return outs[0] if len(outs) == 1 else outs


def atleast_2d(*inputs, name=None):
    outs = [apply(jnp.atleast_2d, as_tensor(x), name="atleast_2d")
            for x in inputs]
    return outs[0] if len(outs) == 1 else outs


def atleast_3d(*inputs, name=None):
    outs = [apply(jnp.atleast_3d, as_tensor(x), name="atleast_3d")
            for x in inputs]
    return outs[0] if len(outs) == 1 else outs


def diff(x, n=1, axis=-1, prepend=None, append=None, name=None):
    args = [as_tensor(x)]
    if prepend is not None:
        args.append(as_tensor(prepend))
    if append is not None:
        args.append(as_tensor(append))

    def fn(a, *rest):
        i = 0
        pre = app = None
        if prepend is not None:
            pre = rest[i]; i += 1
        if append is not None:
            app = rest[i]
        return jnp.diff(a, n=n, axis=axis, prepend=pre, append=app)
    return apply(fn, *args, name="diff")


def clone(x, name=None):
    from .creation import clone as _clone
    return _clone(x)


def tolist(x):
    return as_tensor(x).tolist()


# ---- split/stack family long tail -----------------------------------------

def tensor_split(x, num_or_indices, axis=0, name=None):
    """paddle.tensor_split: uneven splits allowed (numpy array_split)."""
    x = as_tensor(x)
    axis = int(axis)
    if isinstance(num_or_indices, int):
        n = num_or_indices
        size = x.shape[axis]
        base, extra = divmod(size, n)
        sizes = [base + (1 if i < extra else 0) for i in range(n)]
        bounds = np.cumsum(sizes)[:-1].tolist()
    else:
        bounds = [int(i) for i in num_or_indices]
    outs = apply(lambda a: tuple(jnp.split(a, bounds, axis=axis)), x,
                 n_outputs=len(bounds) + 1, name="tensor_split")
    return list(outs)


def hsplit(x, num_or_indices, name=None):
    x = as_tensor(x)
    if x.ndim < 1:
        raise ValueError("hsplit expects at least a 1-D tensor")
    return tensor_split(x, num_or_indices, axis=0 if x.ndim == 1 else 1)


def vsplit(x, num_or_indices, name=None):
    x = as_tensor(x)
    if x.ndim < 2:
        raise ValueError("vsplit expects at least a 2-D tensor")
    return tensor_split(x, num_or_indices, axis=0)


def dsplit(x, num_or_indices, name=None):
    x = as_tensor(x)
    if x.ndim < 3:
        raise ValueError("dsplit expects at least a 3-D tensor")
    return tensor_split(x, num_or_indices, axis=2)


def hstack(x, name=None):
    ts = [atleast_1d(as_tensor(t)) for t in x]
    axis = 0 if ts[0].ndim <= 1 else 1
    return concat(ts, axis=axis)


def vstack(x, name=None):
    ts = [atleast_2d(as_tensor(t)) for t in x]
    return concat(ts, axis=0)


row_stack = vstack


def dstack(x, name=None):
    ts = [atleast_3d(as_tensor(t)) for t in x]
    return concat(ts, axis=2)


def column_stack(x, name=None):
    ts = []
    for t in x:
        t = as_tensor(t)
        if t.ndim <= 1:
            t = reshape(t, [-1, 1])
        ts.append(t)
    return concat(ts, axis=1)


def unflatten(x, axis, shape, name=None):
    x = as_tensor(x)
    axis = int(axis) % max(x.ndim, 1)
    shape = _norm_shape(shape)
    new_shape = list(x.shape[:axis]) + list(shape) + list(x.shape[axis + 1:])
    return reshape(x, new_shape)


def unfold(x, axis, size, step, name=None):
    """Sliding windows along ``axis`` (paddle.Tensor.unfold): output gains
    a trailing window dim of length ``size``."""
    x = as_tensor(x)
    axis = int(axis) % x.ndim
    n = (x.shape[axis] - int(size)) // int(step) + 1

    def fn(a):
        idx = (np.arange(n)[:, None] * int(step) +
               np.arange(int(size))[None, :])
        win = jnp.take(a, jnp.asarray(idx.reshape(-1)), axis=axis)
        win = jnp.reshape(
            win, a.shape[:axis] + (n, int(size)) + a.shape[axis + 1:])
        return jnp.moveaxis(win, axis + 1, -1)
    return apply(fn, x, name="unfold")


# ---- complex views ---------------------------------------------------------

def as_complex(x, name=None):
    """[..., 2] float -> [...] complex (paddle.as_complex)."""
    x = as_tensor(x)
    return apply(lambda a: jax.lax.complex(a[..., 0], a[..., 1]), x,
                 name="as_complex")


def as_real(x, name=None):
    x = as_tensor(x)
    return apply(lambda a: jnp.stack([jnp.real(a), jnp.imag(a)], axis=-1),
                 x, name="as_real")


# ---- diagonal / scatter-style writes --------------------------------------

def diag_embed(input, offset=0, dim1=-2, dim2=-1, name=None):
    x = as_tensor(input)

    def fn(a):
        n = a.shape[-1] + builtins.abs(int(offset))
        nd = a.ndim + 1
        d1, d2 = int(dim1) % nd, int(dim2) % nd
        base = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
        ii = jnp.arange(a.shape[-1])
        rows = ii + builtins.max(-int(offset), 0)
        cols = ii + builtins.max(int(offset), 0)
        base = base.at[..., rows, cols].set(a)
        # embedded plane currently at (-2, -1); move to (dim1, dim2)
        perm = [i for i in range(nd) if i not in (d1, d2)]
        out_axes = sorted((d1, d2))
        full = list(range(nd - 2)) + [nd - 2, nd - 1]
        dest = perm + [d1, d2]
        inv = [0] * nd
        for src, dst in zip(full, dest):
            inv[dst] = src
        return jnp.transpose(base, inv)
    return apply(fn, x, name="diag_embed")


def _diag_len(rows, cols, offset):
    """Number of elements on diagonal ``offset`` of a (rows, cols) plane."""
    if offset >= 0:
        return builtins.max(builtins.min(rows, cols - offset), 0)
    return builtins.max(builtins.min(rows + offset, cols), 0)


def fill_diagonal_(x, value, offset=0, wrap=False, name=None):
    def fn(a):
        if a.ndim == 2:
            off = int(offset)
            if wrap and a.shape[0] > a.shape[1] and off == 0:
                # tall matrices: the diagonal restarts every cols+1 rows
                per = a.shape[1] + 1
                r = np.arange(a.shape[0])
                c = r % per
                keep = c < a.shape[1]
                r, c = r[keep], c[keep]
            else:
                n = _diag_len(a.shape[0], a.shape[1], off)
                ii = np.arange(n)
                r = ii + builtins.max(-off, 0)
                c = ii + builtins.max(off, 0)
            return a.at[r, c].set(jnp.asarray(value, a.dtype))
        # ndim > 2: paddle/torch fill the main HYPER-diagonal
        # x[i, i, ..., i] (all dims must be equal, offset 0)
        if int(offset) != 0:
            raise ValueError(
                "fill_diagonal_: offset must be 0 for ndim > 2")
        if builtins.len(set(a.shape)) != 1:
            raise ValueError(
                "fill_diagonal_: all dimensions must be equal for "
                f"ndim > 2, got {a.shape}")
        ii = jnp.arange(a.shape[0])
        return a.at[(ii,) * a.ndim].set(jnp.asarray(value, a.dtype))
    out = apply(fn, tape_alias(x), name="fill_diagonal_")
    return tape_rebind(x, out)


def fill_diagonal_tensor(x, y, offset=0, dim1=0, dim2=1, name=None):
    x, y = as_tensor(x), as_tensor(y)

    def fn(a, b):
        d1, d2 = int(dim1) % a.ndim, int(dim2) % a.ndim
        off = int(offset)
        moved = jnp.moveaxis(a, (d1, d2), (-2, -1))
        n = _diag_len(moved.shape[-2], moved.shape[-1], off)
        ii = jnp.arange(n)
        rows = ii + builtins.max(-off, 0)
        cols = ii + builtins.max(off, 0)
        moved = moved.at[..., rows, cols].set(b)   # b: [..., n]
        return jnp.moveaxis(moved, (-2, -1), (d1, d2))
    return apply(fn, x, y, name="fill_diagonal_tensor")


def fill_diagonal_tensor_(x, y, offset=0, dim1=0, dim2=1, name=None):
    return tape_rebind(x, fill_diagonal_tensor(tape_alias(x), y, offset,
                                               dim1, dim2))


def select_scatter(x, values, axis, index, name=None):
    x, v = as_tensor(x), as_tensor(values)
    axis_i, idx = int(axis), int(index)
    return apply(
        lambda a, b: a.at[(np.s_[:],) * (axis_i % a.ndim) + (idx,)].set(
            b.astype(a.dtype)),
        x, v, name="select_scatter")


def slice_scatter(x, value, axes, starts, ends, strides, name=None):
    x, v = as_tensor(x), as_tensor(value)

    def fn(a, b):
        sl = [np.s_[:]] * a.ndim
        for ax, s, e, st in zip(axes, starts, ends, strides):
            sl[int(ax)] = np.s_[int(s):int(e):int(st)]
        return a.at[tuple(sl)].set(b.astype(a.dtype))
    return apply(fn, x, v, name="slice_scatter")


def index_fill(x, index, axis, value, name=None):
    x = as_tensor(x)
    index = as_tensor(index)

    def fn(a, idx):
        moved = jnp.moveaxis(a, int(axis), 0)
        moved = moved.at[idx].set(jnp.asarray(value, a.dtype))
        return jnp.moveaxis(moved, 0, int(axis))
    return apply(fn, x, index, name="index_fill")


def index_fill_(x, index, axis, value, name=None):
    return tape_rebind(x, index_fill(tape_alias(x), index, axis, value))


def masked_fill_(x, mask, value, name=None):
    return tape_rebind(x, masked_fill(tape_alias(x), mask, value))


def masked_scatter_(x, mask, value, name=None):
    return tape_rebind(x, masked_scatter(tape_alias(x), mask, value))


# ---- combinatoric constructors --------------------------------------------

def block_diag(inputs, name=None):
    ts = [as_tensor(t) for t in inputs]

    def fn(*arrs):
        arrs = [jnp.atleast_2d(a) for a in arrs]
        rows = builtins.sum(a.shape[0] for a in arrs)
        cols = builtins.sum(a.shape[1] for a in arrs)
        out = jnp.zeros((rows, cols), arrs[0].dtype)
        r = c = 0
        for a in arrs:
            out = out.at[r:r + a.shape[0], c:c + a.shape[1]].set(a)
            r += a.shape[0]
            c += a.shape[1]
        return out
    return apply(fn, *ts, name="block_diag")


def cartesian_prod(x, name=None):
    ts = [as_tensor(t) for t in x]

    def fn(*arrs):
        grids = jnp.meshgrid(*arrs, indexing="ij")
        return jnp.stack([g.reshape(-1) for g in grids], axis=-1)
    out = apply(fn, *ts, name="cartesian_prod")
    if len(ts) == 1:
        return reshape(out, [-1])
    return out


def combinations(x, r=2, with_replacement=False, name=None):
    import itertools
    x = as_tensor(x)
    n = x.shape[0]
    gen = (itertools.combinations_with_replacement if with_replacement
           else itertools.combinations)
    idx = np.asarray(list(gen(range(n), int(r))), dtype=np.int64)
    if idx.size == 0:
        idx = idx.reshape(0, int(r))
    return apply(lambda a: jnp.take(a, jnp.asarray(idx), axis=0), x,
                 name="combinations")


def vander(x, n=None, increasing=False, name=None):
    x = as_tensor(x)
    num = x.shape[0] if n is None else int(n)
    return apply(lambda a: jnp.vander(a, num, increasing=increasing), x,
                 name="vander")


def take(x, index, mode="raise", name=None):
    """Flat-index gather with paddle's mode semantics ('raise', 'wrap',
    'clip'). mode='raise' validates eagerly when the index is concrete;
    under tracing (where raising is impossible) it clips like
    numpy-on-device."""
    x = as_tensor(x)
    index = as_tensor(index)
    if mode == "raise" and not isinstance(index._data, jax.core.Tracer):
        size = 1
        for s in x.shape:
            size *= int(s)
        idx_np = np.asarray(index._data)
        if idx_np.size and (int(idx_np.min()) < -size
                            or int(idx_np.max()) >= size):
            raise IndexError(
                f"paddle.take(mode='raise'): index out of range for "
                f"input with {size} elements "
                f"(min {int(idx_np.min())}, max {int(idx_np.max())})")

    def fn(a, idx):
        flat = a.reshape(-1)
        size = flat.shape[0]
        if mode == "wrap":
            idx = ((idx % size) + size) % size
        else:
            idx = jnp.where(idx < 0, idx + size, idx)
            idx = jnp.clip(idx, 0, size - 1)
        return jnp.take(flat, idx)
    return apply(fn, x, index, name="take")


def diagonal_scatter(x, y, offset=0, axis1=0, axis2=1, name=None):
    """Write y onto the selected diagonal of x (paddle.diagonal_scatter)."""
    x, y = as_tensor(x), as_tensor(y)
    return fill_diagonal_tensor(x, y, offset=offset, dim1=axis1, dim2=axis2)


def broadcast_shape(x_shape, y_shape):
    return list(np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


__all__ += ["diagonal_scatter", "broadcast_shape"]


def shape(input, name=None):
    """paddle.shape — the runtime shape as an int32 tensor (static under
    XLA, so this is a constant in compiled programs)."""
    x = as_tensor(input)
    from .creation import to_tensor
    return to_tensor(np.asarray(x._data.shape, np.int32))


__all__ += ["shape"]
