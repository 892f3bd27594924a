"""Normalization functionals (python/paddle/nn/functional/norm.py parity,
UNVERIFIED). ``rms_norm``/``layer_norm`` route to Pallas kernels on TPU when
enabled (SURVEY.md §2.1 PHI fused kernels → Pallas)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply
from ...framework import flags
from ...ops.common import as_tensor

__all__ = ["layer_norm", "batch_norm", "instance_norm", "group_norm",
           "spectral_norm",
           "local_response_norm", "rms_norm", "fused_rms_norm_residual"]


def _pallas_mesh():
    """``(use the Pallas kernel, fleet mesh to shard it over or None)``
    — platform + flag, then the placement rule of ops/pallas/_mesh.py."""
    if not (flags.flag("FLAGS_enable_pallas_kernels")
            and jax.default_backend() == "tpu"):
        return False, None
    from ...ops.pallas._mesh import kernel_placement
    return kernel_placement()


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = as_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))

    args = [x]
    if weight is not None:
        args.append(as_tensor(weight))
    if bias is not None:
        args.append(as_tensor(bias))

    def fn(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out
    return apply(fn, *args, name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — fused Pallas kernel on TPU, jnp fallback elsewhere."""
    x = as_tensor(x)
    if weight is not None:
        w = as_tensor(weight)
        use_pallas, mesh = _pallas_mesh()
        if use_pallas:
            from ...ops.pallas import rms_norm as pallas_rms
            from ...ops.pallas._mesh import sharded_rows
            return apply(
                lambda a, ww: sharded_rows(
                    lambda a_, w_: pallas_rms.rms_norm(a_, w_, epsilon),
                    mesh, a, replicated=(ww,)),
                x, w, name="rms_norm")

        def fn(a, ww):
            dt = a.dtype
            af = a.astype(jnp.float32)
            ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
            return (af * jax.lax.rsqrt(ms + epsilon)).astype(dt) * ww
        return apply(fn, x, w, name="rms_norm")

    def fn(a):
        dt = a.dtype
        af = a.astype(jnp.float32)
        ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
        return (af * jax.lax.rsqrt(ms + epsilon)).astype(dt)
    return apply(fn, x, name="rms_norm")


def fused_rms_norm_residual(x, residual, weight, epsilon=1e-6, name=None):
    """``(rms_norm(x + residual) * weight, x + residual)`` — the
    decoder-layer residual-add + norm pair as ONE op: the fused Pallas
    kernel on TPU (ops/pallas/rms_norm.rms_norm_residual, one VMEM
    pass for both outputs, fused dx/dresidual backward), and the
    identical-math jnp pairing elsewhere (the add happens in the input
    dtype, then the f32 norm — bit-parity with the unfused
    ``x + residual`` followed by :func:`rms_norm`)."""
    x, r, w = as_tensor(x), as_tensor(residual), as_tensor(weight)
    from ...ops.pallas import rms_norm as pallas_rms
    use_pallas, mesh = _pallas_mesh()
    if use_pallas:
        from ...ops.pallas._mesh import sharded_rows
        return apply(
            lambda a, b, ww: sharded_rows(
                lambda a_, b_, w_: pallas_rms.rms_norm_residual(
                    a_, b_, w_, epsilon),
                mesh, a, b, replicated=(ww,), n_out=2),
            x, r, w, n_outputs=2, name="fused_rms_norm_residual")
    # the SAME oracle the interpret-mode parity tests pin the kernel to
    # — one source of truth for the fallback math
    return apply(
        lambda a, b, ww: pallas_rms.rms_norm_residual_reference(
            a, b, ww, epsilon),
        x, r, w, n_outputs=2, name="fused_rms_norm_residual")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    x = as_tensor(x)
    ch_axis = x.ndim - 1 if data_format[-1] == "C" and x.ndim > 2 else 1
    if x.ndim == 2:
        ch_axis = 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        # update running stats eagerly (buffer mutation, like paddle)
        xf = x._data.astype(jnp.float32)
        batch_mean = jnp.mean(xf, axis=reduce_axes)
        batch_var = jnp.var(xf, axis=reduce_axes)
        if running_mean is not None:
            running_mean.set_data(
                (momentum * running_mean._data.astype(jnp.float32)
                 + (1 - momentum) * batch_mean).astype(running_mean.dtype))
            running_var.set_data(
                (momentum * running_var._data.astype(jnp.float32)
                 + (1 - momentum) * batch_var).astype(running_var.dtype))

        def fn(a, *wb):
            af = a.astype(jnp.float32)
            m = jnp.mean(af, axis=reduce_axes, keepdims=True)
            v = jnp.var(af, axis=reduce_axes, keepdims=True)
            out = (af - m) * jax.lax.rsqrt(v + epsilon)
            out = out.astype(a.dtype)
            return _affine(out, wb, ch_axis, weight, bias)
        args = [x] + _wb_args(weight, bias)
        return apply(fn, *args, name="batch_norm")

    rm, rv = as_tensor(running_mean), as_tensor(running_var)

    def fn(a, m, v, *wb):
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        out = (a.astype(jnp.float32) - m.astype(jnp.float32).reshape(shape)) \
            * jax.lax.rsqrt(v.astype(jnp.float32).reshape(shape) + epsilon)
        out = out.astype(a.dtype)
        return _affine(out, wb, ch_axis, weight, bias)
    args = [x, rm, rv] + _wb_args(weight, bias)
    return apply(fn, *args, name="batch_norm")


def _wb_args(weight, bias):
    out = []
    if weight is not None:
        out.append(as_tensor(weight))
    if bias is not None:
        out.append(as_tensor(bias))
    return out


def _affine(out, wb, ch_axis, weight, bias):
    shape = [1] * out.ndim
    shape[ch_axis] = out.shape[ch_axis]
    i = 0
    if weight is not None:
        out = out * wb[i].reshape(shape)
        i += 1
    if bias is not None:
        out = out + wb[i].reshape(shape)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  epsilon=1e-05, data_format="NCHW", name=None):
    x = as_tensor(x)
    ch_axis = 1
    reduce_axes = tuple(range(2, x.ndim))

    def fn(a, *wb):
        af = a.astype(jnp.float32)
        m = jnp.mean(af, axis=reduce_axes, keepdims=True)
        v = jnp.var(af, axis=reduce_axes, keepdims=True)
        out = ((af - m) * jax.lax.rsqrt(v + epsilon)).astype(a.dtype)
        return _affine(out, wb, ch_axis, weight, bias)
    args = [x] + _wb_args(weight, bias)
    return apply(fn, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = as_tensor(x)
    channel_last = data_format[-1] == "C" and x.ndim > 2

    def fn(a, *wb):
        if channel_last:
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        g = num_groups
        grouped = a_t.reshape((n, g, c // g) + a_t.shape[2:])
        axes = tuple(range(2, grouped.ndim))
        gf = grouped.astype(jnp.float32)
        m = jnp.mean(gf, axis=axes, keepdims=True)
        v = jnp.var(gf, axis=axes, keepdims=True)
        out = ((gf - m) * jax.lax.rsqrt(v + epsilon)).astype(a.dtype)
        out = out.reshape(a_t.shape)
        out = _affine(out, wb, 1, weight, bias)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = [x] + _wb_args(weight, bias)
    return apply(fn, *args, name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = as_tensor(x)

    def fn(a):
        ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[ch_axis]
        sq_m = jnp.moveaxis(sq, ch_axis, 0)
        pad_width = [(half, size - 1 - half)] + [(0, 0)] * (a.ndim - 1)
        padded = jnp.pad(sq_m, pad_width)
        acc = jnp.zeros_like(sq_m)
        for i in range(size):
            acc = acc + padded[i:i + c]
        denom = (k + alpha * acc) ** beta
        return a / jnp.moveaxis(denom, 0, ch_axis)
    return apply(fn, x, name="local_response_norm")


def spectral_norm(x, weight_u, weight_v, dim=0, power_iters=1,
                  eps=1e-12, name=None):
    """Functional spectral norm (reference
    ``paddle.nn.functional.spectral_norm``): normalize weight ``x`` by
    its largest singular value, estimated by ``power_iters`` rounds of
    power iteration from the CALLER-OWNED u/v vectors (the
    ``nn.SpectralNorm`` layer holds them as buffers and delegates
    here)."""
    from ...framework.core import Tensor, apply

    u0 = weight_u.jax() if isinstance(weight_u, Tensor) else \
        jnp.asarray(weight_u)
    v0 = weight_v.jax() if isinstance(weight_v, Tensor) else \
        jnp.asarray(weight_v)

    def fn(w):
        wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
        u, v = u0, v0
        for _ in range(power_iters):
            v = wm.T @ u
            v = v / (jnp.linalg.norm(v) + eps)
            u = wm @ v
            u = u / (jnp.linalg.norm(u) + eps)
        sigma = u @ wm @ v
        return w / sigma

    return apply(fn, x, name="spectral_norm")
