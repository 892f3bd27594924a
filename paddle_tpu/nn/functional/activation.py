"""Activation functionals (python/paddle/nn/functional/activation.py parity,
UNVERIFIED). All are pure jnp/jax.nn compositions; XLA fuses them into
adjacent matmuls on TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply, tape_alias, tape_rebind
from ...ops.common import as_tensor

__all__ = ["relu", "relu_", "relu6", "gelu", "silu", "swish", "sigmoid",
           "tanh", "softmax", "softmax_", "log_softmax", "leaky_relu", "elu",
           "elu_", "selu", "celu", "hardswish", "hardsigmoid", "hardtanh",
           "hardshrink", "softshrink", "tanhshrink", "mish", "prelu", "glu",
           "swiglu", "maxout", "softplus", "softsign", "thresholded_relu",
           "log_sigmoid", "gumbel_softmax", "rrelu"]


def relu(x, name=None):
    return apply(jax.nn.relu, as_tensor(x), name="relu")


def relu_(x, name=None):
    return tape_rebind(x, relu(tape_alias(x)))


def relu6(x, name=None):
    return apply(jax.nn.relu6, as_tensor(x), name="relu6")


def gelu(x, approximate=False, name=None):
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate),
                 as_tensor(x), name="gelu")


def silu(x, name=None):
    return apply(jax.nn.silu, as_tensor(x), name="silu")


def swish(x, name=None):
    return silu(x)


def sigmoid(x, name=None):
    return apply(jax.nn.sigmoid, as_tensor(x), name="sigmoid")


def tanh(x, name=None):
    return apply(jnp.tanh, as_tensor(x), name="tanh")


def softmax(x, axis=-1, dtype=None, name=None):
    x = as_tensor(x)
    from ...framework.core import to_jax_dtype
    jd = to_jax_dtype(dtype)

    def fn(a):
        if jd is not None:
            a = a.astype(jd)
        return jax.nn.softmax(a, axis=int(axis))
    return apply(fn, x, name="softmax")


def softmax_(x, axis=-1, dtype=None, name=None):
    return tape_rebind(x, softmax(tape_alias(x), axis, dtype))


def log_softmax(x, axis=-1, dtype=None, name=None):
    x = as_tensor(x)
    from ...framework.core import to_jax_dtype
    jd = to_jax_dtype(dtype)

    def fn(a):
        if jd is not None:
            a = a.astype(jd)
        return jax.nn.log_softmax(a, axis=int(axis))
    return apply(fn, x, name="log_softmax")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope),
                 as_tensor(x), name="leaky_relu")


def elu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.elu(a, alpha), as_tensor(x), name="elu")


def elu_(x, alpha=1.0, name=None):
    return tape_rebind(x, elu(tape_alias(x), alpha))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply(lambda a: scale * jnp.where(a > 0, a,
                                             alpha * jnp.expm1(a)),
                 as_tensor(x), name="selu")


def celu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.celu(a, alpha), as_tensor(x), name="celu")


def hardswish(x, name=None):
    return apply(jax.nn.hard_swish, as_tensor(x), name="hardswish")


def hardsigmoid(x, slope=1.0 / 6, offset=0.5, name=None):
    return apply(lambda a: jnp.clip(slope * a + offset, 0.0, 1.0),
                 as_tensor(x), name="hardsigmoid")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(lambda a: jnp.clip(a, min, max), as_tensor(x),
                 name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0),
                 as_tensor(x), name="hardshrink")


def softshrink(x, threshold=0.5, name=None):
    return apply(lambda a: jnp.where(a > threshold, a - threshold,
                                     jnp.where(a < -threshold, a + threshold,
                                               0.0)),
                 as_tensor(x), name="softshrink")


def tanhshrink(x, name=None):
    return apply(lambda a: a - jnp.tanh(a), as_tensor(x), name="tanhshrink")


def mish(x, name=None):
    return apply(lambda a: a * jnp.tanh(jax.nn.softplus(a)), as_tensor(x),
                 name="mish")


def prelu(x, weight, data_format="NCHW", name=None):
    x, weight = as_tensor(x), as_tensor(weight)

    def fn(a, w):
        if w.size > 1:
            ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
            shape = [1] * a.ndim
            shape[ch_axis] = w.size
            w = w.reshape(shape)
        return jnp.where(a > 0, a, w * a)
    return apply(fn, x, weight, name="prelu")


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    x = as_tensor(x)
    if training:
        from ...framework import random as fr
        import jax.random as jr
        key = fr.default_generator.next_key()
        slope = jr.uniform(key, tuple(x.shape), jnp.float32, lower, upper)
        return apply(lambda a: jnp.where(a >= 0, a, slope.astype(a.dtype) * a),
                     x, name="rrelu")
    mid = (lower + upper) / 2.0
    return apply(lambda a: jnp.where(a >= 0, a, mid * a), x, name="rrelu")


def glu(x, axis=-1, name=None):
    def fn(a):
        u, v = jnp.split(a, 2, axis=axis)
        return u * jax.nn.sigmoid(v)
    return apply(fn, as_tensor(x), name="glu")


def _use_fused_swiglu() -> bool:
    from ...framework import flags
    if not (flags.flag("FLAGS_fused_swiglu")
            and flags.flag("FLAGS_enable_pallas_kernels")):
        return False
    return jax.default_backend() == "tpu"


def swiglu(x, y=None, name=None):
    if y is not None:
        if _use_fused_swiglu():
            # one VMEM pass + fused dgate/dup backward, no silu
            # intermediate saved (ops/pallas/swiglu.py)
            from ...ops.pallas import swiglu as pallas_sw
            from ...ops.pallas._mesh import (kernel_placement,
                                             sharded_cols)
            use_kernel, mesh = kernel_placement()
            if use_kernel:      # per shard (rows x MLP columns) on a mesh
                return apply(
                    lambda a, b: sharded_cols(pallas_sw.swiglu_fused,
                                              mesh, a, b),
                    as_tensor(x), as_tensor(y), name="fused_swiglu")
        return apply(lambda a, b: jax.nn.silu(a) * b, as_tensor(x),
                     as_tensor(y), name="swiglu")

    def fn(a):
        u, v = jnp.split(a, 2, axis=-1)
        return jax.nn.silu(u) * v
    return apply(fn, as_tensor(x), name="swiglu")


def maxout(x, groups, axis=1, name=None):
    def fn(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        new_shape = a.shape[:ax] + (c // groups, groups) + a.shape[ax + 1:]
        return jnp.max(a.reshape(new_shape), axis=ax + 1)
    return apply(fn, as_tensor(x), name="maxout")


def softplus(x, beta=1, threshold=20, name=None):
    from ...ops.math import softplus as _sp
    return _sp(x, beta, threshold)


def softsign(x, name=None):
    return apply(jax.nn.soft_sign, as_tensor(x), name="softsign")


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return apply(lambda a: jnp.where(a > threshold, a, value), as_tensor(x),
                 name="thresholded_relu")


def log_sigmoid(x, name=None):
    return apply(jax.nn.log_sigmoid, as_tensor(x), name="log_sigmoid")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    x = as_tensor(x)
    from ...framework import random as fr
    import jax.random as jr
    key = fr.default_generator.next_key()
    g = jr.gumbel(key, tuple(x.shape), jnp.float32)

    def fn(a):
        y = jax.nn.softmax((a + g.astype(a.dtype)) / temperature, axis=axis)
        if hard:
            # straight-through: hard one-hot forward, soft gradient
            oh = jax.nn.one_hot(jnp.argmax(y, axis=axis), y.shape[axis],
                                axis=axis, dtype=y.dtype)
            return oh + y - jax.lax.stop_gradient(y)
        return y
    return apply(fn, x, name="gumbel_softmax")
