"""Pallas kernels per shard under a fleet mesh (ops/pallas/_mesh.py):
values and gradients of the shard_map-wrapped call equal the plain
call's, on the suite's virtual CPU devices (interpret-mode kernels).
That the wrapped calls get past the TPU partitioner is
tests/test_chip_compile.py's job."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.ops.pallas import _mesh
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.rms_norm import rms_norm, rms_norm_residual
from paddle_tpu.ops.pallas.swiglu import swiglu_fused


@pytest.fixture(scope="module")
def mesh():
    names = ("data", "sharding", "pipe", "sep", "model", "expert")
    return Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 1, 1, 2, 1),
                names)


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _value_and_grads(fn, *args):
    def loss(*a):
        out = fn(*a)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        return sum(jnp.sum(jnp.sin(o)) for o in outs)
    return jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args)))))(*args)


def _close(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_flash_heads_over_model_batch_over_sharding(mesh):
    q = _rand((4, 32, 4, 16), 0)
    k, v = _rand((4, 32, 2, 16), 1), _rand((4, 32, 2, 16), 2)  # GQA

    def plain(a, b, c):
        return flash_attention(a, b, c, True, None)
    _close(_value_and_grads(
        lambda a, b, c: _mesh.sharded_heads(plain, mesh, a, b, c),
        q, k, v), _value_and_grads(plain, q, k, v))


@pytest.mark.parametrize("residual", [False, True])
def test_rms_norm_rows_over_sharding(mesh, residual):
    x, r, w = _rand((4, 8, 64), 3), _rand((4, 8, 64), 4), _rand((64,), 5)
    if residual:
        def plain(a, b, ww):
            return rms_norm_residual(a, b, ww, 1e-6)
        got = _value_and_grads(
            lambda a, b, ww: _mesh.sharded_rows(
                plain, mesh, a, b, replicated=(ww,), n_out=2), x, r, w)
        _close(got, _value_and_grads(plain, x, r, w))
    else:
        def plain(a, ww):
            return rms_norm(a, ww, 1e-6)
        got = _value_and_grads(
            lambda a, ww: _mesh.sharded_rows(
                plain, mesh, a, replicated=(ww,)), x, w)
        _close(got, _value_and_grads(plain, x, w))


def test_swiglu_rows_and_columns(mesh):
    g, u = _rand((4, 8, 256), 6), _rand((4, 8, 256), 7)
    _close(_value_and_grads(
        lambda a, b: _mesh.sharded_cols(swiglu_fused, mesh, a, b), g, u),
        _value_and_grads(swiglu_fused, g, u))


def test_dims_an_axis_does_not_divide_stay_replicated(mesh):
    # batch 3 over sharding=2, 3 heads over model=2: both replicated
    q = _rand((3, 16, 3, 16), 8)

    def plain(a, b, c):
        return flash_attention(a, b, c, True, None)
    _close(_value_and_grads(
        lambda a, b, c: _mesh.sharded_heads(plain, mesh, a, b, c),
        q, q, q), _value_and_grads(plain, q, q, q))


def test_placement_rule():
    """No fleet mesh -> the kernel is called as is."""
    assert _mesh.kernel_placement() == (True, None)
