"""The held-share expert layer's backward (``ops.moe.moe_experts_held`` over
``grouped_matmul_live``'s custom VJP, interpret mode): against ``jax.grad``
of a dense loop over the held SwiGLU experts; dead tiles skipped
in dx and dW; and the share: four holders of 16 experts each over a 64-wide
router add up to the uncut layer's output and input gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul_live

F32 = jnp.float32


def _weights(seed, t, d, h, n_router, n_held):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (t, d), F32),
            0.3 * jax.random.normal(ks[1], (d, n_router), F32),
            0.1 * jax.random.normal(ks[2], (n_held, d, h), F32),
            0.1 * jax.random.normal(ks[3], (n_held, d, h), F32),
            0.1 * jax.random.normal(ks[4], (n_held, h, d), F32),
            jax.random.normal(ks[5], (t, d), F32))


def _dense_held(x, idx, w, wg, w1, w2, first):
    """The held experts' part as a loop: each expert on every row, weighed
    where the row chose it."""
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        sel = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        a = jax.nn.silu(x @ wg[e]) * (x @ w1[e])
        out = out + sel[:, None] * (a @ w2[e])
    return out


def test_backward_matches_a_dense_loop():
    t, d, h, k = 48, 128, 128, 3
    x, wr, wg, w1, w2, c = _weights(0, t, d, h, 16, 4)
    first = 4

    def loss(held_fn, x, wr, wg, w1, w2):
        idx, w = moe.softmax_top_k_router(x @ wr, k)
        return jnp.sum(c * held_fn(x, idx, w, wg, w1, w2))

    def prog(x, idx, w, wg, w1, w2):
        return moe.moe_experts_held(x, idx, w, w1, w2, first, bm=16,
                                    w_gate=wg)[0]

    def ref(x, idx, w, wg, w1, w2):
        return _dense_held(x, idx, w, wg, w1, w2, first)

    args = (x, wr, wg, w1, w2)
    got = jax.grad(lambda *a: loss(prog, *a), argnums=tuple(range(5)))(*args)
    want = jax.grad(lambda *a: loss(ref, *a), argnums=tuple(range(5)))(*args)
    for name, g, r in zip(("x", "router", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


def test_the_relu2_epilogue_has_no_backward():
    """Nothing trains through the relu^2 pair: its gradient is refused, not
    computed as if the epilogue were not there."""
    x, wr, _, w1, w2, _ = _weights(4, 32, 128, 128, 16, 4)
    idx, w = moe.softmax_top_k_router(x @ wr, 3)
    with pytest.raises(NotImplementedError, match="relu2"):
        jax.grad(lambda a: jnp.sum(moe.moe_experts_held(
            a, idx, w, w1, w2, 4, bm=16)[0]))(x)


def test_dead_tiles_are_skipped_by_dx_and_dw():
    """Tiles past ``n_live`` hold NaN rows: dx of their rows is zero, dW
    sees only the live rows, and nothing is NaN."""
    bm, nr, d, h = 16, 6, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (nr * bm, d), F32)
    w = jax.random.normal(ks[1], (2, d, h), F32)
    dy = jax.random.normal(ks[2], (nr * bm, h), F32)
    gid = jnp.asarray([0, 0, 1, 1, 1, 1], jnp.int32)
    n_live = jnp.int32(4)
    x = x.at[4 * bm:].set(jnp.nan)
    _, vjp = jax.vjp(lambda a, b: grouped_matmul_live(a, b, gid, n_live),
                     x, w)
    dx, dw = vjp(dy.at[4 * bm:].set(jnp.nan))
    dx, dw = np.asarray(dx), np.asarray(dw)
    assert np.isfinite(dx).all() and np.isfinite(dw).all()
    assert not dx[4 * bm:].any()
    xl, dyl = np.asarray(x[:4 * bm]), np.asarray(dy[:4 * bm])
    w0, w1 = np.asarray(w[0]), np.asarray(w[1])
    np.testing.assert_allclose(dx[:2 * bm], dyl[:2 * bm] @ w0.T, atol=1e-3,
                               rtol=1e-4)
    np.testing.assert_allclose(dx[2 * bm:4 * bm], dyl[2 * bm:] @ w1.T,
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(dw[0], xl[:2 * bm].T @ dyl[:2 * bm],
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(dw[1], xl[2 * bm:].T @ dyl[2 * bm:],
                               atol=1e-3, rtol=1e-4)


def test_four_holders_add_up_to_the_uncut_layer():
    """A 64-expert layer cut into four shares of 16: the holders' outputs
    and their gradients of one loss (input, router, each share's experts)
    add up to what the uncut layer gives. Every chip computes the router
    alike; nothing stands in for an absent holder."""
    t, d, h, k, n = 64, 128, 128, 8, 64
    x, wr, wg, w1, w2, c = _weights(2, t, d, h, n, n)

    def layer(x, wr, wg, w1, w2, first):
        idx, w = moe.softmax_top_k_router(x @ wr, k)
        return moe.moe_experts_held(x, idx, w, w1, w2, first, w_gate=wg)[0]

    def loss(x, wr, wg, w1, w2, first):
        return jnp.sum(c * layer(x, wr, wg, w1, w2, first))

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    whole_out = layer(x, wr, wg, w1, w2, 0)
    whole = grad(x, wr, wg, w1, w2, 0)
    outs, gx, gr, gexp = 0.0, 0.0, 0.0, []
    for r in range(4):
        sl = slice(16 * r, 16 * (r + 1))
        outs = outs + layer(x, wr, wg[sl], w1[sl], w2[sl], 16 * r)
        g = grad(x, wr, wg[sl], w1[sl], w2[sl], 16 * r)
        gx, gr = gx + g[0], gr + g[1]
        gexp.append(g[2:])
    np.testing.assert_allclose(np.asarray(outs), np.asarray(whole_out),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(whole[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(whole[1]),
                               atol=1e-5, rtol=1e-5)
    for i in range(3):
        np.testing.assert_allclose(
            np.concatenate([np.asarray(g[i]) for g in gexp]),
            np.asarray(whole[2 + i]), atol=1e-5, rtol=1e-5)


def test_the_forward_is_unchanged_by_the_backward():
    """The serving path's call (no gradient) gives what the differentiated
    call's primal gives."""
    t, d, h, k = 40, 128, 128, 3
    x, wr, wg, w1, w2, _ = _weights(3, t, d, h, 16, 4)
    idx, w = moe.softmax_top_k_router(x @ wr, k)
    plain, st = moe.moe_experts_held(x, idx, w, w1, w2, 4, bm=16, w_gate=wg)
    primal, _ = jax.vjp(lambda a: moe.moe_experts_held(
        a, idx, w, w1, w2, 4, bm=16, w_gate=wg)[0], x)
    assert np.array_equal(np.asarray(plain), np.asarray(primal))
    held = (idx >= 4) & (idx < 8)
    assert int(st[0]) == int(jnp.sum(held))
