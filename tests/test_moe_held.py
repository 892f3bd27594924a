"""The sigmoid router and the held-share expert layer (ops/moe.py), beside
the softmax gates' tests: what a chip of an expert-parallel deployment
computes when it is told which experts it holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape)
                       * scale, jnp.float32)


def _router_numpy(logits, bias, k, norm, scale):
    """The router written out in numpy, row by row."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    idx = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1,
                     kind="stable")[:, :k]
    w = np.take_along_axis(s, idx, -1)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    return idx, w * scale


@pytest.mark.parametrize("norm,scale", [(True, 5.0), (True, 1.0),
                                        (False, 2.5)])
def test_sigmoid_router_is_its_numpy_transcription(norm, scale):
    logits, bias = _rand(0, 37, 64), _rand(1, 64, scale=0.3)
    idx, w = moe.sigmoid_top_k_router(logits, bias, 6, norm, scale)
    ref_idx, ref_w = _router_numpy(logits, bias, 6, norm, scale)
    # sets, not order: top_k's order among the chosen is its own
    assert (np.sort(np.asarray(idx), -1) == np.sort(ref_idx, -1)).all()
    order, ref_order = np.argsort(np.asarray(idx), -1), np.argsort(ref_idx, -1)
    got = np.take_along_axis(np.asarray(w), order, -1)
    want = np.take_along_axis(ref_w, ref_order, -1)
    # float32 sigmoid and one division against float64: a few ulp
    np.testing.assert_allclose(got, want, rtol=2e-6)
    if norm:
        np.testing.assert_allclose(np.asarray(w).sum(-1), scale, rtol=1e-6)


def test_the_bias_moves_the_selection_and_not_the_weights():
    logits = _rand(2, 16, 32)
    zero = jnp.zeros((32,), jnp.float32)
    idx0, w0 = moe.sigmoid_top_k_router(logits, zero, 4, False, 1.0)
    # a bias that lifts expert 31 above everything: it is chosen in every
    # row, and weighs what its own unbiased score says
    lift = zero.at[31].set(10.0)
    idx1, w1 = moe.sigmoid_top_k_router(logits, lift, 4, False, 1.0)
    assert (np.asarray(idx1) == 31).any(-1).all()
    assert not (np.asarray(idx0) == 31).any(-1).all()
    s = np.asarray(jax.nn.sigmoid(logits))
    got = np.take_along_axis(np.asarray(w1), np.argmax(
        np.asarray(idx1) == 31, -1)[:, None], -1)[:, 0]
    np.testing.assert_allclose(got, s[:, 31], rtol=1e-6)
    # the experts both chose weigh the same under either bias
    for t in range(16):
        both = set(np.asarray(idx0[t])) & set(np.asarray(idx1[t]))
        for e in both:
            a = float(w0[t][list(np.asarray(idx0[t])).index(e)])
            b = float(w1[t][list(np.asarray(idx1[t])).index(e)])
            assert a == b


def test_softmax_gates_share_the_tail_bit_for_bit():
    """The two softmax gates route through ``top_k_weights``; what they
    return is what ``lax.top_k`` + the normalisation gave before."""
    logits = _rand(3, 24, 8)
    probs = jax.nn.softmax(logits, -1)
    vals, idx = jax.lax.top_k(probs, 2)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    gi, gv, _pos, _keep, _aux, _z = moe.top_k_gating_idx(logits, 2, 48)
    assert (np.asarray(gi) == np.asarray(idx)).all()
    assert (np.asarray(gv) == np.asarray(vals)).all()


def _dense_layer(v, idx, w, w1, w2, lo, hi, valid):
    out = np.zeros(v.shape, np.float64)
    for t in range(v.shape[0]):
        if not valid[t]:
            continue
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            if lo <= e < hi:
                a = np.maximum(np.asarray(v[t], np.float64)
                               @ np.asarray(w1[e], np.float64), 0) ** 2
                out[t] += float(w[t, j]) * (a @ np.asarray(w2[e],
                                                           np.float64))
    return out


@pytest.mark.parametrize("bm", [None, 16, 128])
def test_the_held_quarters_add_up_to_the_whole_layer(bm):
    """Four holders of 4 experts each, routed over all 16: each computes
    its own pairs, dropless, and the four parts sum to the uncut layer.
    Padded tokens (``valid`` false) add nothing anywhere."""
    T, d, h, E, k = 24, 128, 256, 16, 5
    v, w1, w2 = _rand(4, T, d), _rand(5, E, d, h, scale=.1), \
        _rand(6, E, h, d, scale=.1)
    idx, w = moe.sigmoid_top_k_router(_rand(7, T, E), _rand(8, E, scale=.5),
                                      k, True, 2.5)
    valid = jnp.arange(T) < 20
    total, pairs = 0, 0
    for q in range(4):
        lo = 4 * q
        out, st = jax.jit(lambda v, i, w, a, b: moe.moe_experts_held(
            v, i, w, a, b, lo, valid=valid, bm=bm))(
                v, idx, w, w1[lo:lo + 4], w2[lo:lo + 4])
        want = _dense_layer(v, idx, w, w1, w2, lo, lo + 4, valid)
        # float32 kernel against a float64 loop over sums of 256 terms
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-4,
                                   rtol=1e-4)
        held = (np.asarray(idx) >= lo) & (np.asarray(idx) < lo + 4) \
            & np.asarray(valid)[:, None]
        counts = np.bincount(np.asarray(idx)[held] - lo, minlength=4)
        assert list(np.asarray(st)) == [held.sum(), counts.max()]
        total, pairs = total + out, pairs + int(st[0])
    assert pairs == 20 * k
    np.testing.assert_allclose(
        np.asarray(total), _dense_layer(v, idx, w, w1, w2, 0, E, valid),
        atol=5e-4, rtol=1e-4)
    assert not np.asarray(total)[20:].any()


def _gated_layer(v, idx, w, wg, w1, w2, lo, hi, valid):
    out = np.zeros(v.shape, np.float64)
    f64 = lambda a: np.asarray(a, np.float64)
    for t in range(v.shape[0]):
        if not valid[t]:
            continue
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            if lo <= e < hi:
                gate = f64(v[t]) @ f64(wg[e - lo])
                a = gate / (1.0 + np.exp(-gate)) * (f64(v[t]) @ f64(w1[e - lo]))
                out[t] += float(w[t, j]) * (a @ f64(w2[e - lo]))
    return out


@pytest.mark.parametrize("bm", [None, 16])
def test_gated_held_experts_are_the_dense_loop(bm):
    """With a gate matrix the held experts are SwiGLU, ``(silu(x Wg) *
    (x W1)) W2``: against a float64 loop over the pairs, a holder of 4 of
    16 experts, padded tokens adding nothing; the same call without the
    gate is still the squared-ReLU pair."""
    T, d, h, E, k, lo = 24, 128, 256, 16, 5, 8
    v = _rand(4, T, d)
    wg, w1, w2 = _rand(12, 4, d, h, scale=.1), _rand(5, 4, d, h, scale=.1), \
        _rand(6, 4, h, d, scale=.1)
    idx, w = moe.sigmoid_top_k_router(_rand(7, T, E), _rand(8, E, scale=.5),
                                      k, True, 2.5)
    valid = jnp.arange(T) < 20
    out, st = jax.jit(lambda *a: moe.moe_experts_held(
        *a, lo, valid=valid, bm=bm, w_gate=wg))(v, idx, w, w1, w2)
    # float32 kernel against a float64 loop over sums of 256 terms
    np.testing.assert_allclose(
        np.asarray(out), _gated_layer(v, idx, w, wg, w1, w2, lo, lo + 4,
                                      valid), atol=2e-4, rtol=1e-4)
    held = (np.asarray(idx) >= lo) & (np.asarray(idx) < lo + 4) \
        & np.asarray(valid)[:, None]
    assert int(st[0]) == held.sum() > 0
    plain, st2 = moe.moe_experts_held(v, idx, w, w1, w2, lo, valid=valid,
                                      bm=bm)
    full1, full2 = jnp.zeros((E, d, h)).at[lo:lo + 4].set(w1), \
        jnp.zeros((E, h, d)).at[lo:lo + 4].set(w2)
    np.testing.assert_allclose(
        np.asarray(plain), _dense_layer(v, idx, w, full1, full2, lo, lo + 4,
                                        valid), atol=2e-4, rtol=1e-4)
    assert list(np.asarray(st2)) == list(np.asarray(st))


def test_the_held_counters_are_declared_once_where_the_function_lives():
    from paddle_tpu.profiler import metrics
    cat = metrics.catalog()
    assert moe.HELD_COUNTERS == ("moe_tokens", "moe_local_pairs",
                                 "moe_max_expert_pairs")
    assert all(cat["serving/" + n][0] == "counter"
               for n in moe.HELD_COUNTERS)
    from paddle_tpu.models import exaone_moe, nemotron_h
    assert nemotron_h.COUNTERS[:3] == exaone_moe.COUNTERS \
        == moe.HELD_COUNTERS


def test_a_holder_that_is_sent_nothing_returns_zero():
    T, d, h = 8, 128, 128
    v, w1, w2 = _rand(9, T, d), _rand(10, 2, d, h), _rand(11, 2, h, d)
    idx = jnp.full((T, 3), 7, jnp.int32)            # nobody chose 0 or 1
    out, st = moe.moe_experts_held(v, idx, jnp.ones((T, 3)), w1, w2, 0)
    assert not np.asarray(out).any() and list(np.asarray(st)) == [0, 0]
