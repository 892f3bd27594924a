"""The gated delta rule's three forms (``ops/gated_delta.py``) against each
other — the position-by-position scan is the definition — and the Pallas
one-step kernel (``ops/pallas/gated_delta_step.py``, interpret mode on the
CPU) against the scan: ragged lengths, a non-zero initial state, a prompt fed
in pieces, rows that do not advance. float32 throughout; each tolerance says
what it absorbs."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops.pallas import gated_delta_step as kernel

B, S, H, DK, DV = 3, 37, 4, 16, 8


def _l2(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    f = np.float32
    return dict(
        q=(_l2(rng.normal(size=(B, S, H, DK))) * DK ** -0.5).astype(f),
        k=_l2(rng.normal(size=(B, S, H, DK))).astype(f),
        v=rng.normal(size=(B, S, H, DV)).astype(f),
        g=(-0.2 * np.abs(rng.normal(size=(B, S, H)))).astype(f),
        beta=(1 / (1 + np.exp(-rng.normal(size=(B, S, H))))).astype(f),
        S0=rng.normal(size=(B, H, DK, DV)).astype(f))


def _seq(d, *names):
    return [d[n] for n in names or ("q", "k", "v", "g", "beta")]


# 1e-5: float32 sums in another order (a chunk's matmuls against the scan's
# running state); values are O(1)
TOL = 1e-5


@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_chunked_is_the_scan(data, chunk):
    """Chunks that divide 37 nowhere (the tail is padded with no-ops), and
    one larger than the sequence; a non-zero initial state."""
    o1, s1 = gd.gated_delta_scan(data["S0"], *_seq(data))
    o2, s2 = gd.gated_delta_chunked(data["S0"], *_seq(data), chunk=chunk)
    np.testing.assert_allclose(o2, o1, atol=TOL)
    np.testing.assert_allclose(s2, s1, atol=TOL)


def test_chunked_stops_each_row_at_its_length(data):
    """Rows of 37, 5 and 0 valid positions: outputs up to the length and
    the state AFTER the last valid position are the scan's over that prefix
    alone; a row of length 0 keeps its state."""
    lens = [37, 5, 0]
    o, s = gd.gated_delta_chunked(data["S0"], *_seq(data),
                                  lengths=jnp.asarray(lens, jnp.int32),
                                  chunk=16)
    for b, n in enumerate(lens):
        ob, sb = gd.gated_delta_scan(
            data["S0"][b:b + 1], *(a[b:b + 1, :n] for a in _seq(data)))
        np.testing.assert_allclose(o[b:b + 1, :n], ob, atol=TOL)
        np.testing.assert_allclose(s[b:b + 1], sb, atol=TOL)
    np.testing.assert_array_equal(s[2], data["S0"][2])


def test_a_prompt_fed_in_pieces_carries_its_state(data):
    """Pieces of 16, 16 and 5 positions, each call starting from the state
    the one before handed back — as the engine feeds a prompt chunk by
    chunk — then single steps: the whole is the scan."""
    o1, s1 = gd.gated_delta_scan(data["S0"], *_seq(data))
    state, outs = jnp.asarray(data["S0"]), []
    for a, b in ((0, 16), (16, 32)):
        o, state = gd.gated_delta_chunked(
            state, *(x[:, a:b] for x in _seq(data)), chunk=8)
        outs.append(o)
    for t in range(32, S):
        o, state = gd.gated_delta_step(
            state, *(x[:, t] for x in _seq(data)))
        outs.append(o[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), o1, atol=TOL)
    np.testing.assert_allclose(state, s1, atol=TOL)


def test_no_decay_no_write_is_a_no_op(data):
    """g = 0 and beta = 0: what a caller sets on padding and idle rows."""
    z = np.zeros((B, H), np.float32)
    _, s = gd.gated_delta_step(data["S0"], data["q"][:, 0], data["k"][:, 0],
                               data["v"][:, 0], z, z)
    np.testing.assert_array_equal(s, data["S0"])


def test_unit_lower_inverse():
    rng = np.random.default_rng(1)
    a = np.tril(rng.normal(size=(2, 3, 16, 16)), -1).astype(np.float32)
    inv = gd._unit_lower_inverse(jnp.asarray(a))
    eye = np.eye(16, dtype=np.float32)
    # 1e-4: forward substitution over 16 rows of O(1) entries in float32
    np.testing.assert_allclose(inv @ (eye + a), np.broadcast_to(
        eye, a.shape), atol=1e-4)


@pytest.mark.parametrize("live", [
    [True, True, True], [False, True, False], [False, False, True],
    [True, False, False], [False, False, False]],
    ids=["all", "middle", "last", "first", "none"])
def test_pallas_step_is_the_scan_and_leaves_dead_rows_alone(data, live):
    """The kernel (interpret mode) on one position: live rows get the
    scan's state and output, the others keep their state bit for bit and
    read 0 — whichever rows are live, none included (a decode micro-step in
    which every slot prefills)."""
    t = 3
    live = jnp.asarray(live)
    args = [a[:, t] for a in _seq(data)]
    o, s = kernel.gated_delta_step(
        jnp.asarray(data["S0"]), args[0], args[1], args[2],
        jnp.exp(args[3]), args[4], live)
    ow, sw = gd.gated_delta_scan(data["S0"], *(a[:, t:t + 1]
                                               for a in _seq(data)))
    keep = np.asarray(live)
    # 1e-6: the same float32 products, summed over sublanes
    np.testing.assert_allclose(np.asarray(o)[keep], ow[:, 0][keep],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s)[keep], sw[keep], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[~keep], data["S0"][~keep])
    assert not np.asarray(o)[~keep].any()


def test_pallas_step_restarts_a_row_from_zero(data):
    """decay 0 on a row = the row starts from zero state (a slot taken over
    by a new request whose first pass is one token long)."""
    t = 0
    args = [a[:, t] for a in _seq(data)]
    decay = jnp.exp(args[3]).at[1].set(0.0)
    o, s = kernel.gated_delta_step(jnp.asarray(data["S0"]), args[0],
                                   args[1], args[2], decay, args[4])
    zero = data["S0"].copy()
    zero[1] = 0
    ow, sw = gd.gated_delta_scan(zero, *(a[:, :1] for a in _seq(data)))
    np.testing.assert_allclose(o, ow[:, 0], atol=1e-6)
    np.testing.assert_allclose(s, sw, atol=1e-6)


def test_pallas_step_at_more_heads_than_a_program_holds():
    """32 heads in programs of 16: two head blocks a row."""
    rng = np.random.default_rng(2)
    b, h, dk, dv = 2, 32, 8, 128
    f = np.float32
    S0 = rng.normal(size=(b, h, dk, dv)).astype(f)
    q, k = (_l2(rng.normal(size=(b, h, dk))).astype(f) for _ in range(2))
    v = rng.normal(size=(b, h, dv)).astype(f)
    g = (-0.1 * np.abs(rng.normal(size=(b, h)))).astype(f)
    beta = rng.uniform(size=(b, h)).astype(f)
    live = jnp.asarray([False, True])
    o, s = kernel.gated_delta_step(jnp.asarray(S0), q, k, v, np.exp(g), beta,
                                   live)
    ow, sw = gd._step(S0[1:], q[1:], k[1:], v[1:], np.exp(g)[1:], beta[1:])
    np.testing.assert_allclose(o[1:], ow, atol=1e-6)
    np.testing.assert_allclose(s[1:], sw, atol=1e-6)
    np.testing.assert_array_equal(s[0], S0[0])


def test_softmax_router_is_softmax_top_k_renormalised():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(7, 16)).astype(np.float32)
    idx, w = moe_ops.softmax_top_k_router(jnp.asarray(logits), 3)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, -1)[:, :3]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want, -1))
    pw = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(w, pw / pw.sum(-1, keepdims=True), rtol=1e-6)
    _, raw = moe_ops.softmax_top_k_router(jnp.asarray(logits), 3,
                                          norm_topk_prob=False)
    np.testing.assert_allclose(raw, pw, rtol=1e-6)
