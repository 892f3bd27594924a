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


# ---- the no-delta rule (lightning attention) ----------------------------------

def _ssd_step_args(S, q, k, v, decay):
    """The no-delta rule as Mamba-2's one-position update: x = v, B = k,
    C = q, dt = 1, A = log(decay); the SSD state is S transposed."""
    from paddle_tpu.ops import mamba2
    Bn, H, dk, dv = S.shape
    A = jnp.log(jnp.asarray(decay[0]))
    y, h = mamba2.ssd_step(jnp.swapaxes(jnp.asarray(S), -1, -2),
                           jnp.asarray(v), jnp.ones((Bn, H), jnp.float32), A,
                           jnp.asarray(k), jnp.asarray(q),
                           jnp.zeros((H,), jnp.float32))
    return y, jnp.swapaxes(h, -1, -2)


@pytest.mark.parametrize("live", [None, [True, False, True]],
                         ids=["all_live", "a_dead_row"])
def test_linear_state_step_is_ssd_step(live):
    """The kernel under its no-delta rule and the jnp form both give
    Mamba-2's one-position update with one group a head; a row that does
    not advance is neither read nor written."""
    rng = np.random.default_rng(4)
    d = DV
    q = rng.normal(size=(B, H, d)).astype(np.float32)
    k = rng.normal(size=(B, H, d)).astype(np.float32)
    v = rng.normal(size=(B, H, d)).astype(np.float32)
    S0 = rng.normal(size=(B, H, d, d)).astype(np.float32)
    decay = np.broadcast_to(np.exp(-np.linspace(0.01, 0.7, H)),
                            (B, H)).astype(np.float32)
    want_o, want_s = _ssd_step_args(S0, q, k, v, decay)
    lv = None if live is None else jnp.asarray(live)
    for fn in (kernel.linear_state_step, gd.linear_state_step):
        o, s = fn(jnp.asarray(S0), q, k, v, decay, lv)
        rows = range(B) if live is None else [i for i in range(B)
                                              if live[i]]
        for i in rows:
            np.testing.assert_allclose(o[i], want_o[i], atol=1e-5)
            np.testing.assert_allclose(s[i], want_s[i], atol=1e-5)
        for i in set(range(B)) - set(rows):
            np.testing.assert_array_equal(s[i], S0[i])


def test_linear_state_step_restarts_a_row_from_zero():
    rng = np.random.default_rng(6)
    d = 8
    S0 = rng.normal(size=(2, H, d, d)).astype(np.float32)
    q, k, v = (rng.normal(size=(2, H, d)).astype(np.float32)
               for _ in range(3))
    decay = np.full((2, H), 0.9, np.float32)
    o, s = gd.linear_state_step(jnp.asarray(S0), q, k, v, decay,
                                reset=jnp.asarray([True, False]))
    np.testing.assert_allclose(s[0], k[0][..., :, None] * v[0][..., None, :],
                               atol=1e-6)
    np.testing.assert_allclose(s[1], 0.9 * S0[1] + k[1][..., :, None]
                               * v[1][..., None, :], atol=1e-5)


def test_the_two_rules_are_two_kernels():
    """The rule is a static argument: the delta rule keeps its kernel name
    (``gated_delta_step``, which the Qwen3-Next cell's roofline reads) and
    the no-delta rule gets its own."""
    import jax
    S = jnp.zeros((2, H, 8, 8), jnp.float32)
    x = jnp.zeros((2, H, 8), jnp.float32)
    dec = jnp.ones((2, H), jnp.float32)
    t1 = jax.jit(kernel.gated_delta_step).lower(S, x, x, x, dec,
                                                dec).as_text()
    t2 = jax.jit(kernel.linear_state_step).lower(S, x, x, x, dec).as_text()
    assert "gated_delta_step" in t1 and "linear_state_step" not in t1
    assert "linear_state_step" in t2
