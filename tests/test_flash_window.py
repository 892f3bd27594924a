"""The flash kernel's window (``ops/pallas/flash_attention.py``, interpret
mode): forward, dq and dkv against a dense masked ``jax.numpy`` reference at
windows that are and are not multiples of the block; K/V blocks wholly
outside every window held as NaN, which only the loop bounds keep out;
``window=None`` at GPT-2's shape taking the resident programs bit for bit;
the blocked programs for a long causal call; the cost surface."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

F32 = jnp.float32


def dense(q, k, v, window):
    """Causal (bottom-right aligned) attention over every key, the window
    written out as a mask; GQA by repeating K/V heads."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        / np.float32(math.sqrt(d))
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)
    k_pos = jnp.arange(sk)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    sc = jnp.where(mask, sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                      precision="highest")


def _inputs(s, sk=None, h=4, kvh=2, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    sk = s if sk is None else sk
    return (jax.random.normal(ks[0], (1, s, h, d), F32),
            jax.random.normal(ks[1], (1, sk, kvh, d), F32),
            jax.random.normal(ks[2], (1, sk, kvh, d), F32),
            jax.random.normal(ks[3], (1, s, h, d), F32))


def _fwd_bwd(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + tuple(vjp(g))


@pytest.mark.parametrize("s,window", [(512, 128), (512, 256), (640, 200),
                                      (384, 1000)],
                         ids=["w128", "w256", "w200", "w_past_seq"])
def test_window_fwd_dq_dkv_match_dense(s, window):
    """Blocks of 128: windows of one and two blocks, one that is not a
    multiple of the block, one longer than the sequence (plain causal)."""
    q, k, v, g = _inputs(s)
    with fa.force_blocks(128, 128):
        got = _fwd_bwd(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, window), q, k, v, g)
    want = _fwd_bwd(lambda a, b, c: dense(a, b, c, window), q, k, v, g)
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5, err_msg=name)


def test_blocks_outside_the_window_are_never_read():
    """K/V block 0 holds NaN. Query blocks 2.. see no key of it (window
    128, blocks of 128), so their outputs and dq, and dk/dv of the K/V
    blocks only they see, are finite and right: a loop from block 0 that
    relied on the mask would multiply a NaN by a zero weight. Rows of query
    blocks 0-1 (which do see block 0) carry no cotangent."""
    s, w = 768, 128
    q, k, v, g = _inputs(s, seed=3)
    nan_k = k.at[:, :128].set(jnp.nan)
    nan_v = v.at[:, :128].set(jnp.nan)
    g = g.at[:, :256].set(0.0)
    with fa.force_blocks(128, 128):
        out, dq, dk, dv = _fwd_bwd(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, w), q, nan_k, nan_v, g)
    want = _fwd_bwd(lambda a, b, c: dense(a, b, c, w), q, k, v, g)
    for name, x, y in zip(("out", "dq"), (out, dq), want[:2]):
        x, y = np.asarray(x)[:, 256:], np.asarray(y)[:, 256:]
        assert np.isfinite(x).all(), name
        np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5, err_msg=name)
    for name, x, y in zip(("dk", "dv"), (dk, dv), want[2:]):
        x, y = np.asarray(x)[:, 256:], np.asarray(y)[:, 256:]
        assert np.isfinite(x).all(), name
        np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5, err_msg=name)


def test_keys_no_query_sees_stay_out_of_every_result():
    """More keys than queries (bottom-right aligned): keys 0..255 are
    seen by no query through a window of 256, and hold NaN. Every output
    and every gradient is finite; their dk and dv are zero."""
    q, k, v, g = _inputs(256, sk=768, seed=5)
    k = k.at[:, :256].set(jnp.nan)
    v = v.at[:, :256].set(jnp.nan)
    with fa.force_blocks(128, 128):
        out, dq, dk, dv = _fwd_bwd(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, 256), q, k, v, g)
    for x in (out, dq, dk, dv):
        assert np.isfinite(np.asarray(x)).all()
    assert not np.asarray(dk)[:, :256].any()
    assert not np.asarray(dv)[:, :256].any()
    clean = lambda a: a.at[:, :256].set(0.0)
    want = _fwd_bwd(lambda a, b, c: dense(a, b, c, 256), q, clean(k),
                    clean(v), g)
    for x, y in zip((out, dq), want[:2]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5)


def test_window_none_at_gpt2_shape_runs_the_resident_programs():
    """At GPT-2's sequence and head size the call without a window takes
    the programs it took before the window existed, bit for bit: the
    resident forward (K/V whole in VMEM) and ``_flash_bwd_pallas``."""
    b, s, h, d = 1, 1024, 2, 64
    assert not fa._takes_blocked(s, s, d, 2, None)
    q, k, v, g = (x.astype(jnp.bfloat16) for x in _inputs(s, h=h, kvh=h,
                                                          d=d, seed=7))
    got = _fwd_bwd(lambda a, b_, c: fa.flash_attention(a, b_, c, True, None),
                   q, k, v, g)
    scale = 1.0 / math.sqrt(d)

    def resident(a, b_, c):
        return fa._flash_fwd(a, b_, c, True, None)

    out, lse = resident(q, k, v)
    dq, dk, dv = fa._flash_bwd_pallas(q, k, v, out, lse.reshape(b * h, s),
                                      g, True, scale)
    for x, y in zip(got, (out, dq, dk, dv)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_long_causal_call_takes_the_blocked_programs():
    """Past what the resident backward can hold in scoped VMEM a causal
    call takes the blocked programs (8k tokens at head_dim 128 needs 25 MB
    there); they compute plain causal attention."""
    assert fa._takes_blocked(8192, 8192, 128, 2, None)
    q, k, v, g = _inputs(384, seed=9)
    real = fa._takes_blocked
    try:
        fa._takes_blocked = lambda *a: True
        with fa.force_blocks(128, 128):
            got = _fwd_bwd(lambda a, b, c: fa.flash_attention(
                a, b, c, True, None), q, k, v, g)
    finally:
        fa._takes_blocked = real
    want = _fwd_bwd(lambda a, b, c: dense(a, b, c, None), q, k, v, g)
    for x, y in zip(got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5)


def test_the_walks_cover_exactly_the_visible_blocks():
    """The forward's walk per q block and dkv's per K/V block, against a
    count of the blocks holding a visible key: at S 8192, window 1024 and
    blocks 256 / 512 a q block walks at most 3 K/V blocks of 16, and a K/V
    block is seen by at most 6 q blocks of 32."""
    sq = sk = 8192
    first, last, trips = fa._kv_walk(sq, sk, 256, 512, 1024)
    assert trips == 3
    for i in range(sq // 256):
        seen = {kp // 512 for qp in range(i * 256, (i + 1) * 256)
                for kp in (max(qp - 1023, 0), qp)}
        assert int(first(i)) == min(seen) and int(last(i)) == max(seen)
    q_first, q_last, q_trips = fa._block_span(
        sk // 512, 256, lambda j: fa._seeing_queries(j, 512, 0, 1024, sq))
    assert q_trips == 6
    assert (int(q_first(0)), int(q_last(0))) == (0, 5)
    assert (int(q_first(15)), int(q_last(15))) == (30, 31)


def test_cost_counts_the_windowed_work():
    s, w = 8192, 1024
    full = fa.flash_attention_cost((2, s, 32, 128), causal=True)
    win = fa.flash_attention_cost((2, s, 32, 128), causal=True, window=w)
    keys = w * (w + 1) / 2 + (s - w) * w
    assert win.flops == 4.0 * 2 * 32 * 128 * keys
    assert win.bytes == full.bytes
    assert 0.2 < win.flops / full.flops < 0.25     # ~ 2 W / S
    assert fa.flash_attention_cost((2, s, 32, 128), causal=True, window=w,
                                   train=True).flops == 3.5 * win.flops


def test_sdpa_window_on_the_jnp_path():
    """``scaled_dot_product_attention(window=)`` off the TPU: the same
    masked attention."""
    import paddle_tpu as paddle
    from paddle_tpu.nn import functional as F
    q, k, v, _ = _inputs(96, h=4, kvh=2, d=32, seed=11)
    got = F.scaled_dot_product_attention(paddle.to_tensor(q),
                                         paddle.to_tensor(k),
                                         paddle.to_tensor(v), is_causal=True,
                                         window=24)
    np.testing.assert_allclose(got.numpy(), np.asarray(dense(q, k, v, 24)),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        F.scaled_dot_product_attention(paddle.to_tensor(q),
                                       paddle.to_tensor(k),
                                       paddle.to_tensor(v), window=24)
