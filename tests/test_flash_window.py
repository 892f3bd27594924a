"""The flash kernel's window (``ops/pallas/flash_attention.py``, interpret
mode): forward, dq and dkv against a dense masked ``jax.numpy`` reference at
windows that are and are not multiples of the block; K/V blocks wholly
outside every window held as NaN, which only the walk keeps out;
``window=None`` at GPT-2's shape taking the resident programs bit for bit;
the blocked programs for a long causal call; their schedule (each pair's
kind against the explicit mask, the walk, the counters) and their unmasked
whole pairs against the masked walk; the cost surface."""

import importlib.util
import math
import os

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


def _explicit_kinds(sq, sk, block_q, block_k, window):
    """{(q block, K/V block): kind} from the explicit mask over the padded
    blocks (a padded row or key is masked): the brute force the schedule
    is held to."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    q = np.arange(nq * block_q)[:, None]
    k = np.arange(nk * block_k)[None, :]
    m = (q < sq) & (k < sk) & (k <= q + sk - sq)
    if window is not None:
        m &= k > q + sk - sq - window
    m = m.reshape(nq, block_q, nk, block_k)
    seen, every = m.any((1, 3)), m.all((1, 3))
    return {(i, j): fa._FULL if every[i, j] else fa._CUT if seen[i, j]
            else fa._NONE for i in range(nq) for j in range(nk)}


def _walked(sq, sk, block_q, block_k, window, kv_major):
    """{(q block, K/V block): kind} of the pairs a schedule visits."""
    row, col, flags = fa._schedule(sq, sk, block_q, block_k, window,
                                   kv_major)
    got = {}
    for r, c, f in zip(row.tolist(), col.tolist(), flags.tolist()):
        if f & 3 != fa._NONE:
            got[(c, r) if kv_major else (r, c)] = f & 3
    return got


@pytest.mark.parametrize("blocks", [(128, 128), (256, 512)],
                         ids=["b128x128", "b256x512"])
@pytest.mark.parametrize("sq,sk,window", [
    (384, 384, 128), (384, 384, 200), (384, 384, 1000), (384, 384, None),
    (640, 640, 128), (640, 640, 200), (640, 640, 1000), (640, 640, None),
    (256, 768, 256), (320, 704, None), (320, 704, 200), (640, 384, 200)],
    ids=lambda x: "none" if x is None else str(x))
def test_schedule_kinds_match_the_explicit_mask(sq, sk, window, blocks):
    """Every pair's kind against a brute-force classification of the
    explicit mask (windows of one block, of no multiple of it, past the
    sequence, none; more keys than queries, fewer, padded tails); the
    forward's walk and dkv's visit exactly the visible pairs, each under
    its kind."""
    bq, bk = blocks
    want = _explicit_kinds(sq, sk, bq, bk, window)
    for (i, j), kind in want.items():
        assert fa._pair_kind(i, j, bq, bk, sq, sk, window) == kind, (i, j)
    visible = {p: kind for p, kind in want.items() if kind != fa._NONE}
    assert _walked(sq, sk, bq, bk, window, False) == visible
    assert _walked(sq, sk, bq, bk, window, True) == visible


@pytest.mark.parametrize("window,full,cut", [(1024, 30, 60),
                                             (None, 240, 32)],
                         ids=["w1024", "causal"])
def test_schedule_counts_at_the_cells_shape(window, full, cut):
    """S 8,192, blocks 256 / 512: a window layer walks 30 whole pairs and
    60 cut ones a head, the full layer 240 and 32 — over Mellum2's three
    window layers and one full layer 330 whole + 212 cut, 542 grid steps
    a head where the clamped walk took 800 (3 x 96 + 512). The kernel
    timer's count from the explicit mask reads the same."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "flash_bench.py")
    spec = importlib.util.spec_from_file_location("flash_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.pair_counts(8192, 8192, 256, 512, window) == (full, cut)
    for kv_major in (False, True):
        kinds = fa._schedule(8192, 8192, 256, 512, window, kv_major)[2] & 3
        assert (int((kinds == fa._FULL).sum()),
                int((kinds == fa._CUT).sum()), len(kinds)) \
            == (full, cut, full + cut)


def test_the_walks_cover_exactly_the_visible_blocks():
    """Each row's steps are consecutive, in ascending order, the first
    flagged ``_FIRST`` and the last ``_LAST``; a row that sees nothing
    takes one step of kind ``_NONE`` (both flags) that stays on the block
    the walk is on. At S 8192, window 1024, blocks 256 / 512 a q block
    walks at most 3 K/V blocks of 16 and a K/V block at most 6 q blocks
    of 32; with more queries than keys (640 over 384, window 200) the
    first q block sees nothing."""
    for sq, sk, window, kv_major in [(8192, 8192, 1024, False),
                                     (8192, 8192, 1024, True),
                                     (640, 384, 200, False),
                                     (640, 384, 200, True)]:
        row, col, flags = fa._schedule(sq, sk, 256 if sq > 640 else 128,
                                       512 if sq > 640 else 128, window,
                                       kv_major)
        runs = {}
        for t, r in enumerate(row.tolist()):
            runs.setdefault(r, []).append(t)
        assert sorted(runs) == list(range(len(runs)))
        for r, steps in runs.items():
            assert steps == list(range(steps[0], steps[-1] + 1))
            cols = col[steps].tolist()
            assert cols == sorted(cols)
            f = flags[steps].tolist()
            assert [bool(x & fa._FIRST) for x in f] == \
                [True] + [False] * (len(f) - 1)
            assert [bool(x & fa._LAST) for x in f] == \
                [False] * (len(f) - 1) + [True]
            if f[0] & 3 == fa._NONE:
                assert len(f) == 1 and (steps[0] == 0
                                        or col[steps[0] - 1] == cols[0])
        if sq == 8192:
            assert max(map(len, runs.values())) == (6 if kv_major else 3)
    row, _, flags = fa._schedule(640, 384, 128, 128, 200, False)
    assert flags[0] & 3 == fa._NONE and row[1] == 1


@pytest.mark.parametrize("sq,sk,window,block", [
    (640, 640, 300, (128, 128)), (320, 704, None, (128, 128)),
    (576, 576, 200, (128, 256)), (8192, 8192, 1024, (256, 512))],
    ids=["w300", "sk_gt_sq_padded", "w200_padded", "cell"])
def test_mask_keeps_the_explicit_logits(sq, sk, window, block):
    """``_mask`` of every pair (written over ``k - q``, with padding
    compares only where a block is padded) against the mask written over
    both positions; at the cell's shape, the pairs of the first rows."""
    bq, bk = block
    nq = min(-(-sq // bq), 6)
    k = np.arange(-(-sk // bk) * bk)[None, :]
    for i in range(nq):
        q = np.arange(i * bq, (i + 1) * bq)[:, None]
        want = (q < sq) & (k < sk) & (k <= q + sk - sq)
        if window is not None:
            want &= k > q + sk - sq - window
        for j in range(k.shape[1] // bk):
            got = fa._mask(i * bq, j * bk, (bq, bk), sk - sq, window, sq, sk)
            assert np.array_equal(np.asarray(got),
                                  want[:, j * bk:(j + 1) * bk]), (i, j)


#: shapes whose pairs hold all three kinds: a window, plain causal, more
#: keys than queries with padded tails, padded tails under a window, and
#: blocks 256 / 512
THREE_KINDS = [(640, 640, 300, 128, 128), (384, 384, None, 128, 128),
               (320, 704, None, 128, 128), (576, 576, 300, 128, 128),
               (1536, 1536, 1000, 256, 512)]
THREE_KIND_IDS = ["w300", "causal", "sk_gt_sq_padded", "w300_padded",
                  "w1000_b256x512"]


def _blocked(sq, sk, window, bq, bk, monkeypatch, seed=1):
    q, k, v, g = _inputs(sq, sk=sk, seed=seed)
    kinds = set(_explicit_kinds(sq, sk, bq, bk, window).values())
    assert kinds == {fa._NONE, fa._CUT, fa._FULL}
    monkeypatch.setattr(fa, "_takes_blocked", lambda *a: True)
    with fa.force_blocks(bq, bk):
        got = _fwd_bwd(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, window), q, k, v, g)
    return got, (q, k, v, g)


@pytest.mark.parametrize("sq,sk,window,bq,bk", THREE_KINDS,
                         ids=THREE_KIND_IDS)
def test_unmasked_pairs_match_the_masked_walk(sq, sk, window, bq, bk,
                                              monkeypatch):
    """fwd, dq and dkv against the same walk with every visible pair
    masked — each pair's work as the programs did it before whole pairs
    went unmasked: equal to float32 rounding (a few ulps of the largest
    value), not merely to a tolerance against the reference."""
    got, args = _blocked(sq, sk, window, bq, bk, monkeypatch)
    real = fa._schedule

    def all_cut(*a):
        row, col, flags = real(*a)
        whole = (flags & 3) == fa._FULL
        return row, col, np.where(whole, flags - fa._FULL + fa._CUT,
                                  flags).astype(np.int32)

    monkeypatch.setattr(fa, "_schedule", all_cut)
    with fa.force_blocks(bq, bk):
        masked = _fwd_bwd(lambda a, b, c: fa.flash_attention(
            a, b, c, True, None, window), *args[:3], args[3])
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, masked):
        x, y = np.asarray(x), np.asarray(y)
        assert np.abs(x - y).max() <= 8 * np.finfo(np.float32).eps \
            * np.abs(y).max(), name


@pytest.mark.parametrize("sq,sk,window,bq,bk", THREE_KINDS,
                         ids=THREE_KIND_IDS)
def test_three_kinds_match_dense_under_gqa(sq, sk, window, bq, bk,
                                           monkeypatch):
    """fwd, dq and dkv of the blocked programs (4 query heads over 2 K/V
    heads) against the dense masked reference."""
    got, (q, k, v, g) = _blocked(sq, sk, window, bq, bk, monkeypatch)
    want = _fwd_bwd(lambda a, b, c: dense(a, b, c, window), q, k, v, g)
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5, err_msg=name)


def test_counters_read_the_schedule(monkeypatch):
    """A traced forward books the forward's walk, its backward dkv's and
    dq's, summed over the call's heads (batch x query heads)."""
    from paddle_tpu.profiler import metrics
    sq, sk, window, bq, bk = THREE_KINDS[0]
    reg = metrics.get_registry()
    names = ("flash/blocks_full", "flash/blocks_cut", "flash/grid_steps")
    before = [reg.counter(n).value for n in names]
    _blocked(sq, sk, window, bq, bk, monkeypatch)
    booked = [reg.counter(n).value - b for n, b in zip(names, before)]
    heads = 1 * 4
    want = [0, 0, 0]
    for kv_major in (False, False, True):      # fwd, dq, dkv
        kinds = fa._schedule(sq, sk, bq, bk, window, kv_major)[2] & 3
        for n, x in enumerate(((kinds == fa._FULL).sum(),
                               (kinds == fa._CUT).sum(), len(kinds))):
            want[n] += int(x) * heads
    assert booked == want
    assert want[0] > 0 and want[1] > 0


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
