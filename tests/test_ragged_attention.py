"""Ragged paged-attention kernel parity (ISSUE 7): the Pallas kernel
(always exercised — interpret mode off-TPU) against the jnp oracle
``ragged_paged_attention_reference`` on mixed batches, and the oracle's
own reduction contracts (C == 1 == the decode oracle; lengths == C ==
the prefill oracle)."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.paged_attention import (
    kv_pool_shape, paged_attention_reference,
    paged_prefill_attention_reference, paged_prefill_write,
    ragged_paged_attention_reference)
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    force_ragged_blocks, ragged_paged_attention as kernel)


def _pool_case(rng, B, KVH, D, page, pages_per_seq, total_pages):
    """Shuffled page pool + block tables (page 0 reserved as trash,
    the engine convention)."""
    shape = kv_pool_shape(KVH, total_pages, page, D)
    kp = rng.randn(*shape).astype("float32")
    vp = rng.randn(*shape).astype("float32")
    perm = rng.permutation(total_pages - 1) + 1     # never page 0
    tables = perm[:B * pages_per_seq].reshape(
        B, pages_per_seq).astype("int32")
    return kp, vp, tables


def _run_both(q, kp, vp, tables, ctx, lens, **kw):
    out = kernel(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(tables), jnp.asarray(ctx),
                 jnp.asarray(lens), **kw)
    ref = ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx), jnp.asarray(lens))
    return np.asarray(out), np.asarray(ref)


@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_mixed_batch_kernel_matches_oracle(H, KVH):
    """One invocation covering every slot kind at once: a prefill chunk
    (s > 1), a decode step (s == 1), an idle slot (s == 0), and a
    partial chunk — the unified batching step's operand shape."""
    rng = np.random.RandomState(0)
    B, D, page, P = 4, 16, 4, 8
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 3)
    C = 6
    q = rng.randn(B, C, H, D).astype("float32")
    ctx = np.array([0, 7, 13, 3], "int32")
    lens = np.array([6, 1, 0, 3], "int32")
    out, ref = _run_both(q, kp, vp, tables, ctx, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # padding rows (and the idle slot) are zero in BOTH
    assert np.all(out[2] == 0)
    assert np.all(out[3, 3:] == 0)


def test_pure_prefill_and_pure_decode_batches():
    rng = np.random.RandomState(1)
    B, H, KVH, D, page, P = 3, 4, 2, 8, 4, 6
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    # pure prefill from empty caches (ctx = 0)
    C = 8
    q = rng.randn(B, C, H, D).astype("float32")
    ctx = np.zeros((B,), "int32")
    lens = np.array([8, 5, 2], "int32")
    out, ref = _run_both(q, kp, vp, tables, ctx, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # pure decode (every slot one token over real history)
    q1 = rng.randn(B, 1, H, D).astype("float32")
    ctx = np.array([4, 11, 17], "int32")
    out, ref = _run_both(q1, kp, vp, tables, ctx,
                         np.ones((B,), "int32"))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_page_boundary_straddling_and_one_token_sequences():
    """Ragged lengths that start mid-page, end mid-page, straddle a
    page boundary, or cover exactly one token — the alignments the
    online-softmax block loop must get right."""
    rng = np.random.RandomState(2)
    B, H, KVH, D, page, P = 4, 4, 2, 8, 4, 8
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    C = 7
    q = rng.randn(B, C, H, D).astype("float32")
    # ctx=3,len=2 straddles the first page boundary (3..4 over page=4);
    # ctx=4 starts exactly ON a boundary; ctx=15,len=7 crosses two
    ctx = np.array([3, 4, 15, 0], "int32")
    lens = np.array([2, 7, 7, 1], "int32")
    out, ref = _run_both(q, kp, vp, tables, ctx, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rep", [1, 4, 7, 16])
def test_write_at_a_pages_last_offset_then_read_through_the_kernel(rep):
    """The layout's seam, end to end and against an oracle that never
    sees a pool: dense k/v are written through ``paged_prefill_write``
    (history, then a chunk whose tokens start at, end at, or are alone at
    a page's LAST offset), read back through the kernel, and compared
    with plain causal attention over the dense tensors — at the GQA
    ratios of MHA, Llama-3, Qwen2-7B and Nemotron-3."""
    rng = np.random.RandomState(8)
    B, KVH, D, page, P, C = 4, 2, 8, 4, 6, 5
    H = KVH * rep
    total = B * P + 1
    tables = (rng.permutation(total - 1)[:B * P] + 1).reshape(
        B, P).astype("int32")
    # chunk spans: 3..7 ends on offset 3; 7..9 starts on it; 11 alone on
    # it; 0..4 runs over it
    ctx = np.array([3, 7, 11, 0], "int32")
    lens = np.array([5, 3, 1, 5], "int32")
    T = int((ctx + lens).max())
    k = rng.randn(B, T, KVH, D).astype("float32")
    v = rng.randn(B, T, KVH, D).astype("float32")
    q = rng.randn(B, C, H, D).astype("float32")
    kp = jnp.zeros(kv_pool_shape(KVH, total, page, D), jnp.float32)
    tb = jnp.asarray(tables)
    # the history 0..ctx-1 in one chunk, then the chunk itself
    kp, vp = paged_prefill_write(kp, kp, jnp.asarray(k), jnp.asarray(v),
                                 tb, jnp.zeros((B,), jnp.int32),
                                 jnp.asarray(ctx))
    kc = np.stack([np.pad(k[b, ctx[b]:ctx[b] + C],
                          ((0, C - min(C, T - ctx[b])), (0, 0), (0, 0)))
                   for b in range(B)])
    vc = np.stack([np.pad(v[b, ctx[b]:ctx[b] + C],
                          ((0, C - min(C, T - ctx[b])), (0, 0), (0, 0)))
                   for b in range(B)])
    kp, vp = paged_prefill_write(kp, vp, jnp.asarray(kc), jnp.asarray(vc),
                                 tb, jnp.asarray(ctx), jnp.asarray(lens))
    # a token is ONE row of KVH * D at (its page, its offset)
    for b in range(B):
        pos = int(ctx[b] + lens[b] - 1)
        np.testing.assert_array_equal(
            np.asarray(kp)[tables[b, pos // page], pos % page],
            k[b, pos].reshape(-1))
    out = np.asarray(kernel(jnp.asarray(q), kp, vp, tb, jnp.asarray(ctx),
                            jnp.asarray(lens)))
    scale = 1.0 / np.sqrt(D)
    for b in range(B):
        for j in range(C):
            if j >= lens[b]:
                assert not out[b, j].any()
                continue
            n = int(ctx[b]) + j + 1
            kk = np.repeat(k[b, :n], rep, axis=1).astype("float64")
            vv = np.repeat(v[b, :n], rep, axis=1).astype("float64")
            lg = np.einsum("hd,lhd->hl", q[b, j], kk) * scale
            w = np.exp(lg - lg.max(-1, keepdims=True))
            ref = np.einsum("hl,lhd->hd", w / w.sum(-1, keepdims=True), vv)
            np.testing.assert_allclose(out[b, j], ref, rtol=2e-5,
                                       atol=2e-5)


@pytest.mark.parametrize("qb,g", [(1, 1), (2, 2), (4, 8), (5, 3)])
def test_block_size_grid_is_numerics_invariant(qb, g):
    """q_block / kv_pages_per_block select the schedule, never the
    numbers — including a q_block that does not divide C (padded) and
    a page block that does not divide the table row."""
    rng = np.random.RandomState(3)
    B, H, KVH, D, page, P = 3, 4, 2, 8, 4, 8
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    C = 6
    q = rng.randn(B, C, H, D).astype("float32")
    ctx = np.array([2, 9, 0], "int32")
    lens = np.array([6, 1, 4], "int32")
    out, ref = _run_both(q, kp, vp, tables, ctx, lens,
                         q_block=qb, kv_pages_per_block=g)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_force_ragged_blocks_hook():
    """The tuner trial hook pins blocks for the calling thread only —
    the sweep contract (candidates must not ride set_flags)."""
    rng = np.random.RandomState(4)
    B, H, KVH, D, page, P = 2, 4, 2, 8, 4, 4
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    q = rng.randn(B, 4, H, D).astype("float32")
    ctx = np.array([1, 5], "int32")
    lens = np.array([4, 2], "int32")
    with force_ragged_blocks(2, 1):
        out, ref = _run_both(q, kp, vp, tables, ctx, lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_c1_reduces_to_decode_oracle():
    """The satellite contract: with C == 1 the ragged oracle reduces
    EXACTLY (reduction order included) to the decode oracle at ctx+1,
    and the kernel agrees to float tolerance."""
    rng = np.random.RandomState(5)
    B, H, KVH, D, page, P = 3, 8, 2, 16, 4, 6
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    q = rng.randn(B, 1, H, D).astype("float32")
    ctx = np.array([0, 6, 19], "int32")
    ones = np.ones((B,), "int32")
    ragged = ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx), jnp.asarray(ones))
    dec = paged_attention_reference(
        jnp.asarray(q[:, 0]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx + 1))
    np.testing.assert_allclose(np.asarray(ragged[:, 0]),
                               np.asarray(dec), rtol=1e-6, atol=1e-6)
    out = kernel(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(tables), jnp.asarray(ctx),
                 jnp.asarray(ones))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(dec),
                               rtol=2e-5, atol=2e-5)


def test_full_lengths_reduce_to_prefill_oracle():
    """lengths == C makes the ragged oracle exactly the chunked-prefill
    oracle — a whole chunk is a special case of the ragged entry
    point."""
    rng = np.random.RandomState(6)
    B, H, KVH, D, page, P = 2, 4, 2, 8, 4, 6
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    C = 5
    q = rng.randn(B, C, H, D).astype("float32")
    ctx = np.array([2, 9], "int32")
    full = np.full((B,), C, "int32")
    ragged = ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx), jnp.asarray(full))
    pre = paged_prefill_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx))
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(pre),
                               rtol=0, atol=0)


@pytest.mark.slow
def test_bf16_pool_gqa_wide_case():
    """Breadth: bf16 pools (the TPU serving dtype), 8:2 GQA, longer
    histories — kernel vs oracle at bf16 tolerance."""
    rng = np.random.RandomState(7)
    B, H, KVH, D, page, P = 4, 8, 2, 32, 8, 8
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 2)
    kp = kp.astype(jnp.bfloat16)
    vp = vp.astype(jnp.bfloat16)
    C = 8
    q = rng.randn(B, C, H, D).astype(jnp.bfloat16)
    ctx = np.array([0, 13, 27, 51], "int32")
    lens = np.array([8, 3, 1, 8], "int32")
    out = kernel(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(tables), jnp.asarray(ctx),
                 jnp.asarray(lens), q_block=4, kv_pages_per_block=2)
    ref = ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx), jnp.asarray(lens))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# ---- a window: the loop starts at the window's first block ---------------------

def _window_case(rep, C, ctx, lens, window, seed=0, **blocks):
    rng = np.random.RandomState(seed)
    B, KVH, D, page, P = len(ctx), 2, 16, 4, 24
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 3)
    q = rng.randn(B, C, KVH * rep, D).astype("float32")
    args = [jnp.asarray(a) for a in (q, kp, vp, tables,
                                     np.asarray(ctx, "int32"),
                                     np.asarray(lens, "int32"))]
    out = kernel(*args, window=window, **blocks)
    ref = ragged_paged_attention_reference(*args, window=window)
    return np.asarray(out), np.asarray(ref), args


@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("C,lens", [(1, [1, 1, 1, 0, 1]),
                                    (8, [8, 3, 8, 0, 1])])
def test_window_kernel_matches_oracle(rep, C, lens):
    """Decode and chunk shapes, GQA 1 and 8, with contexts below, at and
    far above the window of 8 (and an idle slot): the kernel that STARTS
    at the window's first block equals the oracle that masks."""
    ctx = [3, 7, 8, 30, 77]
    out, ref, _ = _window_case(rep, C, ctx, lens, window=8)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qb,g", [(1, 1), (2, 2), (4, 8), (8, 3)])
def test_window_is_block_size_invariant(qb, g):
    """Whatever the q block and the pages per K/V block, so wherever the
    first block falls against the window's first key."""
    out, ref, _ = _window_case(4, 8, [5, 19, 61], [8, 5, 8], window=8,
                               q_block=qb, kv_pages_per_block=g)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_a_window_wider_than_every_context_is_no_window():
    out, _, args = _window_case(4, 8, [5, 19, 61], [8, 5, 8], window=1000)
    np.testing.assert_array_equal(out, np.asarray(kernel(*args)))


def test_window_none_is_todays_output_and_a_window_changes_it():
    """``window=None`` is the call every other model makes: the same
    arrays as the call without the argument; the oracle likewise."""
    _, _, args = _window_case(4, 8, [5, 19, 61], [8, 5, 8], window=None)
    np.testing.assert_array_equal(np.asarray(kernel(*args, window=None)),
                                  np.asarray(kernel(*args)))
    np.testing.assert_array_equal(
        np.asarray(ragged_paged_attention_reference(*args, window=None)),
        np.asarray(ragged_paged_attention_reference(*args)))
    assert np.abs(np.asarray(kernel(*args, window=8))
                  - np.asarray(kernel(*args))).max() > 1e-3


def test_a_key_that_left_the_window_is_never_read():
    """Pages wholly before the window's first block hold NaN: a kernel
    that walked them would poison the softmax; the one that starts at the
    first block never copies them."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import first_kv_block
    rng = np.random.RandomState(3)
    B, KVH, D, page, P, C, g = 2, 2, 16, 4, 24, 4, 2
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 3)
    ctx, lens = np.array([50, 71], "int32"), np.array([4, 1], "int32")
    q = rng.randn(B, C, 4, D).astype("float32")
    want = ragged_paged_attention_reference(
        *[jnp.asarray(a) for a in (q, kp, vp, tables, ctx, lens)], window=8)
    for b in range(B):
        dead = int(first_kv_block(ctx[b], 0, 8, g * page)) * g
        assert dead > 0
        kp[tables[b, :dead]] = np.nan
        vp[tables[b, :dead]] = np.nan
    out = kernel(*[jnp.asarray(a) for a in (q, kp, vp, tables, ctx, lens)],
                 window=8, q_block=4, kv_pages_per_block=g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_first_kv_block_holds_the_windows_oldest_key():
    """Blocks of 8 keys, window 8: a decode token at context 0 / 30 / 100
    sees keys 0..0 / 23..30 / 93..100, whose oldest lies in block 0 / 2 /
    11; the fifth query of a chunk on 30 tokens sees 27..34: block 3.
    Without a window, block 0 whatever the context."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import first_kv_block
    ctx = jnp.asarray([0, 30, 100])
    assert np.asarray(first_kv_block(ctx, 0, 8, 8)).tolist() == [0, 2, 11]
    assert int(first_kv_block(jnp.asarray(30), 4, 8, 8)) == 3
    assert first_kv_block(ctx, 0, None, 8) == 0


# ---- every kv head of a sequence in one program, blocks sized to the shape ----

def _int8_pools(rng, shape, KVH, page):
    from paddle_tpu.ops.paged_attention import kv_scales_shape
    sshape = kv_scales_shape(KVH, shape[0], page)
    return (rng.randint(-127, 128, shape).astype("int8"),
            rng.randint(-127, 128, shape).astype("int8"),
            {"k_scales": jnp.asarray(rng.uniform(0.002, 0.02, sshape),
                                     jnp.float32),
             "v_scales": jnp.asarray(rng.uniform(0.002, 0.02, sshape),
                                     jnp.float32)})


@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8kv"])
@pytest.mark.parametrize("C", [1, 16])
@pytest.mark.parametrize("KVH,rep", [(4, 7), (2, 16), (8, 8), (1, 8),
                                     (12, 1)])
def test_kernel_matches_oracle_at_the_cells_head_geometries(KVH, rep, C,
                                                            quant, window):
    """The GQA geometries of the serving cells (Qwen2-7B 4 x 7, Nemotron-H
    2 x 16, K-EXAONE 8 x 8), of one shard under a mesh (1 x 8) and of
    more kv heads than a program owns (12: two programs of 6, each copying
    its columns of a page), at the decode and a chunk shape, plain and
    int8 pools, with and without a window, at the blocks the shape
    resolves to: contexts from inside the
    first K/V block to the fourth (a decode program's blocks are 512
    keys), mid-page and on a page's edge, idle slots between busy ones —
    so a program finds in its buffers what another sequence's left."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import _resolve_blocks
    rng = np.random.RandomState(11)
    B, D, page, P = 7, 16, 16, 100
    ctx = np.array([1500, 0, 37, 0, 0, 1039, 511], "int32")
    lens = np.array([C, 0, C, 0, 0, max(C // 2, 1), C], "int32")
    shape = kv_pool_shape(KVH, B * P + 1, page, D)
    tables = (rng.permutation(B * P) + 1).reshape(B, P).astype("int32")
    q = rng.randn(B, C, KVH * rep, D).astype("float32")
    if quant:
        kp, vp, kw = _int8_pools(rng, shape, KVH, page)
    else:
        kp, vp, kw = (rng.randn(*shape).astype("float32"),
                      rng.randn(*shape).astype("float32"), {})
    qb, g, hp = _resolve_blocks(C, P, page, D, q.dtype, quant, kv_heads=KVH,
                                rep=rep, window=window, pool_dtype=kp.dtype)
    assert hp == (6 if KVH == 12 else KVH)
    assert window is None or g <= window // page + 2
    assert ctx.max() + C > (1 if window else 2) * g * page   # several blocks
    args = [jnp.asarray(a) for a in (q, kp, vp, tables, ctx, lens)]
    ref = np.asarray(ragged_paged_attention_reference(
        *args, window=window, **kw))
    if not quant:
        # every page no query of the batch can see holds NaN: the trash
        # page, an idle slot's pages, a busy slot's pages past its last
        # key and before its window. A block copies none of them, and
        # what a V buffer position keeps from an EARLIER sequence's block
        # is finite — a kernel that copied a whole block of the table
        # would multiply p == 0 into NaN
        seen = np.zeros(shape[0], bool)
        for b in range(B):
            if lens[b]:
                lo = max(ctx[b] - window + 1, 0) // page if window else 0
                seen[tables[b, lo:-(-(ctx[b] + lens[b]) // page)]] = True
        for pool in (kp, vp):
            pool[~seen] = np.nan
        args[1:3] = jnp.asarray(kp), jnp.asarray(vp)
    out = np.asarray(kernel(*args, window=window, **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    assert not out[[1, 3, 4]].any() and not out[5, lens[5]:].any()


@pytest.mark.parametrize("layer,kvh,rep,pps,window,decode,group", [
    ("qwen2-7b", 4, 7, 128, None, (1, 32, 4), (16, 16, 4)),
    ("nemotron-h *", 2, 16, 128, None, (1, 32, 2), (16, 8, 2)),
    ("k-exaone global", 8, 8, 320, None, (1, 32, 8), (16, 16, 8)),
    ("k-exaone window", 8, 8, 320, 128, (1, 8, 8), (16, 8, 8)),
])
@pytest.mark.parametrize("pool", ["bfloat16", "int8"])
def test_blocks_a_cells_shape_resolves_to(layer, kvh, rep, pps, window,
                                          decode, group, pool):
    """(q block, pages a K/V block, kv heads a program) of the serving
    cells' attention layers at page 16, d 128: a decode step owns every
    kv head and walks blocks of 512 keys; a prefill group of 112-128 rows
    256, of 256 rows 128; a window layer the window's 128 — pinned, so a
    change of the defaults is a change of this table (measured: PERF.md
    section 6, PR 34)."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import _resolve_blocks
    for c, want in ((1, decode), (128, group)):
        assert _resolve_blocks(
            c, pps, 16, 128, jnp.bfloat16, pool == "int8", kv_heads=kvh,
            rep=rep, window=window, pool_dtype=jnp.dtype(pool)) == want


def test_blocks_follow_an_explicit_choice_and_the_vmem_budget():
    """The caller's argument and the trial hook win over the default;
    32 kv heads are walked 8 a program; a page of 8 x 256 columns in f32
    (128 KB) leaves the budget for 8 pages a block, not 32."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import _resolve_blocks
    bf = jnp.bfloat16
    assert _resolve_blocks(1, 128, 16, 128, bf, kv_heads=4, rep=7,
                           kv_pages_per_block=4) == (1, 4, 4)
    with force_ragged_blocks(16, 2, 1):
        assert _resolve_blocks(128, 128, 16, 128, bf, kv_heads=4,
                               rep=7) == (16, 2, 1)
    with force_ragged_blocks(16, 2):
        assert _resolve_blocks(128, 128, 16, 128, bf, kv_heads=4,
                               rep=7) == (16, 2, 4)
    assert _resolve_blocks(1, 128, 16, 128, bf, kv_heads=32, rep=1)[2] == 8
    assert _resolve_blocks(1, 128, 16, 256, jnp.float32, kv_heads=8, rep=1,
                           pool_dtype=jnp.float32)[1] == 8


def test_two_forced_candidates_are_two_programs(monkeypatch):
    """The kernel's call is ONE module-level jitted function whose static
    arguments are everything ``_resolve_blocks`` decides: a second trial
    candidate pinned through ``force_ragged_blocks`` in the same process,
    at the same shapes, is a second program (a block choice read INSIDE
    the jitted function would meet the first candidate's trace) and a
    candidate's repeat is a cache hit. The tuner's builder hands every
    candidate of one shape to that function, each as its static
    arguments."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    from paddle_tpu.tuner.sweeps import ragged_attention_builder
    rng = np.random.RandomState(3)
    B, KVH, D, page, P = 2, 2, 16, 8, 6
    kp, vp, tables = _pool_case(rng, B, KVH, D, page, P, B * P + 1)
    q = rng.randn(B, 8, 4, D).astype("float32")
    args = (q, kp, vp, tables, np.array([30, 5], "int32"),
            np.array([8, 1], "int32"))
    n0 = rpa._ragged_call._cache_size()
    for i, cand in enumerate([(8, 1), (8, 2), (8, 1), (8, 2, 1)]):
        with force_ragged_blocks(*cand):
            out, ref = _run_both(*args)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        assert rpa._ragged_call._cache_size() - n0 == (1, 2, 2, 3)[i]
    # the sweep's own path: one builder, three candidates, one shape
    real, got = rpa._ragged_call, []

    def recording(*a, **static):
        got.append((static["q_block"], static["kv_pages"],
                    static["kv_heads"]))
        return real(*a, **static)

    monkeypatch.setattr(rpa, "_ragged_call", recording)
    build = ragged_attention_builder(slots=2, heads=4, kv_heads=2,
                                     dtype="float32")
    shape = {"c": 8, "pages": 4, "page": 8, "d": 16}
    outs = [np.asarray(build({"q_block": 8, "kv_pages_per_block": g},
                             shape)()) for g in (1, 2, 4)]
    assert got == [(8, 1, 2), (8, 2, 2), (8, 4, 2)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-5, atol=2e-5)
