"""Learned block-sparse attention over paged K/V (``ops.sparse_select``, the
Pallas kernel ``ops.pallas.sparse_paged_attention`` in interpret mode):
the kernel against the jnp oracle over dense, sparse and padded queries
with pages scattered through the pool; the incremental compressed-key cache
against the whole prompt's, chunk by chunk, with a reused slot's stale
entries; and the selection's invariants."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops import sparse_select as SS
from paddle_tpu.ops.pallas import sparse_paged_attention as K

RULE = SS.SparseRule(kernel=8, stride=4, block=8, topk=5, init=1, window=16,
                     dense_len=48, tile=8)


def _pools(rng, B, MP, G, D, dtype=jnp.float32):
    P = 1 + B * MP
    kp = jnp.asarray(rng.standard_normal((P, RULE.block, G * D)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, RULE.block, G * D)), dtype)
    # every slot's pages scattered through the pool, page 0 the trash page
    tbl = jnp.asarray(1 + rng.permutation(B * MP).reshape(B, MP), jnp.int32)
    return kp, vp, tbl


@pytest.mark.parametrize("S,ctx,lengths", [
    (8, [70, 10, 0], [8, 5, 0]),        # sparse, dense, idle rows
    (1, [95, 47, 3], [1, 1, 0]),        # decode: sparse, the switch, idle
    (16, [40, 60, 90], [16, 16, 7]),    # a chunk that crosses dense_len
])
def test_kernel_is_the_oracle(S, ctx, lengths):
    rng = np.random.default_rng(S)
    B, G, R, D, MP = 3, 2, 2, 16, 13
    kp, vp, tbl = _pools(rng, B, MP, G, D)
    ctx = jnp.asarray(ctx, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, S, G * R, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, 26, G * D)), jnp.float32)
    blocks = SS.select_blocks(q, ck, ctx, RULE, MP)
    want = SS.sparse_paged_attention_reference(q, kp, vp, tbl, ctx, lengths,
                                               blocks, RULE)
    got, loaded = K.sparse_paged_attention(q, kp, vp, tbl, ctx, lengths,
                                           blocks, RULE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    p = np.asarray(ctx)[:, None] + np.arange(S)
    sparse = (np.arange(S) < np.asarray(lengths)[:, None]) & (p + 1 > 48)
    assert int(loaded) == sparse.sum() * G * RULE.topk


def test_the_oracle_reads_only_the_selected_blocks():
    """A key outside a sparse query's blocks moves nothing: poisoning every
    page no query chose leaves the output as it was."""
    rng = np.random.default_rng(3)
    B, G, R, D, MP = 1, 2, 2, 16, 13
    kp, vp, tbl = _pools(rng, B, MP, G, D)
    ctx, lengths = jnp.asarray([90]), jnp.asarray([1])
    q = jnp.asarray(rng.standard_normal((B, 1, G * R, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, 26, G * D)), jnp.float32)
    blocks = SS.select_blocks(q, ck, ctx, RULE, MP)
    got, _ = K.sparse_paged_attention(q, kp, vp, tbl, ctx, lengths, blocks,
                                      RULE)
    used = set(np.asarray(tbl)[0, np.asarray(blocks)[0, 0].ravel()])
    dead = [pg for pg in range(kp.shape[0]) if pg not in used]
    kp2 = kp.at[jnp.asarray(dead)].set(1e4)
    vp2 = vp.at[jnp.asarray(dead)].set(jnp.nan)
    got2, _ = K.sparse_paged_attention(q, kp2, vp2, tbl, ctx, lengths,
                                       blocks, RULE)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got), atol=1e-6)


def test_the_selection_holds_its_forced_blocks():
    rng = np.random.default_rng(5)
    B, G, R, D, MP = 1, 2, 2, 16, 13
    S, ctx = 40, 60
    q = jnp.asarray(rng.standard_normal((B, S, G * R, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, 26, G * D)), jnp.float32)
    blocks = np.asarray(SS.select_blocks(q, ck, jnp.asarray([ctx]), RULE,
                                         MP))[0]
    for j in range(S):
        p = ctx + j
        for g in range(G):
            b = blocks[j, g]
            assert list(b) == sorted(set(b)) and b[-1] == p // RULE.block
            assert 0 in b and b.max() * RULE.block <= p
            for m in range(max(p - RULE.window + 1, 0) // RULE.block,
                           p // RULE.block + 1):
                assert m in b


@pytest.mark.parametrize("chunk", [12, 5, 100])
def test_compressed_keys_do_not_depend_on_the_chunking(chunk):
    """Entries written chunk by chunk (boundaries inside a block and inside
    an entry's keys) are the whole prompt's; a reused slot's stale rows past
    the prompt are never among the entries a query may read."""
    rng = np.random.default_rng(chunk)
    G, D, MP, T = 2, 16, 13, 100
    kp, vp, tbl = _pools(rng, 1, MP, G, D)
    keys = jnp.asarray(rng.standard_normal((1, T, G, D)), jnp.float32)
    NC = 26
    stale = jnp.asarray(rng.standard_normal((1, NC, G * D)), jnp.float32)
    ck, kp_c = stale, kp
    for a in range(0, T, chunk):
        n = min(chunk, T - a)
        part = jnp.pad(keys[:, a:a + n], [(0, 0), (0, chunk - n), (0, 0),
                                          (0, 0)])
        kp_c, _ = PA.paged_prefill_write(kp_c, vp, part, part, tbl,
                                         jnp.asarray([a]), jnp.asarray([n]))
        ck = SS.compressed_update(ck, kp_c, tbl, jnp.asarray([a]),
                                  jnp.asarray([n]), RULE,
                                  -(-chunk // RULE.stride))
    want = np.stack([np.asarray(keys[0, 4 * i:4 * i + 8]).mean(0).reshape(-1)
                     for i in range((T - 8) // 4 + 1)])
    np.testing.assert_allclose(np.asarray(ck[0, :len(want)]), want,
                               atol=1e-6)
    # rows past the last complete entry are the stale ones, untouched
    np.testing.assert_array_equal(np.asarray(ck[0, len(want):]),
                                  np.asarray(stale[0, len(want):]))
