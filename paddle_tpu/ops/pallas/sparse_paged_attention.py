"""Block-sparse paged attention (Pallas/TPU): each query and KV group reads
only the pages it attends.

``ops.sparse_select`` states the rule and its jnp oracle. Here, per query
at position ``p`` (``ctx + j``) and KV group ``g``:

- ``p + 1 <= dense_len``: pages ``0 .. p // page`` of the slot's table (the
  causal history);
- otherwise the ``topk`` blocks of the selection (a block is one page),
  ascending, so the last one holds ``p``.

Why one program per (slot, tile of queries, KV group) reading exactly the
selected pages, and not a tile of queries over the union of their blocks:
every query chooses afresh and each KV group on its own, so within a
prefill chunk the union of a tile's choices is nearly the whole history —
a union design reads the history once per tile and masks most of it away,
this one reads 64 pages per query and group and masks only the last page's
keys past ``p``. The MXU tile is the group's query heads (16 rows) by the
keys of ``CB`` pages; the work is bound by the copies either way, and
these copies are exactly what the rule asks for. The kernel counts the
page copies it starts for the sparse queries (a scalar in SMEM, summed over
the grid), so ``sparse_blocks_loaded`` is what it read, not what the rule
says it should.

Structure (``ops.pallas.ragged_paged_attention``'s idioms): the pools stay
in HBM (``ANY``) in the one pool layout ``[num_pages, page, G * D]``; a copy
is the ``[page, D]`` column slice of one page for one KV group; the block
lists of a tile ride in SMEM; a program walks its queries one after the
other and each query's pages ``CB`` at a time, double-buffered through VMEM
(the next unit's copies overlap this unit's compute), with an online
softmax in float32 per query. Rows past a slot's valid count are zeros and
copy nothing.

The call is ONE module-level jitted function whose static arguments are
everything decided at trace time, so the layers of a stack share one jaxpr
and one lowered kernel body (``ops.pallas.ragged_paged_attention`` says what
a call site costs the host otherwise); the kernel's name in a trace is
``sparse_paged_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["sparse_paged_attention"]

_NEG = -1e30
#: pages a unit of copies and compute holds: 16 pages of 64 keys = 1,024
#: keys, a [16, 1024] float32 score tile; two K and two V buffers of
#: 16 x 64 x 128 bf16 = 1 MiB of VMEM
_UNIT_PAGES = 16
#: queries a program walks (prefill); a decode step has one a slot
_QUERIES_PER_PROGRAM = 8


def _kernel(ctx_ref, len_ref, tbl_ref, lst_ref, q_ref, k_hbm, v_hbm, o_ref,
            n_ref, k_buf, v_buf, sem, *, scale, page, tq, topk, dense_len, cb,
            pages_per_seq):
    b, g, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _, rep, d = q_ref.shape
    ctx, length = ctx_ref[b], len_ref[b]
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((b == 0) & (g == 0) & (t == 0))
    def _():
        n_ref[0] = 0
    log_page = page.bit_length() - 1

    def count(u):
        """Pages query ``u`` of the tile reads (0: past the valid count)."""
        j = t * tq + u
        p = ctx + j
        n = jnp.where(p + 1 <= dense_len, p // page + 1, topk)
        return jnp.where(j < length, n, 0)

    def copies(u, c, slot, wait):
        """Start — or wait for — unit ``c`` of query ``u``'s pages into
        buffer ``slot``."""
        dense = ctx + t * tq + u + 1 <= dense_len
        n = count(u)

        def one(i, carry):
            blk = jnp.where(dense, i, lst_ref[u, jnp.minimum(i, topk - 1)])
            pid = 0 if wait else tbl_ref[b * pages_per_seq + blk]
            if not wait:        # a sparse query's page, copied: counted
                n_ref[0] += jnp.where(dense, 0, 1).astype(jnp.int32)
            cols = pl.ds(g * d, d)
            for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                cp = pltpu.make_async_copy(src.at[pid, :, cols],
                                           dst.at[slot, i - c * cb],
                                           sem.at[slot])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(c * cb, jnp.minimum((c + 1) * cb, n), one, 0)

    @pl.when(count(0) > 0)
    def _():
        copies(0, 0, 0, False)

    col = jax.lax.broadcasted_iota(jnp.int32, (rep, cb * page), 1)
    key = jax.lax.broadcasted_iota(jnp.int32, (cb * page, 1), 0)

    def query(u, n_units):
        n = count(u)
        p = ctx + t * tq + u
        n_c = (n + cb - 1) // cb
        qv = q_ref[u].astype(jnp.float32)                  # [rep, d]

        def unit(c, carry):
            n_units, acc, m_prev, l_prev = carry
            slot = jax.lax.rem(n_units, 2)

            @pl.when(c + 1 < n_c)
            def _():
                copies(u, c + 1, 1 - slot, False)

            @pl.when((c + 1 >= n_c) & (u + 1 < tq))
            def _():
                @pl.when(count(u + 1) > 0)
                def _():
                    copies(u + 1, 0, 1 - slot, False)

            copies(u, c, slot, True)

            def ok(x):
                # page slot of the list, and the key's offset in its page;
                # every page but the last is whole, the last stops at p
                i = c * cb + jax.lax.shift_right_logical(x, log_page)
                off = jnp.bitwise_and(x, page - 1)
                return (i < n - 1) | ((i == n - 1) & (off <= p % page))

            k = k_buf[slot].reshape(cb * page, d).astype(jnp.float32)
            v = v_buf[slot].reshape(cb * page, d).astype(jnp.float32)
            # what a buffer holds past the unit's pages is an earlier
            # unit's, or nothing yet: masked out of the scores, and zeroed
            # in V (0 * NaN)
            v = jnp.where(ok(key), v, 0.0)
            valid = ok(col)
            s = jax.lax.dot_general(qv, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, _NEG)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return n_units + 1, acc, m_new, l_new

        init = (n_units, jnp.zeros((rep, d), jnp.float32),
                jnp.full((rep, 1), _NEG, jnp.float32),
                jnp.zeros((rep, 1), jnp.float32))
        n_units, acc, _, l = jax.lax.fori_loop(0, n_c, unit, init)

        @pl.when(n > 0)
        def _():
            o_ref[u] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

        return n_units

    jax.lax.fori_loop(0, tq, query, jnp.int32(0))


def sparse_paged_attention(q, key_pages, value_pages, tables, ctx, lengths,
                           blocks, rule, scale=None):
    """q [B, S, H, D]; pools [P, page, G * D] with ``page == rule.block``;
    ``tables`` [B, MP]; ``ctx``, ``lengths`` [B]; ``blocks`` [B, S, G,
    topk] ascending. Returns (out [B, S, H, D], int32 pages the kernel
    copied for the sparse queries, counted as it starts each copy)."""
    S = q.shape[1]
    if key_pages.shape[1] != rule.block:
        raise ValueError(f"the page size {key_pages.shape[1]} must be the "
                         f"sparse block size {rule.block}")
    tq = math.gcd(S, _QUERIES_PER_PROGRAM)
    return _sparse_call(
        q, key_pages, value_pages, tables, ctx, lengths, blocks,
        scale=float(scale if scale is not None
                    else 1.0 / math.sqrt(q.shape[-1])),
        tq=tq, topk=rule.topk, dense_len=rule.dense_len,
        cb=min(_UNIT_PAGES, tables.shape[1]), interpret=bool(_interpret()))


@functools.partial(jax.jit, static_argnames=(
    "scale", "tq", "topk", "dense_len", "cb", "interpret"))
def _sparse_call(q, key_pages, value_pages, tables, ctx, lengths, blocks, *,
                 scale, tq, topk, dense_len, cb, interpret):
    B, S, H, D = q.shape
    _, page, W = key_pages.shape
    G = W // D
    rep = H // G
    MP = tables.shape[1]
    nt = S // tq
    # [B, S, G, R, D] -> [B, G, S, R, D]: a program's block is [tq, R, D]
    qg = jnp.swapaxes(q.reshape(B, S, G, rep, D), 1, 2)
    lst = jnp.swapaxes(blocks.astype(jnp.int32), 1, 2).reshape(
        B, G, nt, tq, topk)
    q_spec = pl.BlockSpec((None, None, tq, rep, D),
                          lambda b, g, t, *_: (b, g, t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # ctx, lengths, block tables
        grid=(B, G, nt),
        in_specs=[
            pl.BlockSpec((None, None, None, tq, topk),
                         lambda b, g, t, *_: (b, g, t, 0, 0),
                         memory_space=pltpu.SMEM),
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # the page copies the grid started for sparse queries, one scalar
        out_specs=[q_spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[
            pltpu.VMEM((2, cb, page, D), key_pages.dtype),
            pltpu.VMEM((2, cb, page, D), value_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with _no_x64():
        out, loaded = pl.pallas_call(
            functools.partial(_kernel, scale=scale, page=page, tq=tq,
                              topk=topk, dense_len=dense_len, cb=cb,
                              pages_per_seq=MP),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(qg.shape, q.dtype),
                       jax.ShapeDtypeStruct((1,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary",
                                     "arbitrary")),
            interpret=interpret,
            name="sparse_paged_attention",
        )(ctx.astype(jnp.int32), lengths.astype(jnp.int32),
          tables.astype(jnp.int32).reshape(-1), lst, qg, key_pages,
          value_pages)
    return jnp.swapaxes(out, 1, 2).reshape(B, S, H, D), loaded[0]
