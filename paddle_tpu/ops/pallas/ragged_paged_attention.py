"""Ragged paged-attention Pallas kernel for TPU serving.

ONE kernel for the whole mixed prefill+decode batching step (PAPERS.md
"Ragged Paged Attention"): the query operand is a flattened token
stream — slot ``b``'s tokens are the ``[start, length]`` window
``[b * C, b * C + lengths[b])`` of the stream, exposed here in its
uniform-stride ``[B, C, H, D]`` view — and every sequence, whether a
multi-token prefill chunk (s > 1), a single decode step (s == 1), or an
idle slot (s == 0), flows through the same grid. No separate prefill
and decode program families, so the serving engine compiles exactly one
batching-step signature.

Semantics (identical to the jnp oracle
``ops.paged_attention.ragged_paged_attention_reference``): the chunk's
k/v were already written into the paged pool at cache positions
``ctx[b] .. ctx[b] + lengths[b] - 1`` (``paged_prefill_write``; chunk
padding rides the reserved trash page 0), and query token ``j`` of
sequence ``b`` attends every cache position ``<= ctx[b] + j`` — full
paged history behind it, causal within the chunk. Rows ``j >=
lengths[b]`` output zeros. With a static ``window`` (a layer that
attends its ``window`` newest keys) query ``j`` sees positions ``ctx[b]
+ j - window + 1 .. ctx[b] + j`` only, and the loop over K/V blocks
STARTS at the block that holds the oldest key the q block's first query
sees (:func:`first_kv_block`): what left the window is never copied.
``window=None`` compiles the program without one.

Kernel structure (the jax paged-attention decode kernel's scalar-
prefetch idiom, generalized to ragged multi-token queries):

- grid ``(kv_head, sequence, q_block)`` — one program per kv head per
  sequence-block of the token stream;
- the wrapper lays q out kv-head-major, ``[B, KVH, C * rep, D]`` with
  ``row = token * rep + head-in-group``, so a q/out block is the 2-D
  ``[rows, D]`` tile the TPU lowering requires (``rows`` a multiple of
  8, or the whole row-padded chunk) whatever the GQA ratio ``rep``;
- block tables / context lens / lengths ride scalar prefetch, so only
  the pages a sequence actually owns are streamed;
- K/V pools stay in HBM (``ANY`` memory space) in the one pool layout,
  ``[num_pages, page_size, KVH * D]`` (``ops.paged_attention``'s module
  docstring); each grid step DMAs the ``[page_size, D]`` tiles of its kv
  head (columns ``h * D .. (h + 1) * D``, a lane-aligned slice) of
  ``kv_pages_per_block`` pages named in the block table into a
  double-buffered VMEM scratch (next block's copy overlaps the current
  block's compute) and accumulates with an online softmax in fp32.

Block sizes (``q_block``, ``kv_pages_per_block``) are a registered
tunable surface ("ragged_paged_attention") swept by ``bench.py
--autotune`` / the tuner CLI; explicit flags win over cached winners
(the flash_attention precedence contract).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["ragged_paged_attention", "force_ragged_blocks",
           "ragged_attention_cost", "first_kv_block"]

_NEG_INF = -1e30

# sweep hook: the trial engine pins candidate blocks here while it
# compiles fresh variants (thread-local, same contract as
# flash_attention.force_blocks — candidates must not ride set_flags).
import threading as _threading

_forced_tls = _threading.local()


class force_ragged_blocks:
    """Context manager pinning (q_block, kv_pages_per_block) for tuner
    trials (this thread only)."""

    def __init__(self, q_block, kv_pages_per_block):
        self._val = (int(q_block), int(kv_pages_per_block))

    def __enter__(self):
        self._prev = getattr(_forced_tls, "blocks", None)
        _forced_tls.blocks = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.blocks = self._prev
        return False


def _resolve_blocks(c, pages_per_seq, page, d, dtype, quant=False):
    """(q_block, kv_pages_per_block) for this shape, precedence: forced
    trial candidate > explicit user flag > tuner cache > default.
    Host-side at trace time — static ints selecting the compiled
    grid. Quantized pools add a ``kvq`` component to the shape sig so
    bf16 cache entries can't poison quantized configs (and vice versa);
    bf16 shapes keep the historical sig. The wrapper then rounds
    q_block up to one the TPU lowering accepts (:func:`_row_blocking`;
    the tuner surface only offers such blocks, a flag may not)."""
    from ...framework import flags
    forced = getattr(_forced_tls, "blocks", None)
    if forced is not None:
        qb, g = forced
    else:
        qb = int(flags.flag("FLAGS_ragged_attn_q_block"))
        g = int(flags.flag("FLAGS_ragged_attn_kv_pages"))
        qb_explicit = flags.flag_source(
            "FLAGS_ragged_attn_q_block") != "default"
        g_explicit = flags.flag_source(
            "FLAGS_ragged_attn_kv_pages") != "default"
        if not (qb_explicit and g_explicit):
            from ...tuner import lookup
            shape_sig = {"c": int(c), "pages": int(pages_per_seq),
                         "page": int(page), "d": int(d)}
            if quant:
                shape_sig["kvq"] = 1
            cfg = lookup("ragged_paged_attention", shape_sig,
                         str(dtype))
            if cfg:
                if not qb_explicit:
                    qb = int(cfg.get("q_block", qb))
                if not g_explicit:
                    g = int(cfg.get("kv_pages_per_block", g))
    # clamp to the shape: q blocks never exceed the chunk, page blocks
    # never exceed the table row
    qb = max(1, min(qb, c))
    g = max(1, min(g, pages_per_seq))
    return qb, g


def first_kv_block(ctx, q_start, window, bk):
    """The first K/V block (of ``bk`` keys) a q block reads whose first
    token is chunk token ``q_start`` of a sequence with ``ctx`` cached
    tokens: the one that holds key ``ctx + q_start - window + 1``, the
    oldest its first query sees. THE rule of a window layer's reads: the
    kernel's loop, its first copy and the scales it gathers all start
    there. Without a window, block 0."""
    if window is None:
        return 0
    return jnp.maximum(ctx + q_start - (window - 1), 0) // bk


def _ragged_kernel(ctx_ref, len_ref, tbl_ref, q_ref, k_hbm_ref,
                   v_hbm_ref, *rest, scale, page, q_block, rep, g_pages,
                   pages_per_seq, quant, window):
    """One program: (kv head h, sequence b, q block qi). Streams the
    sequence's pages through the double-buffered VMEM scratch and
    accumulates an online softmax over them.

    The q/out block is 2-D ``[rows, d]`` with ``row = token * rep +
    head-in-group`` (the wrapper lays the stream out kv-head-major), so
    its last two dims are the tile-aligned ones Mosaic requires and the
    body never reshapes across the sublane/lane boundary.

    ``quant``: the data pools are int8 (or fp8); the per-token scales of
    the sequence's pages arrive lane-dense as ``[n_kv_blocks, bk]`` rows
    (gathered through the SAME block table by the wrapper) and are
    applied on the key axis of the scores / probabilities —
    ``(q . code_j) * ks_j`` and ``(p_j * vs_j) . code_j`` — which equals
    dequantize-then-dot in exact arithmetic and keeps the softmax in
    fp32."""
    if quant:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    h = pl.program_id(0)
    b = pl.program_id(1)
    qi = pl.program_id(2)
    rows, d = q_ref.shape          # q rows of this block (padded)
    bk = g_pages * page            # keys per kv block
    ctx = ctx_ref[b]
    length = len_ref[b]
    q_start = qi * q_block         # first chunk token of this q block

    # rows past the valid count output zeros (also covers idle slots,
    # length == 0, whose programs skip the whole loop)
    o_ref[...] = jnp.zeros_like(o_ref)

    def dma_block(i, slot):
        """Async copies for kv block i into buffer `slot` — one copy
        per page named in the block table (clamped into the row; the
        overhang past ceil(n_kv/page) pages is masked out below).
        Each buffer slot owns its OWN semaphore: every page copy has
        the same byte count, so a shared counter would let block
        i+1's prefetch completions satisfy a wait for block i and
        hand compute a partially-copied buffer."""
        copies = []
        cols = pl.ds(h * d, d)     # this kv head's columns of a token row
        for gidx in range(g_pages):
            pidx = jnp.minimum(i * g_pages + gidx, pages_per_seq - 1)
            pid = tbl_ref[b * pages_per_seq + pidx]
            copies.append(pltpu.make_async_copy(
                k_hbm_ref.at[pid, :, cols], k_buf.at[slot, gidx],
                sem.at[slot]))
            copies.append(pltpu.make_async_copy(
                v_hbm_ref.at[pid, :, cols], v_buf.at[slot, gidx],
                sem.at[slot]))
        return copies

    @pl.when(q_start < length)
    def compute():  # noqa: ANN001 — pl.when body
        # last key any row of this block may see (+1): the block's last
        # valid token at chunk offset min(q_start + q_block, length) - 1
        n_kv = ctx + jnp.minimum(q_start + q_block, length)
        n_blocks = (n_kv + bk - 1) // bk
        # a window layer's loop starts at the block of the oldest key its
        # first query sees; the blocks before it are never copied
        i0 = first_kv_block(ctx, q_start, window, bk)

        for c in dma_block(i0, 0 if window is None else jax.lax.rem(i0, 2)):
            c.start()

        q2 = q_ref[...].astype(jnp.float32) * scale      # [rows, d]
        # row r is chunk token q_start + r // rep. The masks below are
        # the division-free forms of
        #   k_pos <= ctx + q_tok   and   q_tok < length
        # (x <= r // rep  <=>  x * rep <= r for integer x, rep > 0).
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        row_ok = row < (length - q_start) * rep

        def body(i, carry):
            acc, m_prev, l_prev = carry
            slot = jax.lax.rem(i, 2)
            nslot = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < n_blocks)
            def _():
                for c in dma_block(i + 1, nslot):
                    c.start()

            for c in dma_block(i, slot):
                c.wait()
            # widen BEFORE collapsing (g, page) -> bk: f32 tiles are 8
            # sublanes, so the collapse is layout-free for any page
            # size that is a multiple of 8 (int8/bf16 tiles are not)
            k = k_buf[slot].astype(jnp.float32).reshape(bk, d)
            v = v_buf[slot].astype(jnp.float32).reshape(bk, d)
            s = jax.lax.dot_general(
                q2, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [rows, bk]
            if quant:
                s = s * ks_ref[pl.ds(i, 1), :]
            # causal over the paged history + the row-validity mask
            # (rows past `length` stay fully masked -> zero output)
            valid = ((col + (i * bk - ctx - q_start)) * rep <= row) & row_ok
            if window is not None:
                # k_pos > q_pos - window, division-free like the above
                # (x > r // rep  <=>  x * rep > r)
                valid = valid & ((col + (i * bk - ctx - q_start + window))
                                 * rep > row)
            s = jnp.where(valid, s, _NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * vs_ref[pl.ds(i, 1), :]
            acc = acc * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((rows, d), jnp.float32)
        m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
        acc, m, l = jax.lax.fori_loop(i0, n_blocks, body, (acc0, m0, l0))
        o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


_SUBLANES = 8    # Mosaic: second-to-last block dim % 8, or the whole dim


def _row_blocking(c, qb, rep):
    """(q_block, padded chunk, rows per block) such that the q/out
    block ``[rows, d]`` is one the TPU lowering accepts: ``rows =
    q_block * rep`` a multiple of 8, or — when one block covers the
    chunk — the whole (row-padded) array."""
    step = _SUBLANES // math.gcd(rep, _SUBLANES)
    qb = -(-qb // step) * step
    if qb >= c:                     # one block: pad ROWS, not tokens
        return c, c, -(-c * rep // _SUBLANES) * _SUBLANES
    return qb, -(-c // qb) * qb, qb * rep


def _block_scales(scales, block_tables, g, page):
    """Page-parallel scales pool [P, KVH, page] -> the sequences' own
    scales, lane-dense per kv block: [B, KVH, n_kv_blocks, g * page].
    One XLA gather through the block table (the kernel DMAs only the
    data pages; a per-page scale row is 16-32 lanes, which neither the
    DMA engine nor an in-kernel (g, page) -> (1, bk) relayout
    handles)."""
    kvh = scales.shape[1]
    b, pps = block_tables.shape
    nb = -(-pps // g)
    sc = scales[block_tables]                         # [B, pps, KVH, page]
    sc = jnp.pad(sc, ((0, 0), (0, nb * g - pps), (0, 0), (0, 0)))
    return jnp.swapaxes(sc, 1, 2).reshape(b, kvh, nb, g * page).astype(
        jnp.float32)


def ragged_paged_attention(q, key_pages, value_pages, block_tables,
                           ctx_lens, lengths, scale=None, q_block=None,
                           kv_pages_per_block=None, k_scales=None,
                           v_scales=None, window=None):
    """Mixed prefill+decode paged attention over the flattened token
    stream (uniform-stride view).

    q            [B, C, H, D] — slot b's tokens are the stream window
                 [b*C, b*C + lengths[b]); rows past lengths[b] are
                 padding (zeroed in the output)
    key_pages /  [num_pages, page_size, KVH * D] page pools; the chunk's
    value_pages  k/v already written at ctx .. ctx+len-1
    block_tables [B, pages_per_seq] int32
    ctx_lens     [B] int32 — cache length BEFORE the chunk
    lengths      [B] int32 — valid stream tokens per slot (0 = idle,
                 1 = decode step, >1 = prefill chunk)
    k_scales /   optional [num_pages, KVH, page_size] f32 page-parallel
    v_scales     scales pools — when given, the data pools are int8/fp8
                 and the kernel applies the scales in VMEM
    window       optional static int: query token j sees keys ``ctx + j -
                 window + 1 .. ctx + j`` only, and the loop over K/V
                 blocks starts at :func:`first_kv_block`; None compiles
                 the program without a window
    Returns [B, C, H, D].
    """
    b, c, h, d = q.shape
    _, page, width = key_pages.shape
    kvh = width // d
    rep = h // kvh
    pages_per_seq = block_tables.shape[1]
    quant = k_scales is not None
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qb, g = _resolve_blocks(c, pages_per_seq, page, d, q.dtype,
                            quant=quant)
    if q_block is not None:
        qb = max(1, min(int(q_block), c))
    if kv_pages_per_block is not None:
        g = max(1, min(int(kv_pages_per_block), pages_per_seq))
    qb, c_p, rows = _row_blocking(c, qb, rep)
    n_q = c_p // qb
    # kv-head-major rows: [B, C, KVH, rep, D] -> [B, KVH, C * rep, D]
    qr = jnp.pad(q, ((0, 0), (0, c_p - c), (0, 0), (0, 0)))
    qr = qr.reshape(b, c_p, kvh, rep, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, kvh, c_p * rep, d)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, n_q * rows - c_p * rep),
                      (0, 0)))
    grid = (kvh, b, n_q)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((None, None, rows, d),
                          lambda hh, bb, qq, *_: (bb, hh, qq, 0))
    in_specs = [q_spec,
                any_spec,       # key pages stay in HBM
                any_spec]       # value pages
    operands = [qr, key_pages, value_pages]
    if quant:
        nb = -(-pages_per_seq // g)
        sc_spec = pl.BlockSpec((None, None, nb, g * page),
                               lambda hh, bb, qq, *_: (bb, hh, 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [_block_scales(k_scales, block_tables, g, page),
                     _block_scales(v_scales, block_tables, g, page)]
    scratch = [
        pltpu.VMEM((2, g, page, d), key_pages.dtype),
        pltpu.VMEM((2, g, page, d), value_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),              # one per slot
    ]
    with _no_x64():
        out = pl.pallas_call(
            functools.partial(
                _ragged_kernel, scale=s, page=page, q_block=qb, rep=rep,
                g_pages=g, pages_per_seq=pages_per_seq, quant=quant,
                window=None if window is None else int(window)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,   # ctx, lengths, block tables
                grid=grid,
                in_specs=in_specs,
                out_specs=q_spec,
                scratch_shapes=scratch,
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary",
                                     "arbitrary")),
            out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
            interpret=_interpret(),
            name="ragged_paged_attention",
        )(ctx_lens.astype(jnp.int32), lengths.astype(jnp.int32),
          block_tables.astype(jnp.int32).reshape(-1), *operands)
    out = out[:, :, :c_p * rep].reshape(b, kvh, c_p, rep, d)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c_p, h, d)[:, :c]


# -- tunable surface ---------------------------------------------------------
# q_block / kv_pages_per_block candidate grid, registered next to the
# knob (the flash_attention pattern). No cost_fn: q blocks revisit the
# whole page list, so byte traffic scales with the q-block COUNT — the
# trial engine times every valid candidate rather than trusting a
# first-order roofline that would mispredict the DMA-overlap win of
# larger page blocks. Shape key: (c, pages, page, d). Only blocks the
# TPU lowering takes as given are offered: q_block a multiple of 8 (so
# q_block * rep rows are tile-aligned for every GQA ratio) or the whole
# chunk — anything else _row_blocking would round, and a trial would
# time a block it did not ask for.

def _q_block_accepted(qb, c):
    return qb >= c or qb % _SUBLANES == 0


def _register_ragged_surface():
    from ...tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        c = max(int(shape.get("c", 16)), 1)
        pages = max(int(shape.get("pages", 8)), 1)
        qbs = sorted({qb for qb in (8, 16, 32, 64, 128) if qb < c} | {c})
        gs = sorted({g for g in (1, 2, 4, 8, 16) if g <= pages})
        return [{"q_block": qb, "kv_pages_per_block": g}
                for qb in qbs for g in gs]

    def _is_valid(config, shape):
        c = max(int(shape.get("c", 16)), 1)
        pages = max(int(shape.get("pages", 8)), 1)
        return (1 <= config["q_block"] <= c
                and _q_block_accepted(config["q_block"], c)
                and 1 <= config["kv_pages_per_block"] <= pages)

    register_surface(TunableSurface(
        name="ragged_paged_attention",
        params=("q_block", "kv_pages_per_block"),
        default={"q_block": 16, "kv_pages_per_block": 4},
        candidates=_candidates,
        is_valid=_is_valid,
        describe="Ragged paged-attention kernel blocks: stream tokens "
                 "per q program, KV pages per DMA block. Shape key: "
                 "c (chunk) / pages (per seq) / page (size) / d. "
                 "FLAGS_ragged_attn_q_block / _kv_pages set explicitly "
                 "override any cached value."))


_register_ragged_surface()


def ragged_attention_cost(q_shape, pool_shape, avg_ctx, lengths_sum=None,
                          pool_dtype=None):
    """Static FLOPs/bytes for one :func:`ragged_paged_attention` call
    (profiler cost-accounting surface): q [B, C, H, D], pool
    [pages, page, KVH * D]. Attention over an average history of
    ``avg_ctx`` keys per stream token; bytes count q/pages-touched/out
    only (the kernel never materializes scores). ``pool_dtype`` makes
    the page traffic quant-aware: int8 pools stream half the bytes of
    bf16, plus one f32 scale per (token, kv head) from the scales
    pool."""
    from ...profiler.cost import SectionCost
    b, c, h, d = (int(x) for x in q_shape)
    page = int(pool_shape[1])
    toks = int(lengths_sum) if lengths_sum is not None else b * c
    flops = 4.0 * toks * h * d * float(avg_ctx)
    pages_touched = toks * -(-float(avg_ctx) // page)
    io_itemsize = 2  # q/out are bf16 on TPU
    pool_itemsize = (jnp.dtype(pool_dtype).itemsize
                     if pool_dtype is not None else 2)
    bytes_ = ((toks * h * d + toks * h * d) * io_itemsize
              + 2 * pages_touched * page * d * pool_itemsize)
    if pool_dtype is not None and pool_itemsize == 1:
        # quantized pools also stream the page-parallel f32 scales
        bytes_ += 2 * pages_touched * page * 4
    return SectionCost(flops=flops, bytes=bytes_)
