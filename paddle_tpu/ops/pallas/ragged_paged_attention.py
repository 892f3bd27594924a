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

- grid ``(kv-head group, sequence, q_block)`` — one program per
  sequence-block of the token stream for ``hp`` of the sequence's kv
  heads: ALL of them (one group) up to 8 (:func:`_default_kv_heads`), so
  a decode step of 64 slots is 64 programs whatever KVH;
- the wrapper lays q out kv-head-major, ``[B, KVH, C * rep, D]`` with
  ``row = token * rep + head-in-group``, so a q/out block is ``[hp,
  rows, D]`` whose last two dims are the tile the TPU lowering requires
  (``rows`` a multiple of 8, or the whole row-padded chunk) whatever the
  GQA ratio ``rep``;
- block tables / context lens / lengths ride scalar prefetch, so only
  the pages a sequence actually owns are streamed;
- K/V pools stay in HBM (``ANY`` memory space) in the one pool layout,
  ``[num_pages, page_size, KVH * D]`` (``ops.paged_attention``'s module
  docstring). A COPY is one page of the program's kv heads: the
  ``[page_size, hp * D]`` columns ``hg * hp * D ..`` of a page — the
  whole contiguous page when ``hp == KVH`` — into a double-buffered VMEM
  scratch ``[2, g, page_size, hp * D]`` (next block's copies overlap the
  current block's compute). A block is ``g = kv_pages_per_block`` pages,
  of which only those that hold a key the q block can see are copied
  (from the window's first page to the last key's); kv head ``h`` of a
  block is the static lane slice ``h * D .. (h + 1) * D`` of the buffer;
- K, V and q are widened to f32 in VMEM (the MXU runs an f32 product at
  default precision as one bf16 pass, so the products are bf16 x bf16
  summed in f32 either way) and an online softmax per kv head accumulates
  in fp32.

What a call site costs the HOST: Pallas traces the kernel's body to a
jaxpr where the call is traced, and builds its Mosaic module op by op in
Python where the program is lowered — every time a program that holds
it is traced, from the compile cache or not: 0.4-0.9 s a call site
inside a serving process on a TPU host (measured; it grows with the
body, and the heads are unrolled). A serving step traces the forward
twice (the prefill loop's body, the decode scan's) and is itself traced
twice a set-up (the eager discovery turn, the compiled call), so a
stack of L layers walked in Python paid 4 L times: 7.5 of the 15 s of a
K-EXAONE set-up's warm-up turns. So the call is ONE module-level
jitted function, :func:`_ragged_call`, whose static arguments are
everything decided here at trace time (blocks, window, scale, interpret
mode), resolved OUTSIDE it: calls with one signature share one jaxpr in
the process and one lowered function in a program — a stack pays per
KIND of layer, not per layer (``tools/ragged_kernel_bench.py --lower``
prints it; ``tests/test_chip_compile.py`` counts the bodies).

Block sizes: ``q_block`` (default 16 stream tokens) and
``kv_pages_per_block`` are a registered tunable surface
("ragged_paged_attention") swept by ``bench.py --autotune`` / the tuner
CLI; explicit flags win over cached winners (the flash_attention
precedence contract). The DEFAULT pages a block is a function of the
static shape (:func:`_default_kv_pages`: 512 keys for a decode step, so a
context of 160-1,400 is 1-3 trips; 256 for a prefill group's 112-128
rows; a window layer's the window rounded up to 128 keys), as are the kv
heads a program; :func:`_resolve_blocks` is the one function that says
what a shape got.
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
    """Context manager pinning (q_block, kv_pages_per_block) — and, for
    the microbenchmark, the kv heads a program owns — for tuner trials
    (this thread only)."""

    def __init__(self, q_block, kv_pages_per_block, kv_heads=None):
        self._val = (int(q_block), int(kv_pages_per_block),
                     None if kv_heads is None else int(kv_heads))

    def __enter__(self):
        self._prev = getattr(_forced_tls, "blocks", None)
        _forced_tls.blocks = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.blocks = self._prev
        return False


_SUBLANES = 8    # Mosaic: second-to-last block dim % 8, or the whole dim
# the two K/V buffers (2 slots x K, V x pages a block x page x kv heads a
# program x d x itemsize) may take this much of a core's VMEM
_KV_VMEM_BUDGET = 4 << 20


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


def _default_kv_heads(kvh):
    """Kv heads a program owns: all of them (the largest divisor of KVH up
    to 8). A page of every kv head is then ONE contiguous copy, and a
    call has KVH times fewer programs and copy descriptors — what a call
    costs at page 16, where a descriptor of 4 KB takes as long to issue
    as 16-32 KB take to arrive. Measured at 2, 4 and 8 kv heads, at a
    decode step's 8-16 rows and a prefill group's 112-256 alike
    (``tools/ragged_kernel_bench.py``; PERF.md section 6, PR 34)."""
    return max(hp for hp in range(1, min(kvh, 8) + 1) if kvh % hp == 0)


def _default_kv_pages(rows, width, page, pages_per_seq, itemsize, window):
    """Pages a K/V block holds by default, from the static shape: the
    keys that keep the f32 score tile ``[rows, bk]`` at 32 Ki elements,
    a power of two between 128 and 512 — a decode program (8-64 rows)
    walks a context of 160-1,400 in 1-3 trips of 512 keys, a prefill
    group's block of 112-128 rows takes 256, of 256 rows 128 — inside
    the VMEM budget for the two buffers (``width`` = kv heads a program x
    d), and for a window layer no more than the window rounded up to 128
    keys (the window's pages then lie in two blocks at most, and only
    they are copied)."""
    bk = 128
    while bk < 512 and rows * bk * 2 <= 32768:
        bk *= 2
    if window is not None:
        bk = min(bk, -(-window // 128) * 128)
    g = max(bk // page, 1)
    g = min(g, max(_KV_VMEM_BUDGET // (4 * page * width * itemsize), 1))
    return min(g, pages_per_seq)


def _resolve_blocks(c, pages_per_seq, page, d, dtype, quant=False,
                    kv_heads=1, rep=1, window=None, pool_dtype=None,
                    q_block=None, kv_pages_per_block=None):
    """(q_block, kv_pages_per_block, kv heads a program) for this shape —
    THE function of static shapes that selects the compiled grid
    (host-side, at trace time). The first two by precedence: the caller's
    own argument > forced trial candidate > explicit user flag > tuner
    cache > default, and the default pages a block is itself a function
    of the shape (:func:`_default_kv_pages`), as the kv heads a program
    always are (:func:`_default_kv_heads`; a trial may pin them).
    Quantized pools add a ``kvq`` component to the shape sig so bf16
    cache entries can't poison quantized configs (and vice versa); bf16
    shapes keep the historical sig. Clamped to the shape: q blocks never
    exceed the chunk and come back rounded up to one the TPU lowering
    accepts (:func:`_row_blocking`; the tuner surface only offers such
    blocks, a flag may not), page blocks never exceed the table row."""
    from ...framework import flags
    forced = getattr(_forced_tls, "blocks", None)
    g, hp = 0, None                 # nobody chose: from the shape, below
    if forced is not None:
        qb, g, hp = forced
    else:
        qb = int(flags.flag("FLAGS_ragged_attn_q_block"))
        qb_explicit = flags.flag_source(
            "FLAGS_ragged_attn_q_block") != "default"
        g_explicit = flags.flag_source(
            "FLAGS_ragged_attn_kv_pages") != "default"
        if g_explicit:
            g = int(flags.flag("FLAGS_ragged_attn_kv_pages"))
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
    if q_block is not None:
        qb = int(q_block)
    if kv_pages_per_block is not None:
        g = int(kv_pages_per_block)
    qb, _, rows = _row_blocking(c, max(1, min(qb, c)), rep)
    if hp is None or kv_heads % hp:
        hp = _default_kv_heads(kv_heads)
    if g <= 0:
        g = _default_kv_pages(
            rows, hp * d, page, pages_per_seq,
            jnp.dtype(pool_dtype or dtype).itemsize, window)
    return qb, max(1, min(g, pages_per_seq)), hp


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
    """One program: (kv-head group hg, sequence b, q block qi). Streams
    the sequence's pages — every kv head of the group in ONE copy a page —
    through the double-buffered VMEM scratch and accumulates one online
    softmax per kv head over them.

    The q/out block is ``[heads, rows, d]`` with ``row = token * rep +
    head-in-group`` (the wrapper lays the stream out kv-head-major), so
    its last two dims are the tile-aligned ones Mosaic requires and the
    body never reshapes across the sublane/lane boundary.

    ``quant``: the data pools are int8 (or fp8); the per-token scales of
    the sequence's pages arrive lane-dense as ``[n_kv_blocks, bk]`` rows
    per kv head (gathered through the SAME block table by the wrapper)
    and are applied on the key axis of the scores / probabilities —
    ``(q . code_j) * ks_j`` and ``(p_j * vs_j) . code_j`` — which equals
    dequantize-then-dot in exact arithmetic and keeps the softmax in
    fp32."""
    if quant:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    hg = pl.program_id(0)
    b = pl.program_id(1)
    qi = pl.program_id(2)
    heads, rows, d = q_ref.shape   # kv heads of this program, q rows
    bk = g_pages * page            # keys per kv block
    ctx = ctx_ref[b]
    length = len_ref[b]
    q_start = qi * q_block         # first chunk token of this q block
    whole = heads * d == k_hbm_ref.shape[2]     # a copy is a whole page

    # rows past the valid count output zeros (also covers idle slots,
    # length == 0, whose programs skip the whole loop)
    o_ref[...] = jnp.zeros_like(o_ref)

    # a block copies only the pages that hold a key the q block can see,
    # so what a buffer holds beside them is whatever an earlier block
    # left: masked below, which is enough for K (a select) but not for V
    # (0 * NaN): the V buffer starts the call as zeros. Scratch belongs
    # to a core and lives across its grid steps; every core's first
    # program has b == qi == 0.
    @pl.when((b == 0) & (qi == 0))
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)

    def page_copies(src_page, slot, gidx):
        if whole:
            k_src, v_src = k_hbm_ref.at[src_page], v_hbm_ref.at[src_page]
        else:
            cols = pl.ds(hg * (heads * d), heads * d)
            k_src = k_hbm_ref.at[src_page, :, cols]
            v_src = v_hbm_ref.at[src_page, :, cols]
        # each buffer slot owns its OWN semaphore: every page copy has
        # the same byte count, so a shared counter would let block
        # i+1's prefetch completions satisfy a wait for block i and
        # hand compute a partially-copied buffer
        return (pltpu.make_async_copy(k_src, k_buf.at[slot, gidx],
                                      sem.at[slot]),
                pltpu.make_async_copy(v_src, v_buf.at[slot, gidx],
                                      sem.at[slot]))

    @pl.when(q_start < length)
    def compute():  # noqa: ANN001 — pl.when body
        # last key any row of this block may see (+1): the block's last
        # valid token at chunk offset min(q_start + q_block, length) - 1
        n_kv = ctx + jnp.minimum(q_start + q_block, length)
        n_blocks = (n_kv + bk - 1) // bk
        # a window layer's loop starts at the block of the oldest key its
        # first query sees; the blocks before it are never copied
        i0 = first_kv_block(ctx, q_start, window, bk)
        # ... and inside a block, the pages [p_lo, p_hi) alone: from the
        # page of that oldest key to the page of the last key
        p_lo = first_kv_block(ctx, q_start, window, page)
        p_hi = jnp.minimum((n_kv + page - 1) // page, pages_per_seq)

        def block_copies(i, slot, wait=False):
            """Start — or wait for — the copies of block ``i``'s pages
            into buffer ``slot``."""
            def one(p, carry):
                # a wait needs the copy's size, not its source
                pid = 0 if wait else tbl_ref[b * pages_per_seq + p]
                for c in page_copies(pid, slot, p - i * g_pages):
                    c.wait() if wait else c.start()
                return carry
            jax.lax.fori_loop(jnp.maximum(i * g_pages, p_lo),
                              jnp.minimum((i + 1) * g_pages, p_hi), one, 0)

        block_copies(i0, 0 if window is None else jax.lax.rem(i0, 2))

        # row r is chunk token q_start + r // rep. The masks below are
        # the division-free forms of
        #   k_pos <= ctx + q_tok   and   q_tok < length
        # (x <= r // rep  <=>  x * rep <= r for integer x, rep > 0).
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        row_ok = row < (length - q_start) * rep

        def body(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _():
                block_copies(i + 1, jax.lax.rem(i + 1, 2))

            block_copies(i, slot, wait=True)
            # causal over the paged history + the row-validity mask
            # (rows past `length` stay fully masked -> zero output)
            valid = ((col + (i * bk - ctx - q_start)) * rep <= row) & row_ok
            if window is not None:
                # k_pos > q_pos - window, division-free like the above
                # (x > r // rep  <=>  x * rep > r)
                valid = valid & ((col + (i * bk - ctx - q_start + window))
                                 * rep > row)
            out = []
            # the heads are unrolled, their accumulators loop carries: a
            # fori_loop over heads (a 128-aligned dynamic lane slice,
            # accumulators in VMEM scratch) compiles and keeps the body
            # from growing with KVH, but read 1.2-2.7 times the time at
            # every cell's shape (PERF.md section 6, PR 35) — the host
            # cost of the larger body is held by _ragged_call instead
            for h in range(heads):
                acc, m_prev, l_prev = carry[h]
                # kv head h is a static lane slice of the copied pages.
                # Widen BEFORE collapsing (g, page) -> bk: f32 tiles are
                # 8 sublanes, so the collapse is layout-free for any page
                # size that is a multiple of 8 (int8/bf16 tiles are not).
                # The MXU takes an f32 x f32 product at default precision
                # as ONE bf16 pass summed in f32, so bf16 operands would
                # buy nothing: the same time and the same bits at every
                # cell's shape (PERF.md section 6, PR 34)
                k = k_buf[slot, :, :, h * d:(h + 1) * d].astype(
                    jnp.float32).reshape(bk, d)
                v = v_buf[slot, :, :, h * d:(h + 1) * d].astype(
                    jnp.float32).reshape(bk, d)
                s = jax.lax.dot_general(
                    q_ref[h].astype(jnp.float32), k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [rows, bk]
                s = s * (ks_ref[h, pl.ds(i, 1), :] * scale if quant
                         else scale)
                s = jnp.where(valid, s, _NEG_INF)
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                if quant:
                    p = p * vs_ref[h, pl.ds(i, 1), :]
                acc = acc * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out.append((acc, m_new, l_new))
            return tuple(out)

        init = (jnp.zeros((rows, d), jnp.float32),
                jnp.full((rows, 1), _NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32))
        done = jax.lax.fori_loop(i0, n_blocks, body, (init,) * heads)
        for h, (acc, _, l) in enumerate(done):
            o_ref[h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    window = None if window is None else int(window)
    qb, g, hp = _resolve_blocks(
        c, block_tables.shape[1], page, d, q.dtype,
        quant=k_scales is not None, kv_heads=kvh, rep=h // kvh,
        window=window, pool_dtype=key_pages.dtype, q_block=q_block,
        kv_pages_per_block=kv_pages_per_block)
    return _ragged_call(
        q, key_pages, value_pages, block_tables, ctx_lens, lengths,
        k_scales, v_scales,
        scale=float(scale if scale is not None else 1.0 / math.sqrt(d)),
        q_block=qb, kv_pages=g, kv_heads=hp, window=window,
        interpret=bool(_interpret()))


@functools.partial(jax.jit, static_argnames=(
    "scale", "q_block", "kv_pages", "kv_heads", "window", "interpret"))
def _ragged_call(q, key_pages, value_pages, block_tables, ctx_lens,
                 lengths, k_scales, v_scales, *, scale, q_block, kv_pages,
                 kv_heads, window, interpret):
    """The kernel's call with everything :func:`ragged_paged_attention`
    decides at trace time (blocks, window, scale, interpret mode) as
    STATIC arguments of ONE module-level jitted function: what selects
    the program is in its signature, so a forced trial candidate, a flag
    or a test's ``_interpret`` patch can never meet a stale trace — and
    inside a traced program every call with one signature shares ONE
    jaxpr and ONE lowered function. A stack of layers walked in Python
    then lowers the kernel once per KIND of layer, not once per layer,
    and the eager discovery turn and the compiled call of a serving step
    share the kernel's trace (PERF.md section 6, PR 35: what a call site
    costs the host)."""
    b, c, h, d = q.shape
    _, page, width = key_pages.shape
    kvh = width // d
    rep = h // kvh
    pages_per_seq = block_tables.shape[1]
    quant = k_scales is not None
    g, hp = kv_pages, kv_heads
    qb, c_p, rows = _row_blocking(c, q_block, rep)
    n_q = c_p // qb
    # kv-head-major rows: [B, C, KVH, rep, D] -> [B, KVH, C * rep, D]
    qr = jnp.pad(q, ((0, 0), (0, c_p - c), (0, 0), (0, 0)))
    qr = qr.reshape(b, c_p, kvh, rep, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, kvh, c_p * rep, d)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, n_q * rows - c_p * rep),
                      (0, 0)))
    grid = (kvh // hp, b, n_q)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((None, hp, rows, d),
                          lambda hh, bb, qq, *_: (bb, hh, qq, 0))
    in_specs = [q_spec,
                any_spec,       # key pages stay in HBM
                any_spec]       # value pages
    operands = [qr, key_pages, value_pages]
    if quant:
        nb = -(-pages_per_seq // g)
        sc_spec = pl.BlockSpec((None, hp, nb, g * page),
                               lambda hh, bb, qq, *_: (bb, hh, 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [_block_scales(k_scales, block_tables, g, page),
                     _block_scales(v_scales, block_tables, g, page)]
    scratch = [
        pltpu.VMEM((2, g, page, hp * d), key_pages.dtype),
        pltpu.VMEM((2, g, page, hp * d), value_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),              # one per slot
    ]
    with _no_x64():
        out = pl.pallas_call(
            functools.partial(
                _ragged_kernel, scale=scale, page=page, q_block=qb,
                rep=rep, g_pages=g, pages_per_seq=pages_per_seq,
                quant=quant, window=window),
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
            interpret=interpret,
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
# time a block it did not ask for. Pages a block run up to 64 (1,024 keys
# at page 16: two trips over a 2,048-token table); the kv heads a program
# are not a candidate: they follow the block's rows.

def _q_block_accepted(qb, c):
    return qb >= c or qb % _SUBLANES == 0


def _register_ragged_surface():
    from ...tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        c = max(int(shape.get("c", 16)), 1)
        pages = max(int(shape.get("pages", 8)), 1)
        qbs = sorted({qb for qb in (8, 16, 32, 64, 128) if qb < c} | {c})
        gs = sorted({g for g in (1, 2, 4, 8, 16, 32, 64) if g <= pages})
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
        default={"q_block": 16, "kv_pages_per_block": 32},
        candidates=_candidates,
        is_valid=_is_valid,
        describe="Ragged paged-attention kernel blocks: stream tokens "
                 "per q program, KV pages per DMA/compute block (each "
                 "page one copy of the program's kv heads; unset, the "
                 "kernel sizes the block to the shape: 512 keys for a "
                 "decode step, 128-256 for a prefill group). Shape key: "
                 "c (chunk) / pages (per seq) / page (size) / d. "
                 "FLAGS_ragged_attn_q_block / _kv_pages set explicitly "
                 "override any cached value."))


_register_ragged_surface()


def ragged_attention_cost(q_shape, pool_shape, avg_ctx, lengths_sum=None,
                          pool_dtype=None, kv_tokens=None):
    """Static FLOPs/bytes for one :func:`ragged_paged_attention` call
    (profiler cost-accounting surface): q [B, C, H, D], pool
    [pages, page, KVH * D]. Attention of ``lengths_sum`` stream tokens
    (default B * C) over ``avg_ctx`` keys each — the keys a token SEES: a
    window layer's at most ``window``. Bytes are what the algorithm has
    to move and the kernel never more than once a q block: q and out,
    and K and V of ``kv_tokens`` cached tokens in every kv head
    (``KVH * D`` columns a token: the row a page copy brings) — the sum
    over sequences of the span their queries see; the default,
    ``lengths_sum * avg_ctx``, is that sum for a decode step. The kernel
    never materializes scores. ``pool_dtype`` makes the page traffic
    quant-aware: int8 pools stream half the bytes of bf16, plus one f32
    scale per (token, kv head) from the scales pool."""
    from ...profiler.cost import SectionCost
    b, c, h, d = (int(x) for x in q_shape)
    width = int(pool_shape[2])
    toks = int(lengths_sum) if lengths_sum is not None else b * c
    flops = 4.0 * toks * h * d * float(avg_ctx)
    kv_tokens = toks * float(avg_ctx) if kv_tokens is None \
        else float(kv_tokens)
    io_itemsize = 2  # q/out are bf16 on TPU
    pool_itemsize = (jnp.dtype(pool_dtype).itemsize
                     if pool_dtype is not None else 2)
    bytes_ = (2 * toks * h * d * io_itemsize
              + 2 * kv_tokens * width * pool_itemsize)
    if pool_dtype is not None and pool_itemsize == 1:
        # quantized pools also stream the page-parallel f32 scales
        bytes_ += 2 * kv_tokens * (width // d) * 4
    return SectionCost(flops=flops, bytes=bytes_)
