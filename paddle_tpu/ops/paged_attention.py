"""Paged attention for serving-time autoregressive decode.

Role of the reference inference engine's paged/ragged KV-cache attention
(Paddle Inference fused attention ops + PaddleNLP serving kernels,
UNVERIFIED — reference mount empty). The KV cache is stored as fixed-size
*pages* in a global pool; each sequence owns a list of pages (its block
table), so cache memory is allocated per-page instead of per-max-length —
the vLLM/TPU-serving design (see PAPERS.md ragged-paged-attention).

TPU-native: the fast path is the ragged paged-attention Pallas kernel
(``ops/pallas/ragged_paged_attention.py``, a scalar-prefetch kernel that
streams only the pages named in the block table through VMEM). The
reference path below is pure jnp (gather + masked softmax) — the numeric
oracle and the CPU/debug fallback.

THE pool layout — one, for every function here, the kernel and the
serving engine (:func:`kv_pool_shape`):

  key_pages    [num_pages, page_size, KVH * D]
  value_pages  [num_pages, page_size, KVH * D]
  k/v scales   [num_pages, KVH, page_size] f32 (quantized pools only,
               :func:`kv_scales_shape`: one scale per token and head)

Page axis first, a token's heads side by side in the minor dimension
(head ``h`` is columns ``h * D .. (h + 1) * D``). A token's k/v is ONE
row, so the write is a row scatter at ``(page, offset)`` with no
transpose, and a page's ``[page_size, D]`` tile of one kv head is a
lane-aligned slice the kernel DMAs as it lies: XLA's scatter and the
Mosaic call agree on the array's default tiling, and a compiled step
holds no pool-sized re-layout (``tests/test_chip_compile.py`` counts).

Other operands (decode step, one query token per sequence):
  q            [B, H, D]
  block_tables [B, pages_per_seq] int32 — page ids, row-padded with any
               valid id past the sequence's last page
  context_lens [B] int32 — tokens currently in cache per sequence
GQA/MQA: H a multiple of KVH; q head h attends kv head h // (H // KVH).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["kv_pool_shape", "kv_scales_shape",
           "paged_attention", "paged_attention_reference",
           "paged_prefill_attention", "paged_prefill_attention_reference",
           "ragged_paged_attention", "ragged_paged_attention_reference",
           "paged_prefill_write",
           "paged_verify_write", "kv_quant_range", "quantize_kv",
           "dequantize_pages", "paged_prefill_write_quant",
           "paged_verify_write_quant"]

_NEG_INF = -1e30


def kv_pool_shape(kv_heads, num_pages, page_size, head_dim):
    """The shape of one paged K or V pool (module docstring)."""
    return (int(num_pages), int(page_size), int(kv_heads) * int(head_dim))


def kv_scales_shape(kv_heads, num_pages, page_size):
    """The shape of a quantized pool's f32 scales pool: page axis first
    like the data, a page's offsets in the minor dimension — the order
    the kernel's lane-dense ``[.., kv block keys]`` scale rows are
    gathered in, so the scales too are written and read in one layout."""
    return (int(num_pages), int(kv_heads), int(page_size))


def _gather_heads(pages, table, kvh):
    """One sequence's pages, head-major: pool [P, page, KVH * D] x table
    [pages_per_seq] -> [KVH, pages_per_seq * page, D]."""
    _, page_size, width = pages.shape
    x = pages[table].reshape(table.shape[0] * page_size, kvh, width // kvh)
    return jnp.swapaxes(x, 0, 1)


def kv_quant_range(dtype):
    """Symmetric quantization range for a quantized-KV pool dtype: the
    largest magnitude a quantized code can carry, so ``scale = absmax /
    range``. The quant MODE is inferred from the pool dtype everywhere
    (no extra traced operand through the compiled batching step)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.int8:
        return 127.0       # symmetric, the reference skips -128
    if "float8_e4m3" in dtype.name:
        return 448.0       # e4m3 finite max
    raise ValueError(f"not a quantized KV pool dtype: {dtype}")


def quantize_kv(x, dtype):
    """Per-vector absmax quantization of k/v projections: x [..., D]
    float -> (q [..., D] ``dtype``, scales [...] float32) with
    ``dequant = q.astype(f32) * scale``. One scale per (token, kv head)
    — written WITH the token, so decode appends into a partially filled
    page never requantize earlier tokens (write-once discipline)."""
    r = kv_quant_range(dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.where(amax > 0, amax, 1.0) / r
    y = xf / scales[..., None]
    if jnp.dtype(dtype) == jnp.int8:
        q = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        q = y.astype(dtype)
    return q, scales


def dequantize_pages(pages, scales):
    """Quantized pool -> f32: pages [P, page, KVH * D] x scales
    [P, KVH, page] (the page-parallel scales pool) -> f32 pages."""
    p, kvh, page_size = scales.shape
    x = pages.astype(jnp.float32).reshape(p, page_size, kvh, -1)
    sc = jnp.swapaxes(scales.astype(jnp.float32), 1, 2)[..., None]
    return (x * sc).reshape(pages.shape)


def paged_attention_reference(q, key_pages, value_pages, block_tables,
                              context_lens, scale=None):
    """Pure-jnp oracle: gather each sequence's pages, mask, soft-max."""
    b, h, d = q.shape
    _, page_size, width = key_pages.shape
    kvh = width // d
    rep = h // kvh
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    max_len = block_tables.shape[1] * page_size

    def one_seq(qi, table, ctx_len):
        k = _gather_heads(key_pages, table, kvh)    # [KVH, max_len, D]
        v = _gather_heads(value_pages, table, kvh)
        k = jnp.repeat(k, rep, axis=0)  # [H, max_len, D]
        v = jnp.repeat(v, rep, axis=0)
        logits = jnp.einsum("hd,hkd->hk", qi, k,
                            preferred_element_type=jnp.float32) * s
        mask = jnp.arange(max_len) < ctx_len
        logits = jnp.where(mask[None, :], logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("hk,hkd->hd", probs, v)

    return jax.vmap(one_seq)(q, block_tables, context_lens)


def paged_attention(q, key_pages, value_pages, block_tables, context_lens,
                    scale=None):
    """Decode-step paged attention: one query token per sequence over
    ``context_lens`` cached tokens (the new token's k/v included). It is
    :func:`ragged_paged_attention` at ``lengths == 1`` — the one kernel
    on TPU, the jnp oracle elsewhere — and equals
    :func:`paged_attention_reference`."""
    ctx = context_lens.astype(jnp.int32) - 1
    out = ragged_paged_attention(q[:, None], key_pages, value_pages,
                                 block_tables, ctx, jnp.ones_like(ctx),
                                 scale)
    return out[:, 0]


def paged_prefill_attention_reference(q, key_pages, value_pages,
                                      block_tables, context_lens,
                                      scale=None, k_scales=None,
                                      v_scales=None, window=None):
    """Pure-jnp oracle for CHUNKED prefill over the page pool.

    q: [B, C, H, D] — C query tokens per sequence whose k/v have already
    been written into the pages at positions ``ctx .. ctx+C-1`` (see
    :func:`paged_prefill_write`). ``context_lens`` [B] is the cache
    length BEFORE the chunk; query token j attends every cache position
    ``<= ctx + j`` — full paged history behind it, causal within the
    chunk. With C == 1 this reduces exactly to the decode oracle called
    as ``paged_attention(q[:, 0], ..., ctx + 1)``.

    Per-query masking is over the SAME gathered [max_len] axis the
    decode oracle uses, so chunked and whole-prompt prefill reduce in
    the same order — the basis of the token-parity guarantee.

    ``k_scales``/``v_scales`` [num_pages, KVH, page_size] f32 mark the
    pools as quantized (int8/fp8): pages are dequantized to f32 right
    after the gather — the same block-table indirection, so trash-page
    routing and page sharing compose unchanged — and the output is cast
    back to q's dtype.

    ``window`` (static int or None): query token j sees the ``window``
    newest positions ``<= ctx + j`` only — its own among them.
    """
    b, c, h, d = q.shape
    _, page_size, width = key_pages.shape
    kvh = width // d
    quantized = k_scales is not None
    if quantized:
        key_pages = dequantize_pages(key_pages, k_scales)
        value_pages = dequantize_pages(value_pages, v_scales)
    rep = h // kvh
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    max_len = block_tables.shape[1] * page_size

    def one_seq(qi, table, ctx_len):
        k = _gather_heads(key_pages, table, kvh)    # [KVH, max_len, D]
        v = _gather_heads(value_pages, table, kvh)
        k = jnp.repeat(k, rep, axis=0)  # [H, max_len, D]
        v = jnp.repeat(v, rep, axis=0)
        logits = jnp.einsum("chd,hkd->chk", qi, k,
                            preferred_element_type=jnp.float32) * s
        q_pos = (ctx_len + jnp.arange(c))[:, None]
        k_pos = jnp.arange(max_len)[None, :]
        allow = k_pos <= q_pos                            # [C, max_len]
        if window is not None:
            allow = allow & (k_pos > q_pos - window)
        logits = jnp.where(allow[:, None, :], logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("chk,hkd->chd", probs, v)

    out = jax.vmap(one_seq)(q, block_tables, context_lens)
    return out.astype(q.dtype) if quantized else out


def paged_prefill_attention(q, key_pages, value_pages, block_tables,
                            context_lens, scale=None):
    """Multi-token-query paged attention (chunked prefill) — every
    chunk token treated as valid. Kept as the whole-chunk entry point;
    the serving hot path goes through :func:`ragged_paged_attention`,
    which adds per-sequence valid counts (mixed prefill+decode+idle
    slots in one call) and the Pallas kernel dispatch."""
    b, c = q.shape[0], q.shape[1]
    lengths = jnp.full((b,), c, jnp.int32)
    return ragged_paged_attention(q, key_pages, value_pages,
                                  block_tables, context_lens, lengths,
                                  scale)


def ragged_paged_attention_reference(q, key_pages, value_pages,
                                     block_tables, ctx_lens, lengths,
                                     scale=None, k_scales=None,
                                     v_scales=None, window=None):
    """Pure-jnp oracle for the RAGGED mixed prefill+decode batching
    step: q [B, C, H, D] is the uniform-stride view of the flattened
    token stream (slot b's tokens are the ``[start=b*C, length=
    lengths[b]]`` window), ``ctx_lens`` the cache length BEFORE the
    chunk, ``lengths`` the per-slot valid token count — 0 (idle slot),
    1 (decode step) or >1 (prefill chunk) all flow through the same
    reduction. Rows past the valid count are zeroed.

    Reduces over the SAME gathered [max_len] axis as the prefill and
    decode oracles (it *is* the prefill oracle plus the validity mask),
    so with lengths == C it equals
    :func:`paged_prefill_attention_reference` exactly and with
    lengths == 1 it reduces exactly to the decode oracle at ctx+1 —
    the basis of the kernel parity tests."""
    c = q.shape[1]
    out = paged_prefill_attention_reference(
        q, key_pages, value_pages, block_tables, ctx_lens, scale,
        k_scales=k_scales, v_scales=v_scales, window=window)
    valid = jnp.arange(c)[None, :] < lengths[:, None]      # [B, C]
    return jnp.where(valid[:, :, None, None], out, 0).astype(out.dtype)


def ragged_paged_attention(q, key_pages, value_pages, block_tables,
                           ctx_lens, lengths, scale=None,
                           k_scales=None, v_scales=None, window=None):
    """Mixed prefill+decode paged attention — the serving engine's ONE
    attention entry point (PAPERS.md ragged-paged-attention). Pallas
    kernel on TPU (``FLAGS_use_pallas_ragged_attention``), jnp oracle
    elsewhere; the kernel module itself always runs (interpret mode)
    in the parity tests, the flash_attention discipline. The path is a
    rule on platform + flag: a kernel that fails on TPU raises, it is
    never swapped for the oracle. ``window`` (static): a layer whose
    queries see their ``window`` newest keys only; the kernel then
    starts at the window's first block."""
    from ..framework import flags
    platform = jax.devices()[0].platform
    use_kernel = (platform == "tpu"
                  and bool(int(flags.flag(
                      "FLAGS_use_pallas_ragged_attention"))))
    if use_kernel:
        from .pallas.ragged_paged_attention import (
            ragged_paged_attention as _kernel)
        return _kernel(q, key_pages, value_pages, block_tables,
                       ctx_lens, lengths, scale,
                       k_scales=k_scales, v_scales=v_scales, window=window)
    return ragged_paged_attention_reference(
        q, key_pages, value_pages, block_tables, ctx_lens, lengths,
        scale, k_scales=k_scales, v_scales=v_scales, window=window)


def _chunk_rows(pool, block_tables, ctx, valid, c):
    """(page id, offset) [B, C] of a chunk's tokens: token j of sequence
    b lands at global position ``ctx[b] + j`` of its block-table row;
    tokens with ``j >= valid[b]`` go to the reserved trash page 0."""
    page = pool.shape[1]
    pos = ctx[:, None] + jnp.arange(c, dtype=ctx.dtype)[None, :]  # [B, C]
    # padded positions can run past the table row — clamp the page index
    # (the write is trash-routed anyway) so the gather stays in bounds
    pidx = jnp.minimum(pos // page, block_tables.shape[1] - 1)
    pid = jnp.take_along_axis(block_tables, pidx, axis=1)         # [B, C]
    ok = jnp.arange(c)[None, :] < valid[:, None]
    return jnp.where(ok, pid, 0), pos % page


def paged_prefill_write(kp, vp, k, v, block_tables, ctx, valid):
    """Write one prefill chunk's k/v into the page pools.

    k, v: [B, C, KVH, D] (the chunk's projections, already rotated).
    Token j of sequence b lands at global position ``ctx[b] + j`` in its
    block-table row, as one row of ``KVH * D``; tokens with
    ``j >= valid[b]`` (chunk padding, or a slot not in this prefill
    wave) are routed to the reserved trash page 0 so a real page is
    never clobbered. A decode step is the chunk of one token."""
    b, c = k.shape[:2]
    pid, off = _chunk_rows(kp, block_tables, ctx, valid, c)
    kp = kp.at[pid, off].set(k.reshape(b, c, -1))
    vp = vp.at[pid, off].set(v.reshape(b, c, -1))
    return kp, vp


def paged_verify_write(kp, vp, k, v, block_tables, ctx, valid):
    """Multi-token speculative VERIFY write (ISSUE 18): write a
    ``1 + K``-token verification chunk's k/v — the pending token plus
    ``K`` draft tokens — into positions ``ctx .. ctx + K`` of each
    slot's block-table row, BEFORE knowing how many drafts the target
    will accept.

    Rollback-safe page commit, by construction rather than by an undo
    log:

    - **Reads are fenced by ctx.** Every attention entry point masks
      cache reads to positions ``<= ctx + j`` for query token ``j``,
      and the engine only ever advances its committed ``ctx`` mirror by
      the ACCEPTED length. KV written past the accepted position is
      therefore unreachable — no future query can attend it.
    - **Writes overwrite in place.** The next chunk for the slot starts
      at the committed ``ctx`` and re-writes those same page offsets,
      so rejected-draft garbage has the lifetime of one scheduler turn.
    - **Sharing is prompt-only.** The prefix cache publishes full pages
      of PROMPT tokens at prefill completion; decode/verify positions
      live past ``len(prompt)`` in COW-private pages, so a rejected
      draft can never leak into a page another sequence attaches.

    Accepting tokens is thus a pure bookkeeping commit (advance ctx);
    rejecting is a no-op. The write routing itself is identical to a
    short prefill chunk — token ``j >= valid`` is trash-routed to page
    0 and out-of-row positions are clamped — because a verification
    chunk IS a short prefill chunk to the page pool."""
    return paged_prefill_write(kp, vp, k, v, block_tables, ctx, valid)


def paged_prefill_write_quant(kp, vp, ks, vs, k, v, block_tables, ctx,
                              valid):
    """Quantize-at-write prefill chunk write for quantized KV pools.

    kp, vp: [num_pages, page_size, KVH * D] int8 (or fp8) data pools;
    ks, vs: [num_pages, KVH, page_size] f32 page-parallel scales pools.
    k, v: [B, C, KVH, D] float projections (already rotated). The quant
    mode rides the pool dtype (:func:`kv_quant_range`) and each token's
    per-kv-head scales are written at the SAME (page, offset) its data
    lands at, so the scales ride the block-table indirection unchanged:
    trash-routed padding writes its scale to trash page 0, COW forks
    copy the scale page with the data page, and preemption replay
    rewrites both."""
    b, c = k.shape[:2]
    qk, sk = quantize_kv(k, kp.dtype)       # [B, C, KVH, D] / [B, C, KVH]
    qv, sv = quantize_kv(v, vp.dtype)
    pid, off = _chunk_rows(kp, block_tables, ctx, valid, c)
    kp = kp.at[pid, off].set(qk.reshape(b, c, -1))
    vp = vp.at[pid, off].set(qv.reshape(b, c, -1))
    ks = ks.at[pid, :, off].set(sk.astype(ks.dtype))
    vs = vs.at[pid, :, off].set(sv.astype(vs.dtype))
    return kp, vp, ks, vs


def paged_verify_write_quant(kp, vp, ks, vs, k, v, block_tables, ctx,
                             valid):
    """Speculative verify write into quantized pools — the same
    rollback-safety argument as :func:`paged_verify_write` (reads are
    fenced by ctx, writes overwrite in place, sharing is prompt-only)
    holds per-token for the scales too, since a scale is only ever read
    together with the data it was written with."""
    return paged_prefill_write_quant(kp, vp, ks, vs, k, v, block_tables,
                                     ctx, valid)
