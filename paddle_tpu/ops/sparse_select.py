"""Learned block-sparse attention over paged K/V (InfLLM-v2's rule, as
MiniCPM4 serves it) — the selection in plain ``jax.numpy`` and the jnp
oracle of the attention; on a TPU the attention is the Pallas kernel
``ops.pallas.sparse_paged_attention``.

The rule, for a query at position ``p`` and KV group ``j`` (sizes from the
model's config; MiniCPM4's in brackets):

- compressed keys ``c_i = mean(k_{s i} .. k_{s i + K - 1})`` (kernel ``K``
  [32], stride ``s`` [16]) for every ``i`` with ``s i + K - 1 <= p``;
- ``score_i = sum over the group's query heads h of softmax_i(q_h . c_i /
  sqrt(d))``, in float32;
- block score ``b_m = max score_i`` over the entries whose ``K`` keys overlap
  block ``m`` (keys ``bl m .. bl m + bl - 1``, ``bl`` [64] = the page size);
- the first ``init`` [1] blocks and every block holding one of the last
  ``window`` [2048] keys ``p - window + 1 .. p`` score ``+inf``, blocks past
  ``p`` ``-inf``;
- the ``topk`` [64] highest blocks (``lax.top_k``: ties to the lower ``m``),
  in ascending order — so the last one is the block that holds ``p``;
- causal softmax over the keys of those blocks only.

A query with ``p + 1 <= dense_len`` [8192] attends every key ``<= p``
instead, whatever the selection says. The switch is per query POSITION, so
a prompt gives the same result whether it arrives whole or in chunks.

The compressed keys live in a per-slot cache (``inference.cache_spec.
CompressedKeys``) that :func:`compressed_update` fills as keys arrive: an
entry is written once, in the pass that writes its last key, from the keys
in the page pool — so an entry whose keys straddle a chunk boundary is
completed by the later chunk. Entry ``i`` is read only by queries at ``p >=
s i + K - 1``, i.e. after the request itself wrote it: a reused slot needs
no reset.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ..profiler import metrics as _pmetrics

__all__ = ["SparseRule", "compressed_update", "select_blocks",
           "sparse_paged_attention", "sparse_paged_attention_reference",
           "query_counts", "SPARSE_COUNTERS"]

_HI = lax.Precision.HIGHEST
_NEG = -1e30

#: what a sparse layer counts per pass (``StepCounters`` names)
SPARSE_COUNTERS = ("sparse_queries", "dense_queries",
                   "sparse_blocks_selected", "sparse_blocks_loaded")
_pmetrics.declare("serving/sparse_queries", "counter",
                  "query x KV-group passes of a block-sparse attention "
                  "layer that took the sparse path (p + 1 > dense_len)")
_pmetrics.declare("serving/dense_queries", "counter",
                  "query x KV-group passes of a block-sparse attention "
                  "layer that attended every key (p + 1 <= dense_len)")
_pmetrics.declare("serving/sparse_blocks_selected", "counter",
                  "blocks the selection rule chose for the sparse queries "
                  "(top-k per query and KV group)")
_pmetrics.declare("serving/sparse_blocks_loaded", "counter",
                  "pages the sparse attention kernel copied for the sparse "
                  "queries, counted as it starts each copy (off the TPU: "
                  "the pages the jnp oracle gathers)")


@dataclass(frozen=True)
class SparseRule:
    """The static sizes of the rule (hashable: a jit's static argument);
    ``tile`` is how many queries the selection scores at a time."""
    kernel: int
    stride: int
    block: int
    topk: int
    init: int
    window: int
    dense_len: int
    tile: int = 256

    def __post_init__(self):
        if self.block % self.stride:
            raise ValueError("the block size must be a multiple of the "
                             "compression stride")
        if self.dense_len < self.topk * self.block:
            raise ValueError("dense_len must reach topk blocks: a sparse "
                             "query always has topk blocks to choose")
        if self.topk < self.init + -(-(self.window - 1) // self.block) + 1:
            raise ValueError("topk must hold the init blocks and every "
                             "block the window touches, and choose more")


def compressed_update(ckeys, k_pool, tables, ctx, valid, rule, n_new):
    """Write the compressed entries a pass completes. ``ckeys`` [B, NC, W]
    (W = kv heads x d); ``k_pool`` [P, page, W] AFTER the pass's keys were
    written; ``tables`` [B, MP]; ``ctx`` [B] keys before the pass; ``valid``
    [B] keys the pass wrote; ``n_new`` (static) the most entries one pass can
    complete (``ceil(S / stride)``). Returns the new ``ckeys``."""
    B, NC, W = ckeys.shape
    page, MP = k_pool.shape[1], tables.shape[1]
    K, st = rule.kernel, rule.stride
    ctx = ctx.astype(jnp.int32)
    # the first entry whose last key (s i + K - 1) is at or after ctx
    first = jnp.maximum(-((K - 1 - ctx) // st), 0)
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None]
    last = idx * st + K - 1
    new = (last < (ctx + valid.astype(jnp.int32))[:, None]) & (idx < NC)
    kpos = idx[..., None] * st + jnp.arange(K, dtype=jnp.int32)  # [B, E, K]
    pidx = jnp.minimum(kpos // page, MP - 1).reshape(B, -1)
    pages = jnp.take_along_axis(tables, pidx, axis=1).reshape(kpos.shape)
    rows = k_pool[pages, kpos % page]                     # [B, E, K, W]
    c = jnp.mean(rows.astype(jnp.float32), axis=2).astype(ckeys.dtype)
    at = jnp.where(new, idx, NC)                          # NC: dropped
    return ckeys.at[jnp.arange(B)[:, None], at].set(c, mode="drop")


def _select_tile(q, ck, pos, rule, n_blocks):
    """q [B, T, G, R, D] f32 (normed, unscaled); ck [B, NC, G, D] f32; pos
    [B, T] -> blocks [B, T, G, topk] int32, ascending."""
    d = q.shape[-1]
    NC = ck.shape[1]
    K, st, bl = rule.kernel, rule.stride, rule.block
    logits = jnp.einsum("btgrd,bngd->btgrn", q, ck, precision=_HI) \
        / math.sqrt(d)
    ok = (jnp.arange(NC, dtype=jnp.int32) * st + K - 1)[None, None] \
        <= pos[..., None]                                  # [B, T, NC]
    ok = ok[:, :, None, None]
    m = jnp.max(jnp.where(ok, logits, -jnp.inf), axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(ok, jnp.exp(logits - m), 0.0)
    prob = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    score = jnp.sum(prob, axis=3)                          # [B, T, G, NC]
    # entries overlapping block m: i = m r + o, o in [o_lo, o_hi]
    r = bl // st
    o_lo, o_hi = -((K - 1) // st), (bl - 1) // st
    need = (n_blocks - 1) * r + (o_hi - o_lo) + 1
    sp = jnp.pad(score, [(0, 0)] * 3 + [(-o_lo, max(need - NC + o_lo, 0))])
    b = None
    for o in range(o_hi - o_lo + 1):
        part = lax.slice_in_dim(sp, o, o + (n_blocks - 1) * r + 1, r,
                                axis=3)
        b = part if b is None else jnp.maximum(b, part)
    mi = jnp.arange(n_blocks, dtype=jnp.int32)[None, None, None]
    p = pos[:, :, None, None]
    forced = (mi < rule.init) | ((mi + 1) * bl > p - rule.window + 1)
    b = jnp.where(mi * bl > p, -jnp.inf, jnp.where(forced, jnp.inf, b))
    _, top = lax.top_k(b, rule.topk)
    return jnp.sort(top, axis=-1).astype(jnp.int32)


def select_blocks(q, ckeys, ctx, rule, n_blocks):
    """The selection for every query of a pass. q [B, S, H, D] (after the
    per-head norm, before any scale); ``ckeys`` [B, NC, G * D]; ``ctx`` [B];
    ``n_blocks`` (static) blocks of a slot's table. Returns [B, S, G, topk]
    int32 block indices, ascending (meaningful where ``p + 1 >
    dense_len``). Queries are scored ``rule.tile`` at a time, so the
    ``[tile, H, NC]`` float32 scores are the largest array."""
    B, S, H, D = q.shape
    G = ckeys.shape[-1] // D
    ck = ckeys.astype(jnp.float32).reshape(B, -1, G, D)
    qf = q.astype(jnp.float32).reshape(B, S, G, H // G, D)
    pos = ctx.astype(jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)
    T = min(rule.tile, S)
    if S <= T:
        return _select_tile(qf, ck, pos, rule, n_blocks)
    pad = -S % T
    qf = jnp.pad(qf, [(0, 0), (0, pad)] + [(0, 0)] * 3)
    pos = jnp.pad(pos, [(0, 0), (0, pad)])
    n = (S + pad) // T
    out = lax.map(
        lambda a: _select_tile(a[0], ck, a[1], rule, n_blocks),
        (jnp.moveaxis(qf.reshape(B, n, T, G, H // G, D), 1, 0),
         jnp.moveaxis(pos.reshape(B, n, T), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * T, G, rule.topk)[:, :S]


def query_counts(ctx, lengths, S, G, rule):
    """(sparse, dense) query x KV-group passes of one pass, int32."""
    j = jnp.arange(S, dtype=jnp.int32)[None]
    live = j < lengths.astype(jnp.int32)[:, None]
    sparse = live & (ctx.astype(jnp.int32)[:, None] + j + 1 > rule.dense_len)
    n_sp = jnp.sum(sparse).astype(jnp.int32) * G
    return n_sp, jnp.sum(live).astype(jnp.int32) * G - n_sp


def sparse_paged_attention_reference(q, key_pages, value_pages, tables, ctx,
                                     lengths, blocks, rule, scale=None):
    """The jnp oracle. q [B, S, H, D]; pools [P, page, G * D] (page ==
    ``rule.block``); ``tables`` [B, MP]; ``ctx`` [B] keys before the pass
    (the pass's own already written); ``lengths`` [B] valid queries;
    ``blocks`` [B, S, G, topk]. Query ``j`` at ``p = ctx + j`` attends keys
    ``<= p`` of every block if ``p + 1 <= dense_len``, else of its
    ``blocks`` only. Rows ``j >= lengths`` are zero. Returns [B, S, H, D]."""
    B, S, H, D = q.shape
    _, page, W = key_pages.shape
    G = W // D
    MP = tables.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    k = key_pages[tables].reshape(B, MP * page, G, D)
    v = value_pages[tables].reshape(B, MP * page, G, D)
    pos = ctx.astype(jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)
    chosen = jnp.zeros((B, S, G, MP), bool).at[
        jnp.arange(B)[:, None, None, None], jnp.arange(S)[None, :, None,
                                                          None],
        jnp.arange(G)[None, None, :, None], blocks].set(True)
    dense = (pos + 1 <= rule.dense_len)[:, :, None, None]
    chosen = jnp.repeat(chosen | dense, page, axis=-1)     # [B, S, G, keys]
    kpos = jnp.arange(MP * page, dtype=jnp.int32)
    allow = chosen & (kpos[None, None, None] <= pos[:, :, None, None])
    qg = q.reshape(B, S, G, H // G, D)
    lg = jnp.einsum("bsgrd,bkgd->bsgrk", qg.astype(jnp.float32),
                    k.astype(jnp.float32), precision=_HI) * s
    lg = jnp.where(allow[:, :, :, None], lg, _NEG)
    pr = jax.nn.softmax(lg, axis=-1)
    o = jnp.einsum("bsgrk,bkgd->bsgrd", pr, v.astype(jnp.float32),
                   precision=_HI).reshape(B, S, H, D)
    live = jnp.arange(S)[None] < lengths[:, None]
    return jnp.where(live[..., None, None], o, 0.0).astype(q.dtype)


def sparse_paged_attention(q, key_pages, value_pages, tables, ctx, lengths,
                           blocks, rule, scale=None):
    """Block-sparse paged attention (shapes as
    :func:`sparse_paged_attention_reference`). Returns (out [B, S, H, D],
    pages read for the sparse queries). On a TPU the Pallas kernel, which
    copies, per query and KV group, exactly the pages it attends and counts
    the copies it starts; elsewhere the oracle, which gathers every page of
    a slot's table for each query (``MP`` per sparse query and group). The
    path is a rule on the platform: a kernel that fails on a TPU raises."""
    if jax.devices()[0].platform == "tpu":
        from .pallas.sparse_paged_attention import (
            sparse_paged_attention as _kernel)
        return _kernel(q, key_pages, value_pages, tables, ctx, lengths,
                       blocks, rule, scale)
    G = key_pages.shape[-1] // q.shape[-1]
    n_sp, _ = query_counts(ctx, lengths, q.shape[1], G, rule)
    out = sparse_paged_attention_reference(q, key_pages, value_pages, tables,
                                           ctx, lengths, blocks, rule, scale)
    return out, n_sp * tables.shape[1]
