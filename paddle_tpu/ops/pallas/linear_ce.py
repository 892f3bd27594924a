"""Fused LM head + cross-entropy on the TPU: the logits live in VMEM only.

The loss tail of a language model, ``ce(h @ W, labels)`` over ``V``
vocabulary columns, costs ``2 D`` FLOPs a logit: 1,536 at ``D`` = 768,
against 4 bytes to write an f32 logit to HBM and 4 to read it back. That
is below the v5e's ridge, so an ``[N, V]`` logits array that reaches HBM
costs as much as the matmul that made it, each time an op reads it. These
kernels compute every ``[tm, tv]`` logits tile on the MXU (operands in
``h``'s dtype, f32 accumulation) and consume it where it was made:

- :func:`lse_and_target` (forward): grid (row tiles, vocab tiles), the
  vocabulary innermost; an online max and sum-exp and the target logit
  of every row ride VMEM scratch across the vocab tiles; the kernel
  writes ``lse`` and the target logit, f32 ``[N]``, and nothing else.
- :func:`head_grads` (backward): one kernel recomputes each tile, forms
  ``dlogits = (softmax - onehot) * scale`` in VMEM and writes it ONCE,
  in ``h``'s dtype; XLA's two matmuls make ``dh`` and ``dW`` from it.
  (Two kernels that accumulated ``dh`` and ``dW`` in VMEM instead, each
  recomputing its tiles, took 8.8-10.1 ms a step against 7.2-8.6 at
  the same tiles, timed alone on a v5e.)

The weight is read as stored, in its own dtype, each tile cast in VMEM:
``[D, V]`` (an untied head) or ``[V, D]`` (a tied embedding,
``vocab_first``), never transposed or cast whole in HBM; ``dW`` comes
back in that layout and dtype. The last vocab tile runs past ``V`` (the
weight is never padded): its columns are masked in the kernel. Rows are
padded to the row tile (``h`` is small); a padded row's label is -1 and
its scale 0.

Tiles come from ONE shape rule, :func:`_resolve_tiles`; each kernel's
call is ONE module-level jitted function whose static arguments are the
tiles and the layout, so a program lowers it once a signature.
``tools/ce_tail_bench.py`` times the tail alone on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["lse_and_target", "head_grads"]

#: rows of ``h`` a program holds (the MXU's M) and vocabulary columns a
#: step computes. A weight tile is read once per row tile, so the row
#: tile sets the weight's re-reads: at 1024 rows x 1024 columns of D 768
#: a step is 1.6 GFLOP against 3 MB of f32 weight, compute-bound. Timed
#: alone on a v5e at 4096 x 768 x 50257 (tools/ce_tail_bench.py), fwd +
#: bwd: 7.19 ms at 1024 x 1024 against 7.48-8.60 ms at 2048 x 512,
#: 1024 x 512, 2048 x 256, 1024 x 256 and 512 x 512.
_ROW_TILE = 1024
_VOCAB_TILE = 1024
#: the forward's logits tile, its exponentials and the double-buffered
#: operands want more than the default 16 MiB of scoped VMEM at these
#: tiles; a v5e core has 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def _round_up(n, m):
    return -(-n // m) * m


def _resolve_tiles(n, v):
    """``(tm, tv)`` for ``n`` rows over ``v`` columns: the one rule both
    kernels share. A row tile is a multiple of 16 (a bf16 sublane tile),
    a vocab tile of 128 (a lane tile)."""
    return (min(_ROW_TILE, _round_up(n, 16)),
            min(_VOCAB_TILE, _round_up(v, 128)))


def _logits(h, w, vocab_first):
    """One ``[tm, tv]`` tile, f32: ``h [tm, D] @ w``, w ``[tv, D]``
    (vocab_first) or ``[D, tv]``, cast to h's dtype in VMEM."""
    w = w.astype(h.dtype)
    contract = ((1,), (1,)) if vocab_first else ((1,), (0,))
    return lax.dot_general(h, w, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _columns(shape, j, tv, v):
    """Each element's vocabulary column in vocab tile ``j``, and whether
    it is < V."""
    col = j * tv + lax.broadcasted_iota(jnp.int32, shape, 1)
    return col, col < v


def _fwd_kernel(lab_ref, h_ref, w_ref, lse_ref, tgt_ref, m_sc, s_sc, t_sc,
                *, tv, v, vocab_first):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        s_sc[...] = jnp.zeros(s_sc.shape, jnp.float32)
        t_sc[...] = jnp.zeros(t_sc.shape, jnp.float32)

    x = _logits(h_ref[...], w_ref[...], vocab_first)
    col, valid = _columns(x.shape, j, tv, v)
    # where, not a product: a column past V may hold a NaN
    x = jnp.where(valid, x, -jnp.inf)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    s_sc[...] = (s_sc[...] * jnp.exp(m_prev - m_new)
                 + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    m_sc[...] = m_new
    # an ignored row's label is -1 and matches no column
    t_sc[...] += jnp.sum(jnp.where(col == lab_ref[...], x, 0.0),
                         axis=-1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse_ref[...] = m_sc[...] + jnp.log(s_sc[...])
        tgt_ref[...] = t_sc[...]


def _dl_tile(lab_ref, lse_ref, scale_ref, h, w, j, tv, v, vocab_first):
    """``(softmax - onehot) * scale`` of vocab tile ``j``, in h's dtype."""
    x = _logits(h, w, vocab_first)
    col, valid = _columns(x.shape, j, tv, v)
    p = jnp.where(valid, jnp.exp(x - lse_ref[...]), 0.0)
    hit = (col == lab_ref[...]).astype(jnp.float32)
    return ((p - hit) * scale_ref[...]).astype(h.dtype)


def _dlogits_kernel(lab_ref, lse_ref, scale_ref, h_ref, w_ref, dl_ref, *,
                    tv, v, vocab_first):
    dl_ref[...] = _dl_tile(lab_ref, lse_ref, scale_ref, h_ref[...],
                           w_ref[...], pl.program_id(1), tv, v, vocab_first)


def _specs(d, tm, tv, vocab_first):
    """Block specs of a per-row column ``[N, 1]``, of h and of w on the
    (row tile, vocab tile) grid."""
    row = pl.BlockSpec((tm, 1), lambda i, j: (i, 0))
    h = pl.BlockSpec((tm, d), lambda i, j: (i, 0))
    w = (pl.BlockSpec((tv, d), lambda i, j: (j, 0)) if vocab_first
         else pl.BlockSpec((d, tv), lambda i, j: (0, j)))
    return row, h, w


def _pad_rows(a, n_p, fill=0):
    if a.shape[0] == n_p:
        return a
    pad = ((0, n_p - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=a.dtype.type(fill))


@functools.partial(jax.jit, static_argnames=("vocab_first", "tm", "tv"))
def _fwd_call(h, w, labels, *, vocab_first, tm, tv):
    n, d = h.shape
    v = w.shape[0] if vocab_first else w.shape[1]
    n_p = _round_up(n, tm)
    row, h_spec, w_spec = _specs(d, tm, tv, vocab_first)
    col_f32 = jax.ShapeDtypeStruct((n_p, 1), jnp.float32)
    with _no_x64():
        lse, tgt = pl.pallas_call(
            functools.partial(_fwd_kernel, tv=tv, v=v,
                              vocab_first=vocab_first),
            grid=(n_p // tm, pl.cdiv(v, tv)),
            in_specs=[row, h_spec, w_spec],
            out_specs=[row, row],
            out_shape=[col_f32, col_f32],
            scratch_shapes=[pltpu.VMEM((tm, 1), jnp.float32)] * 3,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="linear_ce_fwd",
            interpret=_interpret(),
        )(_pad_rows(labels.astype(jnp.int32).reshape(-1, 1), n_p, -1),
          _pad_rows(h, n_p), w)
    return lse[:n, 0], tgt[:n, 0]


@functools.partial(jax.jit, static_argnames=("vocab_first", "tm", "tv"))
def _dlogits_call(h, w, labels, lse, scale, *, vocab_first, tm, tv):
    n, d = h.shape
    v = w.shape[0] if vocab_first else w.shape[1]
    n_p = _round_up(n, tm)
    row, h_spec, w_spec = _specs(d, tm, tv, vocab_first)
    h_p = _pad_rows(h, n_p)
    with _no_x64():
        dl = pl.pallas_call(
            functools.partial(_dlogits_kernel, tv=tv, v=v,
                              vocab_first=vocab_first),
            grid=(n_p // tm, pl.cdiv(v, tv)),
            in_specs=[row, row, row, h_spec, w_spec],
            out_specs=pl.BlockSpec((tm, tv), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((n_p, v), h.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="linear_ce_dlogits",
            interpret=_interpret(),
        )(_pad_rows(labels.astype(jnp.int32).reshape(-1, 1), n_p, -1),
          _pad_rows(lse.astype(jnp.float32).reshape(-1, 1), n_p),
          _pad_rows(scale.astype(jnp.float32).reshape(-1, 1), n_p),
          h_p, w)
    # a padded row's dlogits are 0 and its h row 0: both matmuls run over
    # the padded rows rather than copy the [N, V] array to slice them off
    v_axis = 0 if vocab_first else 1
    dh = lax.dot_general(dl, w.astype(h.dtype), (((1,), (v_axis,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dw = (lax.dot_general(dl, h_p, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
          if vocab_first else
          lax.dot_general(h_p, dl, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
    return dh[:n].astype(h.dtype), dw.astype(w.dtype)


def lse_and_target(h, w, labels, vocab_first):
    """Forward: per row the log-sum-exp of ``h @ W`` over the vocabulary
    and the logit of its label, f32 ``[N]`` each. ``labels`` int ``[N]``,
    -1 (or any value outside ``[0, V)``) for a row that has no target."""
    n = h.shape[0]
    v = w.shape[0] if vocab_first else w.shape[1]
    tm, tv = _resolve_tiles(n, v)
    return _fwd_call(h, w, labels, vocab_first=vocab_first, tm=tm, tv=tv)


def head_grads(h, w, labels, lse, scale, vocab_first):
    """Backward: ``(dh, dW)`` of ``sum(scale * (lse - target logit))``,
    ``scale`` f32 ``[N]`` (0 for a row that has no target); ``dh`` in
    h's dtype, ``dW`` in the weight's layout and dtype."""
    n = h.shape[0]
    v = w.shape[0] if vocab_first else w.shape[1]
    tm, tv = _resolve_tiles(n, v)
    return _dlogits_call(h, w, labels, lse, scale, vocab_first=vocab_first,
                         tm=tm, tv=tv)
