"""Pallas inner kernels for the chunked fused linear+cross-entropy.

``ops/fused_ce.py`` scans the vocab in chunks; the matmul producing
each ``[N, vc]`` logits block is XLA's job, but the per-chunk softmax
STATISTICS (chunk max, exp-sum, target gather) and the backward's
``dlogits`` construction each lower to several elementwise HLOs that
round-trip the f32 logits block through HBM between them. These
kernels keep the whole block in VMEM for one pass each:

- :func:`chunk_stats`: ``logits [N, vc]`` -> (m, s, t): the row max
  over valid columns, ``sum(exp(logits - m))``, and the target logit
  gathered by comparing a column iota against the row's local label
  (no one-hot materialized).
- :func:`chunk_dlogits`: ``(softmax(logits) - onehot(label)) * scale``
  for the backward, again without materializing the one-hot.

The chunk grid clamps the tail chunk's start back into bounds instead
of padding the weight (fused_ce._chunk_grid), so a chunk's first
``lo`` columns may OVERLAP the previous chunk: both kernels mask
``col < lo`` out (``lo`` is 0 everywhere but the tail).

Both run in interpret mode off-TPU (the oracle-parity tests exercise
exactly that path); ``fused_ce`` routes through them on TPU or when a
test forces them on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["chunk_stats", "chunk_dlogits"]

#: rows per program at the default 1024-column chunk. The f32 logits
#: block, its double buffer and the kernels' elementwise temporaries
#: (iota, mask, exp, one-hot) must fit 16 MB of scoped VMEM: 256 x 1024
#: does, 256 x 8192 does not (compiled for a described v5e) — so the
#: row block shrinks as the chunk widens, holding rows x columns.
_BLOCK_ROWS = 256
_BLOCK_ELEMS = _BLOCK_ROWS * 1024


def _stats_kernel(lo_ref, logits_ref, local_ref, m_ref, s_ref, t_ref):
    # literals are explicit f32: weak python floats re-concretize as f64
    # when the interpret-mode kernel jaxpr lowers under an outer
    # x64-enabled trace (the _utils.no_x64 scope covers only the
    # pallas_call trace itself)
    zero = jnp.float32(0.0)
    ninf = jnp.float32(-jnp.inf)
    x = logits_ref[:].astype(jnp.float32)            # [blk, vc]
    lo = lo_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col >= lo                 # overlap prefix already counted
    xm = jnp.where(valid, x, ninf)
    m = jnp.max(xm, axis=-1, keepdims=True)          # [blk, 1]
    # guard a fully-masked row: exp(-inf - -inf) is NaN and jnp.where
    # evaluates both branches — shift by a finite max instead
    m_safe = jnp.where(jnp.isfinite(m), m, zero)
    e = jnp.where(valid, jnp.exp(x - m_safe), zero)
    s_ref[:] = jnp.sum(e, axis=-1, keepdims=True)
    m_ref[:] = m
    # target gather: a row's local label matches at most one valid
    # column; out-of-chunk labels (negative or >= vc) match none
    match = valid & (col == local_ref[:])
    t_ref[:] = jnp.sum(jnp.where(match, x, zero), axis=-1, keepdims=True)


def _dlogits_kernel(lo_ref, logits_ref, lse_ref, local_ref, scale_ref,
                    o_ref):
    zero = jnp.float32(0.0)
    x = logits_ref[:].astype(jnp.float32)
    lo = lo_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col >= lo
    p = jnp.where(valid, jnp.exp(x - lse_ref[:].astype(jnp.float32)),
                  zero)
    onehot = (valid & (col == local_ref[:])).astype(jnp.float32)
    o_ref[:] = ((p - onehot)
                * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _row_blk(n, vc):
    rows = min(_BLOCK_ROWS, max(8, _BLOCK_ELEMS // vc // 8 * 8))
    return min(rows, -(-n // 8) * 8)


def _pad_rows(a, n_pad):
    if n_pad == a.shape[0]:
        return a
    pads = ((0, n_pad - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
    # explicit-dtype fill: jnp.pad's weak-int 0 re-concretizes as i64
    # under an outer x64-enabled trace and fails interpret lowering
    return jnp.pad(a, pads, constant_values=a.dtype.type(0))


def chunk_stats(logits, local, lo):
    """Per-chunk online-softmax stats. ``logits [N, vc]`` (any float),
    ``local [N]`` int32 (the row's label minus the chunk's start
    column — any out-of-range value gathers nothing), ``lo`` scalar
    int32 (columns before it belong to the previous chunk — tail-
    overlap masking). Returns ``(m, s, t)`` f32 ``[N]`` vectors."""
    n, vc = logits.shape
    blk = _row_blk(n, vc)
    n_p = -(-n // blk) * blk
    lo_arr = jnp.reshape(jnp.asarray(lo, jnp.int32), (1,))
    col2 = pl.BlockSpec((blk, 1), lambda i: (i, 0))
    with _no_x64():
        m, s, t = pl.pallas_call(
            _stats_kernel,
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((1,), lambda i: (0,)),
                      pl.BlockSpec((blk, vc), lambda i: (i, 0)),
                      col2],
            out_specs=[col2, col2, col2],
            out_shape=[jax.ShapeDtypeStruct((n_p, 1), jnp.float32)] * 3,
            name="ce_chunk_stats",
            interpret=_interpret(),
        )(lo_arr, _pad_rows(logits, n_p),
          _pad_rows(local.astype(jnp.int32).reshape(-1, 1), n_p))
    return m[:n, 0], s[:n, 0], t[:n, 0]


def chunk_dlogits(logits, lse, local, scale, lo, out_dtype=None):
    """Backward inner: ``(softmax - onehot) * scale`` per chunk.
    ``lse [N]`` the saved log-sum-exp, ``scale [N]`` the per-row loss
    scale (0 for ignored rows), ``lo`` the overlap-prefix bound
    (columns before it emit 0 — the previous chunk owns them).
    Returns ``[N, vc]`` in ``out_dtype`` (default: logits dtype)."""
    n, vc = logits.shape
    out_dtype = logits.dtype if out_dtype is None else out_dtype
    blk = _row_blk(n, vc)
    n_p = -(-n // blk) * blk
    lo_arr = jnp.reshape(jnp.asarray(lo, jnp.int32), (1,))
    col2 = pl.BlockSpec((blk, 1), lambda i: (i, 0))
    with _no_x64():
        out = pl.pallas_call(
            _dlogits_kernel,
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((1,), lambda i: (0,)),
                      pl.BlockSpec((blk, vc), lambda i: (i, 0)),
                      col2, col2, col2],
            out_specs=pl.BlockSpec((blk, vc), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, vc), out_dtype),
            name="ce_chunk_dlogits",
            interpret=_interpret(),
        )(lo_arr, _pad_rows(logits, n_p),
          _pad_rows(lse.astype(jnp.float32).reshape(-1, 1), n_p),
          _pad_rows(local.astype(jnp.int32).reshape(-1, 1), n_p),
          _pad_rows(scale.astype(jnp.float32).reshape(-1, 1), n_p))
    return out[:n]
