"""Fused linear + cross-entropy: the LM loss tail without ``[N, V]``
logits in HBM.

``ce(h @ W, labels)`` over a vocabulary of ``V`` columns would write an
``[N, V]`` logits array (and its f32 softmax, and the backward's
dlogits) to HBM: at 4,096 rows of GPT-2's 50,257 columns, 823 MB of f32
a pass. On the TPU the tail runs the kernels of
``ops/pallas/linear_ce.py``, which keep each logits tile in VMEM. Off
the TPU, and under a mesh (where no ``shard_map`` wraps the kernels),
it scans the vocabulary in chunks of ``_CHUNK_V`` columns with
``jax.numpy``: the CPU's path and the kernels' oracle in the tests.

Contract: ``h [N, D]``, ``w`` as stored — ``[D, V]`` or ``[V, D]``
(``vocab_first``, a tied embedding) —, ``labels [N]`` int; the mean
over the rows whose label is not ``ignore_index`` (0 when there is
none). The matmuls run in ``h``'s dtype with f32 accumulation (the
caller casts ``h`` to the precision the head should run in; ``w`` is
cast tile by tile); ``dW`` comes back in the weight's layout and dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["fused_linear_cross_entropy", "lm_head_loss", "fused_ce_cost"]

#: vocab columns a step of the jnp scan computes: ``[N, 1024]`` x MXU
#: tiles stay fat, and the f32 block and its temporaries stay small
_CHUNK_V = 1024


def _use_kernel() -> bool:
    if jax.default_backend() != "tpu":
        return False
    # under a fleet mesh the kernels are not wrapped in a shard_map (the
    # head is vocab-parallel there): by rule, the jnp scan
    from .pallas._mesh import kernel_placement
    use_kernel, mesh = kernel_placement()
    return use_kernel and mesh is None


def _vocab(w, vocab_first):
    return w.shape[0] if vocab_first else w.shape[1]


def _chunk(w, start, cv, vocab_first):
    """Columns ``[start, start + cv)`` of the head, in its own layout."""
    if vocab_first:
        return lax.dynamic_slice_in_dim(w, start, cv, axis=0)
    return lax.dynamic_slice_in_dim(w, start, cv, axis=1)


def _mm(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _chunk_grid(v):
    """(cv, c): chunk width and count. Chunk ``ci`` covers columns
    ``[start, start + cv)`` with ``start = min(ci*cv, v - cv)``: the last
    chunk's start clamps back so every slice stays in bounds and the
    weight is never padded; it overlaps its predecessor, and masks the
    already-counted prefix (``col < lo``)."""
    cv = min(int(_CHUNK_V), int(v))
    return cv, -(-int(v) // cv)


def _scan_fwd(h, w, labels, vocab_first):
    """(lse, target logit) per row by a scan over vocab chunks."""
    n = h.shape[0]
    v = _vocab(w, vocab_first)
    cv, c = _chunk_grid(v)
    contract = ((1,), (1,)) if vocab_first else ((1,), (0,))

    def chunk(carry, ci):
        m, s, tgt = carry
        start = jnp.minimum(ci * cv, v - cv)
        lo = ci * cv - start          # overlap prefix, 0 except tail
        logits = _mm(h, _chunk(w, start, cv, vocab_first).astype(h.dtype),
                     contract)
        col = jnp.arange(cv)
        lg = jnp.where(col[None, :] >= lo, logits, -jnp.inf)
        m_new = jnp.maximum(m, lg.max(-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(lg - m_new[:, None]).sum(-1)
        local = labels - start
        hit = (col[None, :] == local[:, None]) & (col[None, :] >= lo)
        tgt = tgt + jnp.where(hit, logits, 0.0).sum(-1)
        return (m_new, s, tgt), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, s, tgt), _ = lax.scan(chunk, init, jnp.arange(c))
    return m + jnp.log(s), tgt


def _scan_bwd(h, w, labels, lse, scale, vocab_first):
    """(dh, dW) by a scan over vocab chunks: dh an f32 carry, dW one
    stacked chunk a step (no ``[D, V]`` carry)."""
    v = _vocab(w, vocab_first)
    cv, c = _chunk_grid(v)
    v_axis = 0 if vocab_first else 1

    def chunk(dh_acc, ci):
        start = jnp.minimum(ci * cv, v - cv)
        lo = ci * cv - start
        wc = _chunk(w, start, cv, vocab_first).astype(h.dtype)
        logits = _mm(h, wc, ((1,), (1 - v_axis,)))
        col = jnp.arange(cv)
        keep = col[None, :] >= lo
        p = jnp.where(keep, jnp.exp(logits - lse[:, None]), 0.0)
        hit = (col[None, :] == (labels - start)[:, None]) & keep
        dl = ((p - hit) * scale[:, None]).astype(h.dtype)
        dh_acc = dh_acc + _mm(dl, wc, ((1,), (v_axis,)))
        dw_c = (_mm(dl, h, ((0,), (0,))) if vocab_first
                else _mm(h, dl, ((0,), (0,)))).astype(w.dtype)
        return dh_acc, dw_c

    dh, dw_chunks = lax.scan(chunk, jnp.zeros(h.shape, jnp.float32),
                             jnp.arange(c))
    # chunks 0..c-2 tile [0, (c-1)*cv); the clamped tail covers
    # [v - cv, v): drop its (static-size) overlap prefix
    keep = (c - 1) * cv - (v - cv)
    if vocab_first:
        dw = jnp.concatenate([dw_chunks[:-1].reshape(-1, w.shape[1]),
                              dw_chunks[-1][keep:]], axis=0)
    else:
        body = jnp.moveaxis(dw_chunks[:-1], 0, 1).reshape(w.shape[0], -1)
        dw = jnp.concatenate([body, dw_chunks[-1][:, keep:]], axis=1)
    return dh.astype(h.dtype), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(h, w, labels, ignore_index=-100,
                               vocab_first=False):
    """Mean CE of ``h @ w`` (``h @ w.T`` when ``vocab_first``) against
    ``labels`` without ``[N, V]`` logits in HBM (module docstring)."""
    loss, _ = _flce_fwd(h, w, labels, ignore_index, vocab_first)
    return loss


def _flce_fwd(h, w, labels, ignore_index, vocab_first):
    valid = labels != ignore_index
    # a row with no target names no column
    target = jnp.where(valid, labels, -1).astype(jnp.int32)
    if _use_kernel():
        from .pallas.linear_ce import lse_and_target
        lse, tgt = lse_and_target(h, w, target, vocab_first)
    else:
        lse, tgt = _scan_fwd(h, w, target, vocab_first)
    count = jnp.sum(valid.astype(jnp.float32))
    loss = jnp.sum(jnp.where(valid, lse - tgt, 0.0)) / jnp.maximum(count,
                                                                   1.0)
    return loss, (h, w, target, lse, count)


def _flce_bwd(ignore_index, vocab_first, res, g):
    h, w, target, lse, count = res
    scale = jnp.where(target >= 0, g / jnp.maximum(count, 1.0),
                      0.0).astype(jnp.float32)
    if _use_kernel():
        from .pallas.linear_ce import head_grads
        dh, dw = head_grads(h, w, target, lse, scale, vocab_first)
    else:
        dh, dw = _scan_bwd(h, w, target, lse, scale, vocab_first)
    return dh, dw, None


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)


def lm_head_loss(hidden, weight, labels, vocab_first, ignore_index=-100):
    """The labeled training forward's loss: next-token CE of ``hidden
    [B, S, D]`` through the head ``weight`` against ``labels [B, S]``
    (position t predicts label t + 1), by
    :func:`fused_linear_cross_entropy`. Every row of ``hidden`` is kept:
    the last position of each sequence takes ``ignore_index``, so no
    ``[:, :-1]`` slice copies the hidden state. Under ``auto_cast`` the
    head runs in the AMP dtype, as a matmul would; otherwise in the
    promoted dtype of ``hidden`` and ``weight``."""
    from ..amp.auto_cast import amp_state
    from ..framework.core import apply
    amp = amp_state()
    cdt = amp.dtype if amp.enabled else jnp.promote_types(hidden.dtype,
                                                          weight.dtype)

    def fn(h, w, lab):
        b, s, d = h.shape
        nxt = jnp.concatenate(
            [lab[:, 1:], jnp.full((b, 1), ignore_index, lab.dtype)], 1)
        return fused_linear_cross_entropy(h.reshape(b * s, d).astype(cdt),
                                          w, nxt.reshape(-1), ignore_index,
                                          vocab_first)
    return apply(fn, hidden, weight, labels, name="fused_linear_xent")


def fused_ce_cost(n, d, v, train=False):
    """Static FLOPs/bytes for one fused-CE call (profiler cost-
    accounting surface). Model FLOPs only, like every estimator here:
    the backward's logits RE-matmul is real hardware work but remat-
    class recompute, deliberately not counted (profiler/cost module
    docstring)."""
    from ..profiler.cost import fused_linear_ce_cost
    return fused_linear_ce_cost(int(n), int(d), int(v), train=train)
