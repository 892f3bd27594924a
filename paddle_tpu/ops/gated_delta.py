"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) for a batch of
independent streams — pure ``jax.numpy``, shape-static.

One stream's recurrence, per head (``d_k`` key rows, ``d_v`` value columns,
state ``S`` [d_k, d_v], ``g <= 0`` a log-decay and ``beta`` in [0, 1]):

    S <- exp(g_t) S
    d  = beta_t (v_t - S^T k_t)          # what the state gets wrong at k_t
    S <- S + k_t d^T
    o_t = S^T q_t

The update reads the state it writes (``S^T k_t``), so unlike Mamba-2's
diagonal decay (``ops.mamba2``) a chunk has no closed form in the inputs
alone. Three forms of the same recurrence:

- :func:`gated_delta_scan`: position by position (``lax.scan``) — the
  definition the other two are tested against;
- :func:`gated_delta_chunked`: a block of positions as matmuls. Inside a
  chunk of ``C`` positions the deltas solve a unit lower-triangular system
  ``(I + A) D = beta (V - diag(e^gamma) K S_0)`` with ``A[t, s] = beta_t
  e^(gamma_t - gamma_s) k_t.k_s`` for ``s < t`` (the WY / UT transform;
  ``gamma`` the running sum of ``g`` inside the chunk), so ``D = U - W
  S_0`` with ``U = T beta V`` and ``W = T beta e^gamma K``, ``T = (I +
  A)^-1`` (:func:`_unit_lower_inverse`); between chunks the state is
  carried by a short scan;
- :func:`gated_delta_step`: one position, the decode step — on a TPU the
  Pallas kernel ``ops.pallas.gated_delta_step`` (one pass over the state,
  in place), plain ``jax.numpy`` elsewhere.

All advance NOTHING where ``g = 0`` and ``beta = 0``, which is how a caller
masks padded positions and idle streams. All state arithmetic is float32
whatever the activations' dtype, and the chunked form's matmuls run at
``HIGHEST`` precision: a TPU multiplies float32 operands as bfloat16 by
default, and the triangular solve compounds that over a chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..profiler import metrics as _pmetrics

__all__ = ["gated_delta_scan", "gated_delta_chunked", "gated_delta_step",
           "GDN_COUNTERS"]

#: what a delta-rule layer counts per pass
#: (``inference.cache_spec.StepCounters`` names, declared here once for
#: every model that has such a layer)
GDN_COUNTERS = ("gdn_tokens", "gdn_chunk_tokens")
_pmetrics.declare("serving/gdn_tokens", "counter",
                  "token-layer passes through a gated delta-rule layer "
                  "(valid tokens x such layers), from the step program")
_pmetrics.declare("serving/gdn_chunk_tokens", "counter",
                  "those of gdn_tokens that the chunked form took (a "
                  "prompt chunk); the rest took the one-step form")

_HI = lax.Precision.HIGHEST


def _step(S, q, k, v, decay, beta):
    """One position, elementwise (no dot: exact float32 on any platform).
    S [B, H, dk, dv]; q, k [B, H, dk]; v [B, H, dv]; decay, beta [B, H]."""
    S = S * decay[..., None, None]
    m = jnp.sum(S * k[..., None], axis=-2)
    d = beta[..., None] * (v - m)
    S = S + k[..., None] * d[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def gated_delta_scan(S0, q, k, v, g, beta):
    """The recurrence position by position. S0 [B, H, dk, dv]; q, k [B, S,
    H, dk] (already normalised and scaled); v [B, S, H, dv]; g, beta [B, S,
    H]. Returns (o [B, S, H, dv] f32, final state)."""
    def body(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        o, S = _step(S, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in _f32(q, k, v, g, beta))
    S, o = lax.scan(body, S0.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), S


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A`` [..., n, n] strictly lower triangular, n a
    power of two: the diagonal blocks' inverses are merged pairwise,
    ``[[P, 0], [L, R]]^-1 = [[P^-1, 0], [-R^-1 L P^-1, R^-1]]``, from 1 x 1
    up — block forward substitution, log2(n) levels of batched matmuls."""
    n = A.shape[-1]
    lead = A.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), A.dtype)
    b = 1
    while b < n:
        nb = n // (2 * b)
        blocks = A.reshape(lead + (nb, 2, b, nb, 2, b))
        # block (2i + 1, 2i) of every pair i
        low = jnp.moveaxis(jnp.diagonal(blocks[..., :, 1, :, :, 0, :],
                                        axis1=-4, axis2=-2), -1, -3)
        pair = inv.reshape(lead + (nb, 2, b, b))
        p, r = pair[..., 0, :, :], pair[..., 1, :, :]
        low = -jnp.matmul(jnp.matmul(r, low, precision=_HI), p,
                          precision=_HI)
        inv = jnp.concatenate([
            jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
            jnp.concatenate([low, r], axis=-1)], axis=-2)
        b *= 2
    return inv[..., 0, :, :]


def gated_delta_chunked(S0, q, k, v, g, beta, lengths=None, chunk=64):
    """A block of positions in matmul form. Shapes as
    :func:`gated_delta_scan`; ``lengths`` [B] int32: how many of the S
    positions of each row are real (None: all) — the rest advance nothing;
    ``chunk`` a power of two. Returns (o [B, S, H, dv] f32, the state after
    each row's last VALID position), so a caller that feeds a prompt in
    pieces carries the state from one call to the next."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = 1
    while C < min(int(chunk), S):
        C *= 2
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    if lengths is not None:
        live = jnp.arange(S, dtype=jnp.int32)[None, :] < lengths[:, None]
        g = jnp.where(live[..., None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    pad = -S % C
    if pad:
        # g = 0 and beta = 0 on the padding: the state holds
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (S + pad) // C

    def chunks(a):                  # [B, S, H, ...] -> [B, H, nc, C, ...]
        a = a.reshape((B, nc, C) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    qc, kc, vc, gc, bc = map(chunks, (q, k, v, g, beta))
    gam = jnp.cumsum(gc, axis=-1)                      # inclusive, <= 0
    incl = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(incl, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))               # [.., t, s], s <= t
    kk = jnp.einsum("bhctd,bhcsd->bhcts", kc, kc, precision=_HI)
    a = jnp.where(jnp.tril(incl, -1), bc[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate([vc * bc[..., None],
                           kc * (bc * jnp.exp(gam))[..., None]], axis=-1)
    uw = jnp.matmul(_unit_lower_inverse(a), rhs, precision=_HI)
    u, w = uw[..., :dv], uw[..., dv:]
    qk = jnp.einsum("bhctd,bhcsd->bhcts", qc, kc, precision=_HI) * decay
    q_in = qc * jnp.exp(gam)[..., None]                # reads S_0
    k_end = kc * jnp.exp(gam[..., -1:] - gam)[..., None]
    whole = jnp.exp(gam[..., -1])                      # [B, H, nc]

    def carry(S, c):
        u_c, w_c, qk_c, q_c, k_c, whole_c = c
        d = u_c - jnp.matmul(w_c, S, precision=_HI)
        o = jnp.matmul(q_c, S, precision=_HI) \
            + jnp.matmul(qk_c, d, precision=_HI)
        S = whole_c[..., None, None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, d, precision=_HI)
        return S, o

    S_end, o = lax.scan(
        carry, S0.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 2, 0)
              for x in (u, w, qk, q_in, k_end, whole)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, nc * C, dv)    # [B, H, S, dv]
    return jnp.moveaxis(o, 1, 2)[:, :S], S_end


def gated_delta_step(S, q, k, v, g, beta, live=None, reset=None):
    """One position: the decode step. S [B, H, dk, dv] f32; q, k [B, H,
    dk]; v [B, H, dv]; g, beta [B, H]; ``live`` [B] bool, the rows that
    advance (None: all; a row that does not keeps its state and its output
    is unspecified); ``reset`` [B] bool, rows that start from zero state.
    Returns (o [B, H, dv] f32, new state). On a TPU the Pallas kernel
    updates the state in place and neither reads nor writes a row that is
    not live; elsewhere plain ``jax.numpy``. The path is a rule on the
    platform: a kernel that fails on a TPU raises."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    decay = jnp.exp(g)
    if reset is not None:
        decay = jnp.where(reset[:, None], 0.0, decay)
    if live is not None:
        decay = jnp.where(live[:, None], decay, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    if jax.devices()[0].platform == "tpu":
        from .pallas.gated_delta_step import gated_delta_step as _kernel
        return _kernel(S, q, k, v, decay, beta, live)
    return _step(S.astype(jnp.float32), q, k, v, decay, beta)
