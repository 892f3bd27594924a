"""Mamba-2 (state-space duality, arXiv:2405.21060) for a batch of
independent streams — pure ``jax.numpy``, shape-static.

One stream's recurrence, per head h (P channels, N state columns):

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t + D * x_t

with ``A < 0`` a scalar per head and B, C shared by the heads of a group.
:func:`ssd_chunked` computes a whole ``[B, S]`` block from an initial state
and returns the final one: inside a chunk of ``chunk`` positions the
recurrence is the masked matrix ``(C B^T * decay)`` applied to ``dt x`` (MXU
work), between chunks the state is carried by a short scan. :func:`ssd_step`
is the one-position update a decode step needs. Both advance NOTHING where
``dt`` is 0, which is how a caller masks padded positions and idle streams.

The depthwise causal convolution in front of the recurrence carries its
last ``K - 1`` input columns between calls (:func:`causal_conv_carry`); the
output side is a gated RMSNorm over channel groups
(:func:`gated_group_rms_norm`).

All state math is float32 whatever the activations' dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssd_chunked", "ssd_step", "ssd_scan", "causal_conv_carry",
           "gated_group_rms_norm"]


def causal_conv_carry(x, tail, w, b, lengths):
    """Depthwise causal conv over time with a carried tail.

    x [B, S, C] this call's inputs, ``tail`` [B, K-1, C] the K-1 inputs
    before them, w [K, C], b [C] or None, ``lengths`` [B] how many of the
    S columns are real. Returns (y [B, S, C], new tail): the new tail is
    the K-1 columns that end at each stream's own length, so a stream with
    length 0 keeps its tail and padding never enters it."""
    K = w.shape[0]
    S = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(seq[:, k:k + S].astype(jnp.float32)
            * w[k].astype(jnp.float32) for k in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    idx = lengths[:, None] + jnp.arange(K - 1, dtype=lengths.dtype)[None]
    new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)


def gated_group_rms_norm(y, z, w, groups, eps):
    """``RMSNorm(y * silu(z))`` over ``groups`` equal channel groups of the
    last axis, then the per-channel weight. float32 inside."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shp = y.shape
    g = y.reshape(shp[:-1] + (groups, shp[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shp) * w.astype(jnp.float32)


def _expand_groups(m, heads):
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssd_step(h, x, dt, A, Bm, Cm, D):
    """One position. h [B, H, P, N] f32; x [B, H, P]; dt [B, H] (already
    softplus'ed; 0 = hold); A, D [H]; Bm, Cm [B, G, N].
    Returns (y [B, H, P] f32, new h)."""
    H = x.shape[1]
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    Bh = _expand_groups(Bm.astype(jnp.float32), H)
    Ch = _expand_groups(Cm.astype(jnp.float32), H)
    decay = jnp.exp(dt * A.astype(jnp.float32))
    h = h * decay[:, :, None, None] \
        + (dt[:, :, None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(h * Ch[:, :, None, :], axis=-1) \
        + D.astype(jnp.float32)[None, :, None] * x
    return y, h


def ssd_scan(h, x, dt, A, Bm, Cm, D):
    """The recurrence position by position (``lax.scan`` over S): the
    definition :func:`ssd_chunked` is tested against. Shapes as there."""
    def body(h, t):
        x_t, dt_t, b_t, c_t = t
        y, h = ssd_step(h, x_t, dt_t, A, b_t, c_t, D)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    h, ys = lax.scan(body, h.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), h


def ssd_chunked(h0, x, dt, A, Bm, Cm, D, chunk=128):
    """A block of positions. h0 [B, H, P, N] f32; x [B, S, H, P];
    dt [B, S, H] (softplus'ed; 0 at a position = the state holds and
    nothing is added); A, D [H]; Bm, Cm [B, S, G, N].
    Returns (y [B, S, H, P] f32, final state)."""
    B_, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    Q = min(int(chunk), S)
    pad = -S % Q
    if pad:
        # dt = 0 on the padding: it holds the state and adds nothing
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (x, dt, Bm, Cm))
    nc = (S + pad) // Q
    R = H // G                      # heads of one group: h = g * R + r
    f32 = jnp.float32
    xc = x.astype(f32).reshape(B_, nc, Q, G, R, P)
    dtc = dt.astype(f32).reshape(B_, nc, Q, G, R)
    Bc = Bm.astype(f32).reshape(B_, nc, Q, G, N)
    Cc = Cm.astype(f32).reshape(B_, nc, Q, G, N)

    a = dtc * A.astype(f32).reshape(G, R)                # <= 0
    acum = jnp.cumsum(a, axis=2)                         # inclusive
    xdt = xc * dtc[..., None]                            # dt_s x_s
    # inside a chunk: y_l += sum_{s<=l} (C_l.B_s) exp(acum_l - acum_s) xdt_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc)        # [B, nc, G, Q, Q]
    al = jnp.moveaxis(acum, 2, -1)                       # [B, nc, G, R, Q]
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    seg = jnp.where(tril, al[..., :, None] - al[..., None, :], -jnp.inf)
    m = cb[:, :, :, None] * jnp.exp(seg)                 # [B,nc,G,R,l,s]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xdt)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(acum[:, :, -1:] - acum)             # [B, nc, Q, G, R]
    add = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xdt * to_end[..., None], Bc)
    whole = jnp.exp(acum[:, :, -1])                      # [B, nc, G, R]

    def carry(h, t):
        add_c, whole_c = t
        return h * whole_c[..., None, None] + add_c, h

    h_end, h_in = lax.scan(
        carry, h0.astype(f32).reshape(B_, G, R, P, N),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                      # state entering c
    # the entering state's part: C_l . h_in * exp(acum_l)
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, h_in) \
        * jnp.exp(acum)[..., None]
    y = y + D.astype(f32).reshape(G, R)[:, :, None] * xc
    y = y.reshape(B_, nc * Q, H, P)[:, :S]
    return y, h_end.reshape(B_, H, P, N)
