"""One step of a per-head ``[d_k, d_v]`` linear-attention state as ONE pass
over it (Pallas/TPU), under one of two static rules.

``ops.gated_delta`` states both recurrences. A decode step updates, per slot
and head, a ``[d_k, d_v]`` float32 state (64 KiB at 128 x 128):

    delta rule:     S <- decay S;  m = S^T k;  d = beta (v - m);  S <- S + k d^T
    no-delta rule:  S <- decay S + k v^T       (lightning attention)
    both:           o = S^T q

In XLA the reduction ``S^T k`` has to finish before the rank-1 update
starts, so the state is read twice and written once. Here a program holds
``hb`` heads' states of one slot in VMEM, does all the lines there, and
writes them back over the input (``input_output_aliases``): one read, one
write. The rule is a static argument: each compiles its own body under its
own kernel name (``gated_delta_step``, ``linear_state_step``), so a trace
tells them apart.

Layout: the state block is ``[hb, d_k, d_v]`` (d_k on sublanes, d_v on
lanes); ``v`` and ``o`` are rows ``[hb, d_v]``; ``k`` and ``q`` come
TRANSPOSED, ``[d_k, hb]``, so a head's key is a column slice that
broadcasts along the lanes and ``S^T k`` is a sublane reduction — no
in-kernel relayout. ``decay`` and ``beta`` are scalars read from SMEM
(scalar prefetch).

A row that is not ``live`` (an idle or prefilling slot in a decode
micro-step) is neither read nor written: its grid steps name the block the
step before them named, so no copy is issued, and do nothing. Rows before
the first live one name that row's first block and copy it through
unchanged, which also covers a call in which no row is live (the block is
written back as it was read).

The call is ONE module-level jitted function whose static arguments are
what is decided at trace time (``ops.pallas.ragged_paged_attention`` says
what a call site costs the host otherwise): the layers of a stack share
one jaxpr and one lowered kernel body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["gated_delta_step", "linear_state_step"]

#: heads of one slot a program holds: 16 states of 64 KiB = 1 MiB a block,
#: in and out double-buffered 4 MiB of VMEM
_HEADS_PER_PROGRAM = 16


def _kernel(row_ref, col_ref, live_ref, lead_ref, decay_ref, beta_ref,
            s_ref, kt_ref, qt_ref, v_ref, so_ref, o_ref, *, hb, delta):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(live_ref[b] == 1)
    def _():
        for h in range(hb):
            head = j * hb + h
            kcol = kt_ref[:, h:h + 1]                      # [dk, 1]
            qcol = qt_ref[:, h:h + 1]
            s = s_ref[h] * decay_ref[b, head]
            d = v_ref[pl.ds(h, 1), :]                      # [1, dv]
            if delta:
                m = jnp.sum(s * kcol, axis=0, keepdims=True)
                d = beta_ref[b, head] * (d - m)
            s = s + kcol * d
            so_ref[h] = s
            o_ref[pl.ds(h, 1), :] = jnp.sum(s * qcol, axis=0,
                                            keepdims=True)

    @pl.when(lead_ref[b] == 1)
    def _():
        so_ref[...] = s_ref[...]


def gated_delta_step(S, q, k, v, decay, beta, live=None):
    """S [B, H, dk, dv] f32 (updated in place); q, k [B, H, dk]; v [B, H,
    dv]; decay (= exp(g), 0 for a row that starts anew), beta [B, H];
    ``live`` [B] bool or None (all). Returns (o [B, H, dv] f32, S)."""
    B, H = S.shape[:2]
    if live is None:
        live = jnp.ones((B,), bool)
    return _call(S, q, k, v, decay, beta, live, hb=_heads(H), delta=True,
                 interpret=_interpret())


def linear_state_step(S, q, k, v, decay, live=None):
    """The no-delta rule ``S <- decay S + k v^T; o = S^T q``, shapes as
    :func:`gated_delta_step` (no beta). Returns (o [B, H, dv] f32, S)."""
    B, H = S.shape[:2]
    if live is None:
        live = jnp.ones((B,), bool)
    return _call(S, q, k, v, decay, jnp.zeros_like(decay), live,
                 hb=_heads(H), delta=False, interpret=_interpret())


def _heads(H):
    return max(h for h in range(1, min(H, _HEADS_PER_PROGRAM) + 1)
               if H % h == 0)


@functools.partial(jax.jit, static_argnames=("hb", "delta", "interpret"))
def _call(S, q, k, v, decay, beta, live, hb, delta, interpret):
    B, H, dk, dv = S.shape
    nj = H // hb
    f32 = jnp.float32
    idx = jnp.arange(B, dtype=jnp.int32)
    # the newest live row at or before b (-1: none yet)
    seen = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)         # 0 if none is live
    lead = seen < 0
    row = jnp.where(lead, first, seen)
    # a dead row's steps all name ONE block: the last of the live row
    # before it, or the first of the first live row
    col = jnp.where(lead, 0, nj - 1).astype(jnp.int32)

    def at(b, j, row, col, live, *_):
        return row[b], jnp.where(live[b] == 1, j, col[b])

    def state_map(b, j, *pre):
        r, c = at(b, j, *pre)
        return r, c, 0, 0

    def rows_map(b, j, *pre):           # v, o: [B, H, dv]
        r, c = at(b, j, *pre)
        return r, c, 0

    def columns(a):                     # [B, H, dk] -> [B, nj, dk, hb]
        return jnp.swapaxes(a.astype(f32).reshape(B, nj, hb, dk), 2, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, nj),
        in_specs=[
            pl.BlockSpec((None, hb, dk, dv), state_map),
            pl.BlockSpec((None, None, dk, hb), state_map),
            pl.BlockSpec((None, None, dk, hb), state_map),
            pl.BlockSpec((None, hb, dv), rows_map),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, dk, dv), state_map),
            pl.BlockSpec((None, hb, dv), rows_map),
        ],
    )
    with _no_x64():
        S, o = pl.pallas_call(
            functools.partial(_kernel, hb=hb, delta=delta),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                       jax.ShapeDtypeStruct((B, H, dv), f32)],
            input_output_aliases={6: 0},
            name="gated_delta_step" if delta else "linear_state_step",
            interpret=interpret,
        )(row, col, live.astype(jnp.int32), lead.astype(jnp.int32),
          decay.astype(f32), beta.astype(f32), S.astype(f32),
          columns(k), columns(q), v.astype(f32))
    # a row that is not live was never written
    return jnp.where(live[:, None, None], o, 0.0), S
