"""Mixture-of-Experts core — pure jax, shape-static, TPU-first.

Reference parity: ``paddle/incubate/distributed/models/moe`` (MoELayer,
top-k gate, all-to-all dispatch/combine, aux load-balance loss) and the
phi ``moe_*`` GPU dispatch kernels (SURVEY.md §2.1 EP row, §2.3 EP).
Reference mount was empty; no file:line citations available.

TPU-native design — NOT a port of the token-index scatter kernels:

- Gating/dispatch is the GShard/Switch *capacity* formulation: one-hot
  dispatch masks built with cumsum position counters, so every shape is
  static under jit (no ragged scatter; dropped tokens are handled by the
  capacity factor exactly as in the reference's capacity mode).
- Expert compute is a *grouped matmul* over a stacked expert weight bank
  ([E, d, h] einsum) — big, batched MXU work instead of per-expert loops.
- Expert parallelism is an ``lax.all_to_all`` pair over the 'expert' mesh
  axis inside shard_map: tokens travel to their expert's device and back,
  exactly the reference's NCCL all-to-all but compiled into the program
  so XLA overlaps it with the gate/combine math.
- The auxiliary load-balance loss (mean fraction × mean prob, ×E) and the
  router z-loss follow the standard formulations.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import lax

from ..profiler import metrics as _pmetrics

__all__ = ["top_k_gating", "top_k_gating_idx", "moe_dispatch_combine",
           "moe_ffn_grouped", "moe_forward", "moe_forward_ep",
           "sort_rows_by_expert", "moe_forward_dropless", "moe_ablation",
           "top_k_weights", "sigmoid_top_k_router", "softmax_top_k_router",
           "moe_experts_held", "held_range"]


# -- section ablation (profiler.breakdown step-attribution harness) --------
#
# The breakdown harness compiles one program variant per knocked-out
# section; the knockout is a TRACE-TIME decision read from this
# thread-local, so a variant's compiled program simply lacks the
# section. Replacement subgraphs keep every shape/dtype and carry a
# data dependence on the inputs (``_dep0``) so XLA cannot constant-fold
# them away — numerics are garbage under ablation BY DESIGN; only
# timing is meaningful.

_ablation_tl = threading.local()


def _ablated() -> frozenset:
    return getattr(_ablation_tl, "sections", frozenset())


@contextlib.contextmanager
def moe_ablation(sections):
    """Knock out named MoE sections ('gating' | 'sort' | 'a2a' |
    'expert_matmul') for programs TRACED inside this context. Timing
    harness use only (profiler.breakdown); outputs are not meaningful."""
    prev = _ablated()
    _ablation_tl.sections = frozenset(sections)
    try:
        yield
    finally:
        _ablation_tl.sections = prev


def _dep0(x):
    """int32 zero that DEPENDS on ``x``: added to the static replacement
    arrays so the ablated subgraph stays in the compiled program."""
    return (x.reshape(-1)[0] * 0).astype(jnp.int32)


def _ablation_gating(x, T, E, k, capacity):
    """Static round-robin routing standing in for the learned gate:
    same shapes/dtypes as :func:`top_k_gating_idx`'s outputs."""
    z0 = _dep0(x)
    gate_idx = (jnp.arange(T * k, dtype=jnp.int32).reshape(T, k) + z0) % E
    gate_vals = jnp.full((T, k), 1.0 / k, jnp.float32) \
        + z0.astype(jnp.float32)
    pos = (jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None] % max(capacity, 1),
        (T, k)) + z0)
    keep = pos < capacity
    zero = z0.astype(jnp.float32) * 0.0
    return gate_idx, gate_vals, pos, keep, zero, zero


def top_k_weights(select, scores, k, norm_topk_prob=True, scale=1.0):
    """The tail every router here shares: the ``k`` largest columns of
    ``select`` per row, and for each the weight read from ``scores`` at
    that column, normalised over the k chosen (``norm_topk_prob``) and
    times ``scale``. The softmax gates pass their probabilities for
    both; a router with a selection bias passes biased scores to choose
    by and the unbiased ones to weigh by. Returns (idx [T, k], w [T, k])."""
    if select is scores:
        vals, idx = lax.top_k(scores, k)
    else:
        _, idx = lax.top_k(select, k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        vals = vals / jnp.maximum(
            jnp.sum(vals, -1, keepdims=True), 1e-9)
    if scale != 1.0:
        vals = vals * scale
    return idx, vals


def sigmoid_top_k_router(logits, bias, k, norm_topk_prob=True, scale=1.0):
    """Sigmoid router with a selection bias (the DeepSeek-V3 form, one
    group): scores ``sigmoid(logits)``; the k experts are the largest of
    ``scores + bias``; their weights are the UNBIASED scores, normalised
    over the k and scaled. logits [T, E] (computed in float32), bias [E].
    Returns (idx [T, k] int32, w [T, k] float32)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    idx, w = top_k_weights(scores + bias.astype(jnp.float32), scores, k,
                           norm_topk_prob, scale)
    return idx.astype(jnp.int32), w


def softmax_top_k_router(logits, k, norm_topk_prob=True):
    """Softmax router without a bias (the Qwen-MoE form): probabilities
    ``softmax(logits)`` in float32 over ALL experts; the k largest; their
    weights the probabilities, normalised over the k. logits [T, E].
    Returns (idx [T, k] int32, w [T, k] float32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx, w = top_k_weights(probs, probs, k, norm_topk_prob)
    return idx.astype(jnp.int32), w


def top_k_gating(logits, k, capacity, norm_topk_prob=True):
    """Top-k softmax gating with capacity-bounded dispatch tensors.

    logits: [T, E] router outputs (fp32 recommended).
    Returns (dispatch [T, E, C] bool, combine [T, E, C] float,
    aux_loss scalar, z_loss scalar).
    """
    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_idx, gate_vals = top_k_weights(probs, probs, k,
                                        norm_topk_prob)   # [T, k]

    # one-hot per assignment: [T, k, E]
    assign = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each assignment within its expert queue, counting down
    # the token dim then the k dim (priority: token order, then rank)
    flat = assign.reshape(T * k, E)                     # row-major (t, k)
    pos = jnp.cumsum(flat, axis=0) - flat               # positions 0-based
    pos = pos.reshape(T, k, E)
    within_cap = pos < capacity
    keep = assign * within_cap                          # [T, k, E]

    # aux load-balance loss (Switch): E * sum_e(frac_assign_e * mean_prob_e)
    me = jnp.mean(probs, axis=0)                        # [E]
    ce = jnp.sum(jax.nn.one_hot(gate_idx, E), axis=(0, 1)) / (T * k)
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # dispatch/combine: [T, E, C]
    C = capacity
    pos_cap = jnp.clip(pos, 0, C - 1).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos_cap, C, dtype=jnp.float32)  # [T,k,E,C]
    disp_k = keep[..., None] * pos_onehot               # [T, k, E, C]
    dispatch = jnp.sum(disp_k, axis=1)                  # [T, E, C]
    combine = jnp.sum(disp_k * gate_vals[:, :, None, None], axis=1)
    return dispatch, combine, aux_loss, z_loss


def top_k_gating_idx(logits, k, capacity, norm_topk_prob=True):
    """Index-form top-k gating — identical routing/drop semantics to
    :func:`top_k_gating` (same row-major (t, k) queue priority) but
    returns per-assignment INDICES instead of one-hot [T, E, C]
    dispatch/combine tensors. At chip scale the one-hot form is the
    bottleneck: the tensors are O(T·E·C) memory and the dispatch
    einsums cost 2·cf·k·T²·d FLOPs — several times the expert matmuls
    themselves. The index form moves O(T·k·d) bytes with a
    scatter/gather pair instead (the TPU-idiomatic dispatch).

    Returns (gate_idx [T,k] int32, gate_vals [T,k] fp32,
    pos [T,k] int32 queue position, keep [T,k] bool, aux, z).
    """
    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_idx, gate_vals = top_k_weights(probs, probs, k,
                                        norm_topk_prob)   # [T, k]

    assign = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [T, k, E]
    flat = assign.reshape(T * k, E)
    pos_e = jnp.cumsum(flat, axis=0) - flat             # [T*k, E]
    pos = jnp.sum(pos_e.reshape(T, k, E) * assign, axis=-1)  # [T, k]
    pos = pos.astype(jnp.int32)
    keep = pos < capacity

    me = jnp.mean(probs, axis=0)
    ce = jnp.sum(assign, axis=(0, 1)) / (T * k)
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gate_idx.astype(jnp.int32), gate_vals, pos, keep, aux_loss, \
        z_loss


def _dispatch_gather(x, gate_idx, pos, keep, E, C):
    """Build the [E, C, d] expert input bank by scatter+gather.

    Each kept assignment (t, i) owns the unique slot e*C + pos; a
    scatter writes its token index there (sentinel T elsewhere), and a
    gather from zero-padded x fills the bank. Returns (xd [E,C,d],
    slot [T,k] int32 clamped to a trash slot for drops)."""
    T, k = gate_idx.shape
    d = x.shape[-1]
    slot = gate_idx * C + jnp.minimum(pos, C - 1)       # [T, k]
    slot = jnp.where(keep, slot, E * C)                 # trash slot
    token_of = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], (T, k))
    token_idx = jnp.full((E * C + 1,), T, dtype=jnp.int32)
    token_idx = token_idx.at[slot.reshape(-1)].set(
        token_of.reshape(-1), mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xd = x_pad[token_idx[:E * C]].reshape(E, C, d)
    return xd, slot


def _combine_gather(out, slot, gate_vals, keep, x_dtype):
    """Inverse of :func:`_dispatch_gather`: gather each assignment's
    expert output by slot and weight by its gate value."""
    E_C, d = out.shape[0] * out.shape[1], out.shape[-1]
    out_pad = jnp.concatenate(
        [out.reshape(E_C, d),
         jnp.zeros((1, d), out.dtype)], axis=0)
    y_k = out_pad[slot]                                  # [T, k, d]
    w = (gate_vals * keep).astype(y_k.dtype)[..., None]
    return jnp.sum(y_k * w, axis=1).astype(x_dtype)


def moe_dispatch_combine(x, dispatch, combine, expert_fn):
    """Dense (single-device) capacity dispatch: x [T, d] -> [T, d].
    One-hot tensor form (kept for the public OpTest surface; the
    forward paths below use the index form)."""
    xd = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    out = expert_fn(xd)                                 # [E, C, d]
    return jnp.einsum("tec,ecd->td", combine.astype(out.dtype), out)


def moe_ffn_grouped(xd, w_gate, w_up, w_down, act=jax.nn.silu):
    """Grouped SwiGLU FFN over the expert dim: xd [E, C, d],
    w_gate/w_up [E, d, h], w_down [E, h, d]."""
    g = jnp.einsum("ecd,edh->ech", xd, w_gate)
    u = jnp.einsum("ecd,edh->ech", xd, w_up)
    h = act(g) * u
    return jnp.einsum("ech,ehd->ecd", h, w_down)


def moe_forward(x, router_w, expert_fn, k=2, capacity_factor=1.25,
                norm_topk_prob=True):
    """Single-device MoE block: x [T, d], router_w [d, E].
    Returns (out [T, d], aux_loss, z_loss)."""
    T = x.shape[0]
    E = router_w.shape[1]
    ab = _ablated()
    capacity = max(int(capacity_factor * k * T / E), 1)
    if "gating" in ab:
        gate_idx, gate_vals, pos, keep, aux, z = _ablation_gating(
            x, T, E, k, capacity)
    else:
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        gate_idx, gate_vals, pos, keep, aux, z = top_k_gating_idx(
            logits, k, capacity, norm_topk_prob)
    if "sort" in ab:
        # skip the scatter/gather dispatch: a broadcast row bank + a
        # static (in-range) slot map, data-dependent so it survives XLA
        z0 = _dep0(x)
        xd = jnp.broadcast_to(x[0][None, None, :], (E, capacity,
                                                    x.shape[-1])) \
            + z0.astype(x.dtype)
        slot = (jnp.arange(T * k, dtype=jnp.int32).reshape(T, k)
                % (E * capacity)) + z0
    else:
        xd, slot = _dispatch_gather(x, gate_idx, pos, keep, E, capacity)
    out = xd if "expert_matmul" in ab else expert_fn(xd)   # [E, C, d]
    y = _combine_gather(out, slot, gate_vals, keep, x.dtype)
    return y, aux, z


def sort_rows_by_expert(gate_idx, n_experts, bm=128):
    """Expert-sorted, group-padded row layout for the Pallas grouped
    matmul (``ops.pallas.grouped_matmul`` — see its layout contract).

    gate_idx: [T, k] int32 expert assignments. Returns
    (perm [R] int32, tile_gid [nr] int32, P) where R = T*k,
    P = (ceil(R/bm) + n_experts) * bm (static), nr = P // bm, and
    ``perm[r]`` is the padded-layout position of unsorted assignment
    row r (rows of expert e occupy a contiguous, bm-aligned span;
    every expert owns >= 1 tile so empty groups still flush their dw).

    All index arithmetic is 1-D int32 (two small scatters); the [*, d]
    data movement stays gathers — TPU-friendly."""
    T, k = gate_idx.shape
    R = T * k
    E = n_experts
    e_flat = gate_idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(e_flat, stable=True)        # sorted row -> row
    e_sorted = e_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    padded = jnp.maximum(-(-counts // bm) * bm, bm)  # >= 1 tile each
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    offs_p = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1]])
    # padded position of sorted row j: group start + rank within group
    pos_p = offs_p[e_sorted] + (
        jnp.arange(R, dtype=jnp.int32) - offs[e_sorted])
    # perm[r] = padded position of unsorted row r (invert the sort by
    # scattering: perm[order[j]] = pos_p[j])
    perm = jnp.zeros((R,), jnp.int32).at[order].set(pos_p)
    # static capacity: sum(padded) <= R + E*bm, rounded up to a whole
    # number of tiles (R itself need not be bm-aligned)
    P = (-(-R // bm) + E) * bm
    nr = P // bm
    ends = jnp.cumsum(padded)
    tile_gid = jnp.searchsorted(
        ends, jnp.arange(nr, dtype=jnp.int32) * bm, side="right")
    tile_gid = jnp.minimum(tile_gid, E - 1).astype(jnp.int32)
    return perm, tile_gid, P


def moe_forward_dropless(x, router_w, w_gate, w_up, w_down, k=2,
                         norm_topk_prob=True, bm=128, act=jax.nn.silu):
    """Dropless MoE block over the Pallas grouped matmul: x [T, d].

    No capacity, no token drops (the MegaBlocks formulation,
    SURVEY.md §2.3 EP row): assignment rows are expert-sorted into the
    group-padded layout and the three SwiGLU matmuls run as grouped
    MXU matmuls whose weight blocks change only at group boundaries.
    Executed FLOPs exceed activated by <= E*bm/(T*k) padding (~6-12% at
    bench shapes) vs capacity_factor× for the capacity path.
    Returns (out [T, d], aux_loss, z_loss) like :func:`moe_forward`."""
    from .pallas.grouped_matmul import grouped_matmul

    T, d = x.shape
    E = router_w.shape[1]
    ab = _ablated()
    if "gating" in ab:
        gate_idx, gate_vals, _pos, _keep, aux, z = _ablation_gating(
            x, T, E, k, T * k)
    else:
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        # capacity = T*k keeps every assignment (pos < T*k always): the
        # SAME router math as the capacity paths by construction — the
        # dropless-vs-capacity equivalence tests rest on this sharing
        gate_idx, gate_vals, _pos, _keep, aux, z = top_k_gating_idx(
            logits, k, capacity=T * k, norm_topk_prob=norm_topk_prob)

    if "sort" in ab:
        # static identity-ish layout standing in for the argsort/cumsum
        # index machinery (gathers stay — 'sort' measures index build)
        z0 = _dep0(gate_idx)
        R = T * k
        P = (-(-R // bm) + E) * bm
        nr = P // bm
        perm = jnp.arange(R, dtype=jnp.int32) + z0
        tile_gid = (jnp.arange(nr, dtype=jnp.int32) % E) + z0
    else:
        perm, tile_gid, P = sort_rows_by_expert(gate_idx, E, bm=bm)
    # inverse map padded position -> source token (sentinel T = zero row)
    src = jnp.full((P,), T, jnp.int32).at[perm].set(
        jnp.arange(T * k, dtype=jnp.int32) // k)
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    x_p = x_pad[src]                                    # [P, d] gather
    # two grouped matmuls, not a fused [E, d, 2h] concat: the concat
    # would materialize a full copy of every expert bank per forward
    # (+ its remat re-forwards + the VJP residual) on a config that is
    # already HBM-bound. A pre-fused gate|up PARAMETER would avoid the
    # copy but breaks the w_gate/w_up state_dict layout; revisit only
    # if an on-chip A/B shows the wider-N kernel paying for it.
    if "expert_matmul" in ab:
        # rank-1 stand-ins: keep [P, h]/[P, d] shapes and a grad path to
        # x and the banks without the MXU work
        g = x_p[:, :1] * w_gate[0, 0][None, :].astype(x.dtype)
        u = x_p[:, :1] * w_up[0, 0][None, :].astype(x.dtype)
        y_p = (act(g) * u)[:, :1] * w_down[0, 0][None, :].astype(x.dtype)
    else:
        g = grouped_matmul(x_p, w_gate, tile_gid)
        u = grouped_matmul(x_p, w_up, tile_gid)
        y_p = grouped_matmul((act(g) * u).astype(x.dtype), w_down,
                             tile_gid)
    y_k = y_p[perm].reshape(T, k, d)                    # gather back
    w = gate_vals.astype(y_k.dtype)[..., None]
    return jnp.sum(y_k * w, axis=1).astype(x.dtype), aux, z


#: what an expert layer over :func:`moe_experts_held` counts per pass
#: (``inference.cache_spec.StepCounters`` names; declared here, once, for
#: every model that has such a layer): the layer's valid tokens, then the
#: function's two stats
HELD_COUNTERS = ("moe_tokens", "moe_local_pairs", "moe_max_expert_pairs")
_pmetrics.declare("serving/moe_tokens", "counter",
                  "token-layer passes through an expert layer (valid "
                  "tokens x expert layers), from the step program")
_pmetrics.declare("serving/moe_local_pairs", "counter",
                  "(token, expert) pairs whose expert this engine's "
                  "model holds: what its grouped matmuls computed")
_pmetrics.declare("serving/moe_max_expert_pairs", "counter",
                  "pairs of the busiest held expert, summed over "
                  "expert-layer passes (against moe_local_pairs / held "
                  "experts: the load imbalance)")


#: what a TRAINING step's held-share expert layers did, booked on the
#: process registry (the one ``to_static``'s counters use) from the
#: device-side sums a model keeps (:func:`track_train_stats`), when
#: :func:`book_train_stats` is called: no fetch in a step
TRAIN_COUNTERS = ("moe/train_tokens", "moe/train_local_pairs",
                  "moe/train_busiest_pairs")
_pmetrics.declare("moe/train_tokens", "counter",
                  "token-layer passes through a held-share expert layer "
                  "in training steps, summed on the device")
_pmetrics.declare("moe/train_local_pairs", "counter",
                  "(token, expert) pairs of training steps whose expert "
                  "is held here: what the grouped matmuls computed")
_pmetrics.declare("moe/train_busiest_pairs", "counter",
                  "pairs of the busiest held expert of each expert layer "
                  "of training steps, summed (against train_local_pairs / "
                  "held experts: the load imbalance)")
_train_sums = []        # [(tensor of the three sums, what is booked)]


def track_train_stats(sums):
    """Register a model's device-side ``[tokens, local pairs, busiest
    pairs]`` sums (a Tensor the training step reassigns) for
    :func:`book_train_stats`."""
    _train_sums.append([sums, (0.0, 0.0, 0.0)])


def book_train_stats():
    """Fetch every tracked model's sums once and add what they gained
    since the last booking to the ``moe/train_*`` counters."""
    import numpy as np
    reg = _pmetrics.get_registry()
    counters = (reg.counter("moe/train_tokens"),
                reg.counter("moe/train_local_pairs"),
                reg.counter("moe/train_busiest_pairs"))
    for entry in _train_sums:
        now = tuple(float(v) for v in np.asarray(entry[0]._data))
        for c, a, b in zip(counters, now, entry[1]):
            c.inc(a - b)
        entry[1] = now


def held_range(first, held, total):
    """(first, count) of the routed experts an instance holds of a router
    ``total`` wide — :func:`moe_experts_held`'s ``first`` and the leading
    axis of its expert matrices; ``held`` None = all of them."""
    n = total if held is None else int(held)
    first = int(first)
    if not 0 <= first <= first + n <= total:
        raise ValueError(f"held experts [{first}, {first + n}) are not "
                         f"inside the router's {total}")
    return first, n


def moe_experts_held(v, idx, weights, w1, w2, first, valid=None, bm=None,
                     w_gate=None):
    """The routed part of an expert layer that HOLDS experts
    ``[first, first + E_held)`` of a larger routed set: of the ``[T, k]``
    pairs the router chose over ALL experts, only those whose expert is
    held here (and whose token is ``valid``) are sorted, sent through
    the experts' grouped matmuls and summed with the router's weights —
    dropless: every held pair is computed. The other pairs belong to
    other holders, whose outputs add to this one's (everything after the
    selection is linear in the experts' sum).

    The experts: with ``w_gate`` [E_held, d, h] SwiGLU,
    ``(silu(x W_gate) * (x W1)) W2`` — three grouped matmuls over the same
    tiles; without it ``relu(x W1)^2 W2``, two.

    v [T, d] tokens (a latent, or the hidden state), idx [T, k] global
    expert ids, weights [T, k], w1 [E_held, d, h], w2 [E_held, h, d].
    The static row capacity is the worst case (all T*k pairs held); the
    kernel skips the tiles past the live ones. Returns (out [T, d],
    stats) with stats = int32 [local pairs, pairs of the busiest held
    expert].

    Differentiable in ``v``, the expert matrices and ``weights`` (so in
    a router that made them): the backward runs the grouped kernels'
    own over the live tiles alone (``grouped_matmul_live``), at the same
    worst-case capacity, so it too drops no pair."""
    from .pallas.grouped_matmul import grouped_matmul_live

    T, d = v.shape
    k = idx.shape[1]
    E = w1.shape[0]
    if bm is None:
        # a decode step sends an expert a handful of rows: the smallest
        # bf16 row tile; a prompt block fills MXU-sized ones
        bm = 128 if T * k >= 128 * E else 16
    local = idx.astype(jnp.int32) - first
    held = (local >= 0) & (local < E)
    if valid is not None:
        held = held & valid[:, None]
    # group E is the layout's last: everything not computed here
    gid = jnp.where(held, local, E)
    perm, tile_gid, P = sort_rows_by_expert(gid, E + 1, bm=bm)
    n_live = jnp.sum(tile_gid < E).astype(jnp.int32)
    tile_gid = jnp.minimum(tile_gid, E - 1)
    src = jnp.full((P,), T, jnp.int32).at[perm].set(
        jnp.arange(T * k, dtype=jnp.int32) // k)
    v_pad = jnp.concatenate([v, jnp.zeros((1, d), v.dtype)], axis=0)
    x_p = v_pad[src]
    if w_gate is None:
        a = grouped_matmul_live(x_p, w1, tile_gid, n_live, act="relu2")
    else:
        # rows of dead tiles hold whatever was there; nothing reads them
        a = (jax.nn.silu(grouped_matmul_live(x_p, w_gate, tile_gid, n_live))
             * grouped_matmul_live(x_p, w1, tile_gid, n_live)
             ).astype(v.dtype)
    y_p = grouped_matmul_live(a, w2, tile_gid, n_live)
    y_k = y_p[perm].reshape(T, k, d)
    # where, not a product: rows of dead tiles were never written (and
    # before the weights, so their gradient never reads such a row)
    y_k = jnp.where(held[..., None], y_k, 0) \
        * weights.astype(y_k.dtype)[..., None]
    counts = jnp.zeros((E + 1,), jnp.int32).at[gid.reshape(-1)].add(1)
    stats = jnp.stack([jnp.sum(held).astype(jnp.int32),
                       jnp.max(counts[:E])])
    return jnp.sum(y_k, axis=1).astype(v.dtype), stats


def moe_forward_ep(x, router_w, expert_fn_local, axis_name, k=2,
                   capacity_factor=1.25, norm_topk_prob=True):
    """Expert-parallel MoE inside shard_map over ``axis_name``.

    x: [T_local, d] this device's tokens. router_w [d, E] replicated.
    expert_fn_local([E_local, C_total, d]) -> same shape — computes this
    device's experts on all devices' tokens (weights already local).
    Two all-to-alls move token slots expert-ward and back (the NCCL
    alltoall pair of the reference, compiled over ICI).
    """
    ep = lax.psum(1, axis_name)
    T = x.shape[0]
    E = router_w.shape[1]
    if E % ep:
        raise ValueError(f"num_experts {E} not divisible by ep degree {ep}")
    ab = _ablated()
    capacity = max(int(capacity_factor * k * T / E), 1)
    if "gating" in ab:
        gate_idx, gate_vals, pos, keep, aux, z = _ablation_gating(
            x, T, E, k, capacity)
    else:
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        gate_idx, gate_vals, pos, keep, aux, z = top_k_gating_idx(
            logits, k, capacity, norm_topk_prob)
    xd, slot = _dispatch_gather(x, gate_idx, pos, keep, E, capacity)
    if "a2a" in ab:
        # local reshape standing in for the token movement: identical
        # [E/ep, ep*C, d] shape, zero ICI traffic
        xd = xd.reshape(E // ep, ep * capacity, x.shape[-1])
    else:
        # send each expert-slice to its owner; receive every device's
        # slots for the local experts: [E, C, d] -> [E/ep, ep*C, d]
        xd = lax.all_to_all(xd, axis_name, split_axis=0, concat_axis=1,
                            tiled=True)
    out = xd if "expert_matmul" in ab else expert_fn_local(xd)
    if "a2a" in ab:
        out = out.reshape(E, capacity, x.shape[-1])
    else:
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                             tiled=True)                # [E, C, d]
    y = _combine_gather(out, slot, gate_vals, keep, x.dtype)
    # aux losses are per-device estimates; average over the ep group
    aux = lax.pmean(aux, axis_name)
    z = lax.pmean(z, axis_name)
    return y, aux, z
