"""Work counters and readers for a decoder whose token mixers are of two
kinds — ``linear_attention`` (a gated delta-rule layer over a per-head
``[d_k, d_v]`` state) and, every ``full_attention_interval``-th layer,
``full_attention`` (GQA with an output gate) — whose MLPs are all sparse, and
which holds a SHARE of its routed experts (``num_experts`` of
``router_num_experts``), every expert a SwiGLU of three matrices. Like
``flops.py`` this counts what the ALGORITHM needs from the configuration
file's sizes, never how the program does it: the delta rule is counted at its
recurrent form's ``6 d_k d_v`` FLOPs a head and token whatever form runs
(chunked or one-step), and the one-step kernel's bytes are one read and one
write of the state of the rows that ADVANCED in a pass, not of every slot.

The experts' counters are ``exaone_moe.py``'s (the same three-matrix SwiGLU
under the same keys); the readers' helpers are ``hybrid.py``'s and
``readers.py``'s. Readers return None where they find nothing to read (a
program without the counters, a trace without the kernel): the harness then
leaves the metric out of the line.
"""

from __future__ import annotations

from . import flops, serve
from .exaone_moe import (expected_local_pairs, expert_params,
                         grouped_matmul_work, keys_seen)
from .hybrid import (_measured_pairs_per_token, _passes_per_turn,
                     _traced_turns)
from .readers import _cache_served, _kernel_seconds

ACT_BYTES = flops.ACT_BYTES
STATE_BYTES = 4                     # the delta-rule state is float32


def n_full(m):
    return m["num_hidden_layers"] // m["full_attention_interval"]


def n_linear(m):
    return m["num_hidden_layers"] - n_full(m)


def _gdn(m):
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    return hk, hv, dk, dv


def gdn_rule_flops_token(m):
    """The delta rule itself, one token through one layer: per value head
    ``S^T k`` (2), the decayed rank-1 update (2) and ``S^T q`` (2) over the
    ``d_k x d_v`` state."""
    _, hv, dk, dv = _gdn(m)
    return 6.0 * hv * dk * dv


def gdn_flops_token(m):
    """One token through one delta-rule layer: the three projections, the
    conv, and the rule."""
    hk, hv, dk, dv = _gdn(m)
    kd, vd, hid = hk * dk, hv * dv, m["hidden_size"]
    proj = hid * (2 * kd + 2 * vd) + hid * 2 * hv + vd * hid
    return 2.0 * proj + 2.0 * m["linear_conv_kernel_dim"] * (2 * kd + vd) \
        + gdn_rule_flops_token(m)


def attn_matmul_params(m):
    """q AND its output gate, k, v, o."""
    hid, h, kvh, d = (m["hidden_size"], m["num_attention_heads"],
                      m["num_key_value_heads"], m["head_dim"])
    return hid * 2 * h * d + 2 * hid * kvh * d + h * d * hid


def moe_flops_token(m, local_pairs=None):
    """One token through one sparse MLP: router, the shared expert and its
    scalar gate, and its local pairs' experts (``local_pairs`` per token;
    the expectation under uniform routing if not given)."""
    hid = m["hidden_size"]
    dense = hid * m["router_num_experts"] + hid \
        + 3 * hid * m["shared_expert_intermediate_size"]
    if local_pairs is None:
        local_pairs = expected_local_pairs(m)
    return 2.0 * dense + 2.0 * expert_params(m) * local_pairs


def layers_flops_token(m, local_pairs=None):
    """One token through every layer, attention's context term apart."""
    return n_linear(m) * gdn_flops_token(m) \
        + n_full(m) * 2.0 * attn_matmul_params(m) \
        + m["num_hidden_layers"] * moe_flops_token(m, local_pairs)


def attn_flops(m, start, n):
    """n query tokens at positions start.. of one causal sequence, every
    full-attention layer."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] * n_full(m) \
        * keys_seen(start, n)


def serve_flops(m, prompt_spans, sampled_ctx, local_pairs=None):
    """Model FLOPs of a serving window (``flops.serve_flops``'s contract:
    spans of prompt tokens pushed through the layers; per sampled token the
    context its own pass attended, None for a first token)."""
    layer = layers_flops_token(m, local_pairs)
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    total = 0.0
    for start, n in prompt_spans:
        total += n * layer + attn_flops(m, start, n)
    for ctx in sampled_ctx:
        total += head
        if ctx is not None:
            total += layer + attn_flops(m, ctx - 1, 1)
    return total


def ragged_attention_work(m, calls, kv_bytes):
    """FLOPs and bytes of paged attention for ``calls`` = (ctx_before, n_q)
    per slot and pass, on the full-attention layers alone: K and V of the
    whole history once per pass (``kv_heads x head_dim`` columns each), q
    read and the context written once. The output gate is outside the
    kernel and not counted."""
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    fl = by = 0.0
    for ctx, n in calls:
        if n <= 0:
            continue
        fl += 4.0 * h * d * keys_seen(ctx, n)
        by += 2.0 * (ctx + n) * kvh * d * kv_bytes \
            + 2.0 * n * h * d * ACT_BYTES
    return fl * n_full(m), by * n_full(m)


def gated_delta_step_work(m, rows):
    """FLOPs and bytes of the one-step delta-rule kernel for ``rows``
    (slot, delta-rule layer) pairs that ADVANCED by one token: the rule's
    FLOPs; one read and one write of the row's float32 states, and its q,
    k, v in and o out."""
    _, hv, dk, dv = _gdn(m)
    by = rows * hv * (2.0 * dk * dv + 2 * dk + 2 * dv) * STATE_BYTES
    return rows * gdn_rule_flops_token(m), by


# ---- readers -----------------------------------------------------------------

def read_serve_mfu(spec, ctx):
    """Model FLOPs of every prompt and output token the window processed,
    over the window's seconds and the chip's published peak. The experts'
    term uses the EXPECTED local pairs per token, so the number does not
    move with a seed's routing."""
    win = ctx["win"]
    if _cache_served(win):
        return None
    spans, sampled, _ = serve.work_items(win, ctx["chunk"], win.t_start,
                                         win.t_end)
    secs = win.t_end - win.t_start
    if secs <= 0 or not sampled:
        return None
    f = serve_flops(ctx["cfg"]["sizes"], spans, sampled)
    return 100.0 * f / secs / (ctx["peaks"].flops * ctx["chips"])


def _kernel_roofline(spec, ctx, work):
    """max(FLOPs / peak, bytes / peak bandwidth) of ``work(m, spans,
    sampled, calls, turns)`` — the traced turns' work items
    (``serve.work_items``) and how many turns they were — over the kernel's
    summed device time. None without the kernel's events, without whole
    traced turns, or where ``work`` finds a counter missing."""
    secs, n = _kernel_seconds(spec, ctx)
    if not secs or not n:
        return None
    inside = _traced_turns(ctx)
    if inside is None:
        return None
    items = serve.work_items(ctx["win"], ctx["chunk"], inside[0][0],
                             inside[-1][1])
    got = work(ctx["cfg"]["sizes"], *items, len(inside))
    if got is None:
        return None
    (f, b), extra = got
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = dict(
        bound=bound, kernel_s=secs, events=n, flops=f, bytes=b, **extra)
    return 100.0 * least / secs


def read_gated_delta_step_roofline(spec, ctx):
    """The one-step kernel's least time in the traced turns: every output
    token after a request's first is one row that advanced, in every
    delta-rule layer (a first token comes out of the prompt's last chunk,
    which the chunked form takes)."""
    def work(m, spans, sampled, calls, turns):
        rows = sum(1 for c in sampled if c is not None) * n_linear(m)
        return gated_delta_step_work(m, rows), {"rows": rows}

    return _kernel_roofline(spec, ctx, work)


def read_grouped_matmul_roofline(spec, ctx):
    """``exaone_moe.read_grouped_matmul_roofline``'s reading with every
    layer sparse: pairs = the traced turns' tokens x layers x the window's
    MEASURED local pairs per token; the held experts' three matrices once
    per decode pass of a turn and layer."""
    win = ctx["win"]

    def work(m, spans, sampled, calls, turns):
        per_tok = _measured_pairs_per_token(win.gauges)
        per_turn = _passes_per_turn(ctx["cfg"], win.gauges)
        if per_tok is None or per_turn is None:
            return None
        tokens = sum(k for _, k in spans) + sum(1 for c in sampled
                                                if c is not None)
        layers = m["num_hidden_layers"]
        return grouped_matmul_work(m, tokens * layers * per_tok,
                                   turns * per_turn * layers), {
            "pairs_per_token": per_tok, "passes_per_turn": per_turn}

    return _kernel_roofline(spec, ctx, work)


def read_ragged_attn_roofline(spec, ctx):
    """The attention work of the traced turns on the full-attention layers,
    rebuilt from the traffic, over the kernel's summed device time."""
    def work(m, spans, sampled, calls, turns):
        return ragged_attention_work(m, calls, ctx["cfg"]["kv_bytes"]), {}

    return _kernel_roofline(spec, ctx, work)
