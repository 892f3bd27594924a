"""Work counters and readers for a decoder whose attention layers are of two
kinds (``layer_types``: ``sliding_attention`` over a window of
``sliding_window`` keys, ``full_attention`` over everything), whose MLPs are
dense or sparse by ``mlp_layer_types``, and which holds a SHARE of its
routed experts (``num_experts`` of ``router_num_experts``), every expert a
SwiGLU of three matrices. Like ``flops.py`` this counts what the ALGORITHM
needs from the configuration file's sizes, never how the program does it: a
window layer's query attends at most ``sliding_window`` keys whatever the
kernel walks. ``flops.py`` itself counts a dense decoder with one attention
kind and would be wrong here.

Readers return None where they find nothing to read (a program without the
counters, a trace without the kernel): the harness then leaves the metric
out of the line.
"""

from __future__ import annotations

from . import flops, serve
from .hybrid import (_measured_pairs_per_token, _passes_per_turn,
                     _traced_turns)
from .readers import _cache_served, _kernel_seconds

ACT_BYTES = flops.ACT_BYTES


def kinds(m):
    n = m["num_hidden_layers"]
    return list(zip(m["layer_types"][:n], m["mlp_layer_types"][:n]))


def n_window(m):
    return sum(a == "sliding_attention" for a, _ in kinds(m))


def n_global(m):
    return m["num_hidden_layers"] - n_window(m)


def n_sparse(m):
    return sum(f == "sparse" for _, f in kinds(m))


def expected_local_pairs(m):
    """Pairs per token whose expert is held here, under uniform routing:
    experts per token x held / routed over."""
    return m["num_experts_per_tok"] * m["num_experts"] \
        / m["router_num_experts"]


def expert_params(m):
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def attn_matmul_params(m):
    hid, h, kvh, d = (m["hidden_size"], m["num_attention_heads"],
                      m["num_key_value_heads"], m["head_dim"])
    return hid * h * d + 2 * hid * kvh * d + h * d * hid


def sparse_flops_token(m, local_pairs=None):
    """One token through one sparse MLP: router, the shared experts, and
    its local pairs' experts (``local_pairs`` per token; the expectation
    under uniform routing if not given)."""
    dense = m["hidden_size"] * m["router_num_experts"] \
        + m["num_shared_experts"] * expert_params(m)
    if local_pairs is None:
        local_pairs = expected_local_pairs(m)
    return 2.0 * dense + 2.0 * expert_params(m) * local_pairs


def layers_flops_token(m, local_pairs=None):
    """One token through every layer, attention's context term apart."""
    dense = 2.0 * 3 * m["hidden_size"] * m["intermediate_size"]
    return m["num_hidden_layers"] * 2.0 * attn_matmul_params(m) \
        + (m["num_hidden_layers"] - n_sparse(m)) * dense \
        + n_sparse(m) * sparse_flops_token(m, local_pairs)


def keys_seen(start, n, window=None):
    """Keys attended by n queries at positions start.. of one causal
    sequence, each at most ``window`` of them (its own among them)."""
    if window is None:
        return n * start + n * (n + 1) / 2.0
    return float(sum(min(start + j + 1, window) for j in range(n)))


def attn_flops(m, start, n):
    """n query tokens at positions start.. of one causal sequence, every
    attention layer by its kind."""
    per_key = 4.0 * m["num_attention_heads"] * m["head_dim"]
    return per_key * (n_global(m) * keys_seen(start, n)
                      + n_window(m) * keys_seen(start, n,
                                                m["sliding_window"]))


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


def grouped_matmul_work(m, pairs, passes):
    """FLOPs and bytes of the sparse layers' grouped matmuls: ``pairs``
    local (token, expert) pairs in all, over ``passes`` sparse-layer
    passes. FLOPs: three matmuls per pair. Bytes: every held expert's
    three matrices once per pass, plus each pair's rows in and out of the
    three."""
    hid, inter = m["hidden_size"], m["moe_intermediate_size"]
    fl = 2.0 * expert_params(m) * pairs
    by = passes * m["num_experts"] * expert_params(m) * ACT_BYTES \
        + pairs * 3.0 * (hid + inter) * ACT_BYTES
    return fl, by


def ragged_attention_work(m, calls, kv_bytes):
    """FLOPs and bytes of paged attention for ``calls`` = (ctx_before, n_q)
    per slot and pass, every layer by its kind. A global layer reads K and
    V of its whole history once per pass; a window layer those of the keys
    its n_q queries can see, at most ``sliding_window + n_q - 1``; q read
    and out written once."""
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    w, ng, nw = m["sliding_window"], n_global(m), n_window(m)
    fl = by = 0.0
    for ctx, n in calls:
        if n <= 0:
            continue
        fl += 4.0 * h * d * (ng * keys_seen(ctx, n)
                             + nw * keys_seen(ctx, n, w))
        kv_keys = ng * (ctx + n) + nw * min(ctx + n, w + n - 1)
        by += 2.0 * kv_keys * kvh * d * kv_bytes \
            + (ng + nw) * 2.0 * n * h * d * ACT_BYTES
    return fl, by


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


def _note(spec, ctx, **kv):
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = kv


def read_grouped_matmul_roofline(spec, ctx):
    """max(FLOPs / peak, bytes / peak bandwidth) of the sparse layers'
    grouped matmuls in the traced turns, over the kernel's summed device
    time. Pairs: the tokens the traced turns pushed through the layers
    (rebuilt from the traffic) x sparse layers x the window's MEASURED
    local pairs per token; weights once per decode pass of a turn (the
    least an autoregressive step needs; what the prompt groups re-read is
    the program's). A program without the counters is not read."""
    secs, n = _kernel_seconds(spec, ctx)
    if not secs or not n:
        return None
    win, m = ctx["win"], ctx["cfg"]["sizes"]
    per_tok = _measured_pairs_per_token(win.gauges)
    per_turn = _passes_per_turn(ctx["cfg"], win.gauges)
    inside = _traced_turns(ctx)
    if per_tok is None or per_turn is None or inside is None:
        return None
    spans, sampled, _ = serve.work_items(win, ctx["chunk"], inside[0][0],
                                         inside[-1][1])
    tokens = sum(k for _, k in spans) + sum(1 for c in sampled
                                            if c is not None)
    f, b = grouped_matmul_work(m, tokens * n_sparse(m) * per_tok,
                               len(inside) * per_turn * n_sparse(m))
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    _note(spec, ctx, bound=bound, kernel_s=secs, events=n, flops=f, bytes=b,
          pairs_per_token=per_tok, passes_per_turn=per_turn)
    return 100.0 * least / secs


def read_ragged_attn_roofline(spec, ctx):
    """The attention work of the traced turns, rebuilt from the traffic,
    window layers over their window and global layers over their history,
    over the kernel's summed device time: a kernel that walks pages it
    masks reads low."""
    secs, n = _kernel_seconds(spec, ctx)
    if not secs or not n:
        return None
    inside = _traced_turns(ctx)
    if inside is None:
        return None
    _, _, calls = serve.work_items(ctx["win"], ctx["chunk"], inside[0][0],
                                   inside[-1][1])
    f, b = ragged_attention_work(ctx["cfg"]["sizes"], calls,
                                 ctx["cfg"]["kv_bytes"])
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    _note(spec, ctx, bound=bound, kernel_s=secs, events=n, flops=f, bytes=b)
    return 100.0 * least / secs
