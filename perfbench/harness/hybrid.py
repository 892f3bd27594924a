"""Work counters and readers for a hybrid decoder whose layers are chosen
letter by letter (``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` an
expert layer in a latent, ``*`` attention) and which holds a SHARE of its
routed experts. Like ``flops.py`` this counts what the ALGORITHM needs from
the configuration file's sizes, never how the program does it; ``flops.py``
itself counts a dense decoder (one attention and one MLP per layer) and
would be wrong here.

Readers return None where they find nothing to read (a program without the
expert counters, a trace without the kernel): the harness then leaves the
metric out of the line.
"""

from __future__ import annotations

from . import flops, serve
from .readers import _cache_served, _kernel_seconds

ACT_BYTES = flops.ACT_BYTES


def pattern(m):
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def expected_local_pairs(m):
    """Pairs per token whose expert is held here, under uniform routing:
    experts per token x held / routed over."""
    return m["num_experts_per_tok"] * m["n_routed_experts"] \
        / m["router_num_experts"]


def expert_params(m):
    """One routed expert: two matrices in the latent, no gate matrix."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def mamba_flops_token(m):
    """One token through one M layer: the two projections, the conv, and
    the recurrence (decay, outer product, add: 3 HPN; y = h.C: 2 HPN;
    D x: 2 HP)."""
    H, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N = m["n_groups"], m["ssm_state_size"]
    di, hid = H * P, m["hidden_size"]
    cdim = di + 2 * G * N
    proj = hid * (di + cdim + H) + di * hid
    return 2.0 * proj + 2.0 * m["conv_kernel"] * cdim \
        + 5.0 * H * P * N + 2.0 * H * P


def attn_matmul_params(m):
    hid, h, kvh, d = (m["hidden_size"], m["num_attention_heads"],
                      m["num_key_value_heads"], m["head_dim"])
    return hid * h * d + 2 * hid * kvh * d + h * d * hid


def moe_flops_token(m, local_pairs=None):
    """One token through one E layer: router, latent down and up, shared
    expert, and its local pairs' experts (``local_pairs`` per token; the
    expectation under uniform routing if not given)."""
    hid, lat = m["hidden_size"], m["moe_latent_size"]
    dense = hid * m["router_num_experts"] + 2 * hid * lat \
        + 2 * hid * m["moe_shared_expert_intermediate_size"]
    if local_pairs is None:
        local_pairs = expected_local_pairs(m)
    return 2.0 * dense + 2.0 * expert_params(m) * local_pairs


def layers_flops_token(m, local_pairs=None):
    """One token through every layer, attention's context term apart."""
    p = pattern(m)
    return p.count("M") * mamba_flops_token(m) \
        + p.count("*") * 2.0 * attn_matmul_params(m) \
        + p.count("E") * moe_flops_token(m, local_pairs)


def attn_flops(m, start, n):
    """n query tokens at positions start.. of one causal sequence, every
    attention layer."""
    keys = n * start + n * (n + 1) / 2.0
    return 4.0 * keys * m["num_attention_heads"] * m["head_dim"] \
        * pattern(m).count("*")


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
    """FLOPs and bytes of the expert layers' grouped matmuls: ``pairs``
    local (token, expert) pairs in all, over ``passes`` expert-layer
    passes. FLOPs: two matmuls per pair. Bytes: every held expert's two
    matrices once per pass, plus each pair's rows in and out of both."""
    lat, inter = m["moe_latent_size"], m["moe_intermediate_size"]
    fl = 2.0 * expert_params(m) * pairs
    by = passes * m["n_routed_experts"] * expert_params(m) * ACT_BYTES \
        + pairs * 2.0 * (lat + inter) * ACT_BYTES
    return fl, by


# ---- readers -----------------------------------------------------------------

def _measured_pairs_per_token(g):
    if g and g.get("moe_tokens"):
        return g["moe_local_pairs"] / g["moe_tokens"]
    return None


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


def _passes_per_turn(cfg, g):
    """Passes through the model per engine turn (one mixed pass + the
    in-program decode micro-steps), from the engine's own counts: slot
    steps dispatched / (slots x step programs)."""
    if not g.get("slot_occupancy") or not g.get("unified_steps"):
        return None
    steps = g["tokens_emitted"] / g["slot_occupancy"]
    return steps / (cfg["engine"]["num_slots"] * g["unified_steps"])


def _traced_turns(ctx):
    """The engine turns the reduced device window counts, on the host's
    clock (``readers.read_kernel_roofline``'s rule: whole turns inside the
    traced span, the settling ones left out); None where there is none or
    the prefix cache served tokens ``work_items`` would count."""
    win, r = ctx["win"], ctx["reduced"]
    if _cache_served(win):
        return None
    t0, t1 = ctx["span"]
    inside = [(a, b) for a, b in win.turns if a >= t0 and b <= t1]
    inside = inside[len(inside) - r["steps"]:] if r["steps"] else []
    return inside or None


def read_grouped_matmul_roofline(spec, ctx):
    """max(FLOPs / peak, bytes / peak bandwidth) of the expert layers'
    grouped matmuls in the traced turns, over the kernel's summed device
    time. Pairs: the tokens the traced turns pushed through the layers
    (rebuilt from the traffic) x expert layers x the window's MEASURED
    local pairs per token (``moe_local_pairs / moe_tokens``); a program
    without those counters is not read."""
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
    n_e = pattern(m).count("E")
    f, b = grouped_matmul_work(m, tokens * n_e * per_tok,
                               len(inside) * per_turn * n_e)
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = {
        "bound": bound, "kernel_s": secs, "events": n, "flops": f,
        "bytes": b, "pairs_per_token": per_tok, "passes_per_turn": per_turn}
    return 100.0 * least / secs


def ragged_attention_work(m, calls, kv_bytes):
    """``flops.ragged_attention_work`` for a model whose attention layers
    are the ``*`` letters of its pattern: that function counts one
    attention per layer of ``num_hidden_layers``."""
    f, b = flops.ragged_attention_work(m, calls, kv_bytes=kv_bytes)
    share = pattern(m).count("*") / m["num_hidden_layers"]
    return f * share, b * share


def read_ragged_attn_roofline(spec, ctx):
    """``kernel.ragged_attn_roofline.batch``'s reading for a hybrid: the
    attention work of the traced turns, rebuilt from the traffic, on the
    ``*`` layers alone, over the kernel's summed device time."""
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
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = {
        "bound": bound, "kernel_s": secs, "events": n, "flops": f,
        "bytes": b}
    return 100.0 * least / secs


def read_gauge_ratio(spec, ctx):
    """``gauges()[num] / gauges()[den]``, times the configuration's size
    ``times_size`` if the file names one."""
    g = ctx["win"].gauges if ctx["kind"] == "serve" else None
    if not g or not g.get(spec["den"]) or spec["num"] not in g:
        return None
    v = g[spec["num"]] / g[spec["den"]]
    if "times_size" in spec:
        v *= ctx["cfg"]["sizes"][spec["times_size"]]
    return float(v)
