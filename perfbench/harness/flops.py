"""Operations and bytes the ALGORITHM needs, from the model's sizes and the
traffic's shapes. Nothing here looks at how the program implements a layer
(its pool dtype, its padding, its recomputation): a roofline or an MFU must
read the same work whatever runs it.

A "model" here is the dict of sizes in a configuration file (HF key names).
"""

from __future__ import annotations

ACT_BYTES = 2       # q, out and training activations: bfloat16 in every cell


def _heads(m):
    h = m["num_attention_heads"]
    kvh = m.get("num_key_value_heads", h)
    d = m.get("head_dim", m["hidden_size"] // h)
    return h, kvh, d


def layer_matmul_params(m):
    """Weights that take part in a matmul, in ONE decoder layer."""
    hid, inter = m["hidden_size"], m["intermediate_size"]
    h, kvh, d = _heads(m)
    attn = hid * h * d + 2 * hid * kvh * d + h * d * hid
    # gated MLP (SwiGLU) has three matrices, the GPT-2 MLP two
    mlp = (3 if m.get("mlp_gated", False) else 2) * hid * inter
    return attn + mlp


def head_matmul_params(m):
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m):
    """N_matmul: every weight a token is multiplied by on its way to its
    logits (embedding lookups are not matmuls; a tied head counts once)."""
    return m["num_hidden_layers"] * layer_matmul_params(m) \
        + head_matmul_params(m)


def attn_flops_token(m, ctx):
    """QK^T and PV for ONE query token over ``ctx`` keys, all layers."""
    h, _, d = _heads(m)
    return 4.0 * ctx * h * d * m["num_hidden_layers"]


def attn_flops_causal(m, s):
    """A whole causal sequence of length s: position i sees i+1 keys — the
    half of the s x s square the algorithm needs, not the full square."""
    h, _, d = _heads(m)
    return 4.0 * (s * (s + 1) / 2.0) * h * d * m["num_hidden_layers"]


def attn_flops_span(m, start, n):
    """n query tokens at positions start..start+n-1 of one causal sequence."""
    h, _, d = _heads(m)
    keys = n * start + n * (n + 1) / 2.0
    return 4.0 * keys * h * d * m["num_hidden_layers"]


# ---- serving ---------------------------------------------------------------

def serve_flops(m, prompt_spans, sampled_ctx):
    """Model FLOPs of a serving window.

    ``prompt_spans``: (start, n) for every run of prompt tokens pushed
    through the layers; ``sampled_ctx``: for every token the window sampled,
    the context its forward pass attended (a first token rides its prompt's
    last position, so it adds a head but no layer pass of its own: pass
    ctx=None for it)."""
    layer = 2.0 * m["num_hidden_layers"] * layer_matmul_params(m)
    head = 2.0 * head_matmul_params(m)
    total = 0.0
    for start, n in prompt_spans:
        total += n * layer + attn_flops_span(m, start, n)
    for ctx in sampled_ctx:
        total += head
        if ctx is not None:
            total += layer + attn_flops_token(m, ctx)
    return total


def ragged_attention_work(m, calls, kv_bytes):
    """FLOPs and bytes of paged attention for a list of kernel calls' worth
    of work. ``calls``: (ctx_before, n_q) per slot and pass — n_q query
    tokens appended to a history of ctx_before keys. Per layer: QK^T + PV
    FLOPs; K and V of the whole history read once per pass at the
    configuration's KV dtype, q read and out written once. New K/V rows are
    written by the cache update, not by attention."""
    h, kvh, d = _heads(m)
    flops = byts = 0.0
    for ctx, n in calls:
        if n <= 0:
            continue
        keys = n * ctx + n * (n + 1) / 2.0
        flops += 4.0 * keys * h * d
        byts += 2.0 * (ctx + n) * kvh * d * kv_bytes \
            + 2.0 * n * h * d * ACT_BYTES
    L = m["num_hidden_layers"]
    return flops * L, byts * L


# ---- training --------------------------------------------------------------

def train_flops_per_token(m, seq):
    """Forward + backward model FLOPs per trained token: 6 N_matmul plus
    causal attention (backward = 2 x forward). Recomputation is not
    counted."""
    return 6.0 * matmul_params(m) + 3.0 * attn_flops_causal(m, seq) / seq


def flash_attention_work(m, batch, seq):
    """Causal flash attention forward + backward for one step, all layers.
    FLOPs: fwd 4*S(S+1)/2*H*d per sequence, bwd twice that (dQ, dK, dV and
    the recomputed scores count as the algorithm's 2x). Bytes: fwd reads
    q,k,v and writes o; bwd reads q,k,v,o,do and writes dq,dk,dv."""
    h, kvh, d = _heads(m)
    L = m["num_hidden_layers"]
    fwd = 4.0 * (seq * (seq + 1) / 2.0) * h * d * batch
    q_b = batch * seq * h * d * ACT_BYTES
    kv_b = batch * seq * kvh * d * ACT_BYTES
    fwd_bytes = 2 * q_b + 2 * kv_b                  # q,o + k,v
    bwd_bytes = 4 * q_b + 4 * kv_b                  # q,o,do,dq + k,v,dk,dv
    return 3.0 * fwd * L, (fwd_bytes + bwd_bytes) * L


def roofline_seconds(flops, byts, peaks):
    """Least time the chip could take, and which bound it is."""
    tf, tb = flops / peaks.flops, byts / peaks.hbm_bw
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
