"""Plain reference: the Qwen3-Next decoder (HF ``model_type`` ``qwen3_next``;
Qwen3-Next-80B-A3B-Instruct) in straightforward ``jax.numpy`` float32 — no
kernels, no cache, no chunking, no batching tricks (the sequences of a sample
are independent rows). It imports nothing of the program.

``x`` [T, hidden]; ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)``
(zero-centred scale). Layer ``l``:

    h = x + Mixer_l(N(x))          y = h + MoE(N(h))

``Mixer_l`` is ``full_attention`` if ``(l + 1) % full_attention_interval ==
0``, else ``linear_attention``.

``linear_attention`` (Gated DeltaNet), ``H_k`` key heads and ``H_v`` value
  heads of ``d_k`` / ``d_v``: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u
  W_ba`` (each part head-major); ``[q | k | v] = silu(conv([q | k | v]))``, a
  depthwise causal conv of width ``linear_conv_kernel_dim`` without bias,
  zeros before the sequence; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)`` per value head; q, k repeated to ``H_v`` heads
  (head h reads key head h // (H_v / H_k)), each ``x / sqrt(sum x^2 +
  1e-6)``, q times ``d_k^-0.5``; per head from ``S = 0``, a plain
  ``lax.scan`` over time: ``S <- exp(g_t) S``; ``d = beta_t (v_t - S^T
  k_t)``; ``S <- S + k_t d^T``; ``o_t = S^T q_t``; ``y = (o / sqrt(mean(o^2)
  + eps) * w_n) * silu(z)`` per head; ``y W_out``.
``full_attention``: ``[q | gate] = u W_q`` per head, ``k, v = u W_k, u W_v``;
  q and k through ``N`` per head; half-split rotary embedding on the first
  ``head_dim * partial_rotary_factor`` dims (base ``rope_theta``), the rest
  pass through; causal softmax attention at scale head_dim^-0.5, each KV
  head serving num_attention_heads / num_key_value_heads query heads; ``(ctx
  * sigmoid(gate)) W_o``.
``MoE``: ``p = softmax(u W_r)`` over all ``router_num_experts``; the
  ``num_experts_per_tok`` largest; ``w = p_sel / sum(p_sel)``; ``out = sum_k
  w_k E_k(u) + sigmoid(u w_sg) S(u)``, ``E`` and ``S`` SwiGLU.
Then ``N`` and an untied head. Weights are [in, out].

The share. The configuration is one chip's share of an expert-parallel
deployment: the router scores all ``router_num_experts`` experts and
normalises over all it chose, but only experts ``[first_held_expert,
first_held_expert + num_experts)`` exist here; what the absent experts would
add is left out, here as in the program, and the partial result goes on to
the next layer. The vocabulary is the slice the file gives.

Assumed (the published config has no key for them; the configuration file
lists them): everything above that is not a size. Departure from the
published model: the multi-token-prediction head is left out — one token per
step.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# delta-rule and full-attention layers are not laid out alike, so nothing is
# a "layer" to LeafSource: every leaf is fetched by name
LAYER_PATTERN = r"(?!)"

#: query rows attended at a time: [heads, Q_BLOCK, T] scores are the
#: largest array (16 x 512 x 5120 float32 = 168 MB at the cell's sizes)
Q_BLOCK = 512

# where a leaf's values are centred (weights.py: base + std * normal). The
# zero-centred norm scales sit at 0, the gated output norm's plain scale at
# 1. The delta-rule leaves below would leave the recurrence nearly dead at
# base 0 (decay exp(-softplus(0)) = 0.5 a step: the state forgets in a few
# tokens; a conv filter of +-0.02 passes 2% of its input), so they sit where
# a trained model's do: a smoothing conv filter, a + dt_bias around -2 so
# softplus gives ~0.13, A around exp(-1) = 0.37: g ~ -0.05, a per-step decay
# of 0.89-0.98 over the seeded spread of a (std 0.9) — a memory of some
# twenty tokens.
CONV_BASE, DT_BIAS_BASE, A_LOG_BASE = 0.25, -2.0, -1.0

# Faults a control plants (``perfbench/tools/control_hybrid.py``; a run of
# the benchmark never sets one): ``m["fault"]`` names the one part of the
# mathematics computed wrongly — "state_bf16" (the delta-rule state rounded
# to bfloat16 after every step), "no_delta" (beta = 0: the state is never
# written), "zero_state_128" (every delta-rule state zeroed after position
# 127, as a chunk boundary that lost it would), "drop_expert" (the first
# held expert's output left out), "rope_full" (the rotary embedding over the
# whole head).
FAULTS = ("state_bf16", "no_delta", "zero_state_128", "drop_expert",
          "rope_full")


def _bf16(a):
    # an explicit op: XLA drops a float32 -> bfloat16 -> float32 round trip
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def layer_kinds(m):
    n = m["full_attention_interval"]
    return ["full_attention" if (l + 1) % n == 0 else "linear_attention"
            for l in range(m["num_hidden_layers"])]


def _gdn_dims(m):
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    return hk, hv, dk, dv, hk * dk, hv * dv


def param_specs(m):
    """[(name, shape, base)] in the program's parameter order and names."""
    hid, V = m["hidden_size"], m["vocab_size"]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    hk, hv, dk, dv, kd, vd = _gdn_dims(m)
    inter, sh = m["moe_intermediate_size"], \
        m["shared_expert_intermediate_size"]
    held, width = m["num_experts"], m["router_num_experts"]
    out = [("embed_tokens.weight", (V, hid), 0.0)]
    for l, kind in enumerate(layer_kinds(m)):
        p = f"layers.{l}."
        out.append((p + "input_layernorm.weight", (hid,), 0.0))
        if kind == "linear_attention":
            q = p + "linear_attn."
            out += [
                (q + "dt_bias", (hv,), DT_BIAS_BASE),
                (q + "A_log", (hv,), A_LOG_BASE),
                (q + "in_proj_qkvz.weight", (hid, 2 * kd + 2 * vd), 0.0),
                (q + "in_proj_ba.weight", (hid, 2 * hv), 0.0),
                (q + "conv1d.weight",
                 (m["linear_conv_kernel_dim"], 2 * kd + vd), CONV_BASE),
                (q + "norm.weight", (dv,), 1.0),
                (q + "out_proj.weight", (vd, hid), 0.0),
            ]
        else:
            q = p + "self_attn."
            out += [
                (q + "q_proj.weight", (hid, h * 2 * d), 0.0),
                (q + "k_proj.weight", (hid, kvh * d), 0.0),
                (q + "v_proj.weight", (hid, kvh * d), 0.0),
                (q + "o_proj.weight", (h * d, hid), 0.0),
                (q + "q_norm.weight", (d,), 0.0),
                (q + "k_norm.weight", (d,), 0.0),
            ]
        out.append((p + "post_attention_layernorm.weight", (hid,), 0.0))
        q = p + "mlp."
        out += [
            (q + "gate.weight", (hid, width), 0.0),
            (q + "experts.gate_proj", (held, hid, inter), 0.0),
            (q + "experts.up_proj", (held, hid, inter), 0.0),
            (q + "experts.down_proj", (held, inter, hid), 0.0),
            (q + "shared_expert.gate_proj.weight", (hid, sh), 0.0),
            (q + "shared_expert.up_proj.weight", (hid, sh), 0.0),
            (q + "shared_expert.down_proj.weight", (sh, hid), 0.0),
            (q + "shared_expert_gate.weight", (hid, 1), 0.0),
        ]
    out += [("norm.weight", (hid,), 0.0), ("lm_head.weight", (hid, V), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    """Zero-centred: the scale is ``1 + w``."""
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * (1.0 + w)


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


# ---- linear_attention ----------------------------------------------------------

def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated_delta_net(m, w, u, mm):
    """u [T, hid] (already normed) -> [T, hid]."""
    hk, hv, dk, dv, kd, vd = _gdn_dims(m)
    T, K = u.shape[0], m["linear_conv_kernel_dim"]
    fault = m.get("fault")
    qkvz = mm(u, w["in_proj_qkvz.weight"])
    ba = mm(u, w["in_proj_ba.weight"])
    z = qkvz[:, 2 * kd + vd:].reshape(T, hv, dv)
    pad = jnp.concatenate([jnp.zeros((K - 1, 2 * kd + vd), jnp.float32),
                           qkvz[:, :2 * kd + vd]], 0)
    qkv = jax.nn.silu(sum(pad[i:i + T] * w["conv1d.weight"][i]
                          for i in range(K)))
    q = jnp.repeat(l2_norm(qkv[:, :kd].reshape(T, hk, dk)), hv // hk, 1) \
        * dk ** -0.5
    k = jnp.repeat(l2_norm(qkv[:, kd:2 * kd].reshape(T, hk, dk)),
                   hv // hk, 1)
    v = qkv[:, 2 * kd:].reshape(T, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    if fault == "no_delta":
        beta = jnp.zeros_like(beta)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, hv:] + w["dt_bias"])

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t, i = t
        if fault == "zero_state_128":
            S = jnp.where(i == 128, 0.0, S)
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * d[:, None, :]
        if fault == "state_bf16":
            S = _bf16(S)
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(T)))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + m["rms_norm_eps"]) * w["norm.weight"]
    return mm((o * jax.nn.silu(z)).reshape(T, vd), w["out_proj.weight"])


# ---- full_attention ------------------------------------------------------------

def rope(x, theta, rd):
    """x [T, H, d] at positions 0..T-1: half-split rotation of the first
    ``rd`` dims, the others pass through."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], -1)


def attention(m, w, x, mm):
    """x [T, hid] (already normed) -> [T, hid]."""
    t = x.shape[0]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    qg = mm(x, w["q_proj.weight"]).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    q = rms_norm(q, w["q_norm.weight"], eps)
    k = rms_norm(mm(x, w["k_proj.weight"]).reshape(t, kvh, d),
                 w["k_norm.weight"], eps)
    v = mm(x, w["v_proj.weight"]).reshape(t, kvh, d)
    rd = d if m.get("fault") == "rope_full" \
        else int(d * m["partial_rotary_factor"])
    q, k = rope(q, m["rope_theta"], rd), rope(k, m["rope_theta"], rd)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    k_pos = jnp.arange(t)[None, :]
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)

    def block(args):
        q_b, q_pos = args                               # [qb, h, d], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_b, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        s = jnp.where((k_pos <= q_pos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, h, d),
                            jnp.arange(t).reshape(t // qb, qb)))
    return mm((o.reshape(t, h, d) * jax.nn.sigmoid(gate)).reshape(t, h * d),
              w["o_proj.weight"])


# ---- MoE -----------------------------------------------------------------------

def route(m, w, u, mm):
    """Combine weights over ALL routed experts: [T, width], zero where an
    expert was not chosen."""
    p = jax.nn.softmax(mm(u, w["gate.weight"]), axis=-1)
    ws, sel = jax.lax.top_k(p, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        ws = ws / jnp.sum(ws, -1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, sel].set(ws)


def sparse_mlp(m, w, u, mm, first=None, shared=True):
    """The layer's output for the experts ``[first, first + held)`` held
    here (``first`` defaults to the file's), plus the gated shared
    expert."""
    first = m.get("first_held_expert", 0) if first is None else first
    held = w["experts.up_proj"].shape[0]
    cw = route(m, w, u, mm)[:, first:first + held]           # [T, held]
    if m.get("fault") == "drop_expert":
        cw = cw.at[:, 0].set(0.0)

    def one(acc, e):
        wg, wu, wd, c = e
        return acc + c[:, None] * swiglu(u, wg, wu, wd, mm), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["experts.gate_proj"], w["experts.up_proj"],
                           w["experts.down_proj"], cw.T))
    if shared:
        out = out + jax.nn.sigmoid(mm(u, w["shared_expert_gate.weight"])) \
            * swiglu(u, w["shared_expert.gate_proj.weight"],
                     w["shared_expert.up_proj.weight"],
                     w["shared_expert.down_proj.weight"], mm)
    return out


# ---- the model -----------------------------------------------------------------

def layer_weights(src, words, l):
    """{leaf name under ``layers.l.``: value}, float32, made from ``words``
    (the seed's words, handed through a barrier so that a layer's weights
    are made when the layer is reached and not all at the program's
    start)."""
    s = copy.copy(src)
    s.words = words
    q = f"layers.{l}."
    return {n[len(q):]: s.get(n) for n, _, _ in src.specs if n.startswith(q)}


def _under(w, prefix):
    return {n[len(prefix):]: a for n, a in w.items() if n.startswith(prefix)}


def hidden_states(m, src, ids, mm=mm_f32):
    """ids [K, T] int32 -> final-normed hidden states [K, T, hid] float32.
    One layer's weights live at a time."""
    eps = m["rms_norm_eps"]
    x = jnp.take(src.raw("embed_tokens.weight"), ids, axis=0).astype(
        jnp.float32)
    words = src.words
    for l, kind in enumerate(layer_kinds(m)):
        words, x = jax.lax.optimization_barrier((words, x))
        w = layer_weights(src, words, l)
        wm = _under(w, "mlp.")
        if kind == "linear_attention":
            mix, wx = gated_delta_net, _under(w, "linear_attn.")
        else:
            mix, wx = attention, _under(w, "self_attn.")

        def per_seq(xs):
            xs = xs + mix(m, wx, rms_norm(xs, w["input_layernorm.weight"],
                                          eps), mm)
            return xs + sparse_mlp(
                m, wm, rms_norm(xs, w["post_attention_layernorm.weight"],
                                eps), mm)

        # a delta-rule layer's scan is thousands of small sequential
        # steps: the K sequences take them side by side (independent rows
        # of one batch); an attention layer's [heads, Q_BLOCK, T] scores go
        # a sequence at a time
        x = jax.vmap(per_seq)(x) if kind == "linear_attention" \
            else jax.lax.map(per_seq, x)
    return rms_norm(x, src.get("norm.weight"), eps)


def next_token_rows(m, src, ids, pos, tok, mm=mm_f32):
    """For each sequence k and row r: the logits that predict the token
    after position pos[k, r]. Returns (best logit, logit of tok[k, r],
    argmax) — each [K, R]. Logits are made a sequence at a time, so the
    [R, vocab] block is the largest array."""
    hs = hidden_states(m, src, ids, mm)
    wh = src.get("lm_head.weight")

    def per_seq(args):
        h_k, pos_k, tok_k = args
        lg = mm(jnp.take(h_k, pos_k, axis=0), wh)
        chosen = jnp.take_along_axis(lg, tok_k[:, None], axis=1)[:, 0]
        return jnp.max(lg, -1), chosen, jnp.argmax(lg, -1).astype(jnp.int32)

    return jax.lax.map(per_seq, (hs, pos, tok))


def logits(m, src, ids, mm=mm_f32):
    """[K, T, vocab]: every position's logits (tests; small sizes only)."""
    return mm(hidden_states(m, src, ids, mm), src.get("lm_head.weight"))
