"""Plain reference: the EXAONE-MoE decoder (HF ``model_type`` ``exaone_moe``;
K-EXAONE-236B-A23B) in straightforward ``jax.numpy`` float32 — no kernels,
no cache, no paging, no batching tricks. It imports nothing of the program.

``x`` [T, hidden]; ``RMS`` = RMSNorm with ``rms_norm_eps``. Layer ``l``:

    h = x + Attn_l(RMS(x))          y = h + FFN_l(RMS(h))

``Attn_l``: ``q = u W_q`` (num_attention_heads x head_dim), ``k, v = u W_k,
  u W_v`` (num_key_value_heads x head_dim), no bias; q and k pass a per-head
  RMS norm with a learned scale; on a ``sliding_attention`` layer they are
  then rotated (half-split rotary embedding over the whole head, base
  ``rope_theta``), on a ``full_attention`` layer not; causal softmax
  attention at scale head_dim^-0.5, each KV head serving
  num_attention_heads / num_key_value_heads query heads; on a
  ``sliding_attention`` layer query ``i`` sees keys ``i - window + 1 .. i``
  (the window counts the query's own position) — a MASK here; then ``W_o``.
``FFN_l``, ``mlp_layer_types[l] == "dense"``: ``W_down(silu(W_gate u) *
  W_up u)``.
``FFN_l``, ``"sparse"``: ``s = sigmoid(u W_r)``; the ``num_experts_per_tok``
  largest of ``s + b``; ``w = routed_scaling_factor * s_sel / sum(s_sel)``;
  ``out = sum_k w_k E_k(u) + S(u)``, ``E`` and ``S`` SwiGLU.
Then ``RMS`` and an untied head. Weights are [in, out].

The share. The configuration is one chip's share of an expert-parallel
deployment: the router scores all ``router_num_experts`` experts and
normalises over all it chose, but only experts ``[first_held_expert,
first_held_expert + num_experts)`` exist here; what the absent experts would
add is left out, here as in the program, and the partial result goes on to
the next layer. The vocabulary is the slice the file gives.

Assumed (the published config has no key for them; the configuration file
lists them): the pre-norm residual form above; QK-norm before the rotation;
rotation on window layers only. Departures from the published model: the
multi-token-prediction module (``num_nextn_predict_layers``) is left out —
one token per step.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# layer 0's MLP is dense and the others' sparse, so nothing is a "layer" to
# LeafSource: every leaf is fetched by name
LAYER_PATTERN = r"(?!)"

#: query rows attended at a time: [heads, Q_BLOCK, T] scores are the
#: largest array (64 x 512 x 5120 float32 = 671 MB at the cell's sizes)
Q_BLOCK = 512

# Faults a control plants (``perfbench/tools/control_exaone.py``; a run of
# the benchmark never sets one): ``m["fault"]`` names the one part of the
# mathematics computed wrongly — "no_window" (the first window layer after
# the dense one attends everything), "rope_global" (the global layer rotates
# q and k too), "drop_expert" (the first held expert's output left out).
FAULTS = ("no_window", "rope_global", "drop_expert")


def layer_kinds(m):
    n = m["num_hidden_layers"]
    return list(zip(m["layer_types"][:n], m["mlp_layer_types"][:n]))


def param_specs(m):
    """[(name, shape, base)] in the program's parameter order and names."""
    hid, V = m["hidden_size"], m["vocab_size"]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    inter, e_inter = m["intermediate_size"], m["moe_intermediate_size"]
    held, width = m["num_experts"], m["router_num_experts"]
    sh = e_inter * m["num_shared_experts"]
    out = [("embed_tokens.weight", (V, hid), 0.0)]
    for l, (_, mlp_kind) in enumerate(layer_kinds(m)):
        p = f"layers.{l}."
        out += [
            (p + "input_layernorm.weight", (hid,), 1.0),
            (p + "self_attn.q_proj.weight", (hid, h * d), 0.0),
            (p + "self_attn.k_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.v_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.o_proj.weight", (h * d, hid), 0.0),
            (p + "self_attn.q_norm.weight", (d,), 1.0),
            (p + "self_attn.k_norm.weight", (d,), 1.0),
            (p + "post_attention_layernorm.weight", (hid,), 1.0),
        ]
        p += "mlp."
        if mlp_kind == "dense":
            out += [(p + "gate_proj.weight", (hid, inter), 0.0),
                    (p + "up_proj.weight", (hid, inter), 0.0),
                    (p + "down_proj.weight", (inter, hid), 0.0)]
        else:
            out += [
                (p + "gate.weight", (hid, width), 0.0),
                (p + "gate.e_score_correction_bias", (width,), 0.0),
                (p + "experts.gate_proj", (held, hid, e_inter), 0.0),
                (p + "experts.up_proj", (held, hid, e_inter), 0.0),
                (p + "experts.down_proj", (held, e_inter, hid), 0.0),
                (p + "shared_experts.gate_proj.weight", (hid, sh), 0.0),
                (p + "shared_experts.up_proj.weight", (hid, sh), 0.0),
                (p + "shared_experts.down_proj.weight", (sh, hid), 0.0),
            ]
    out += [("norm.weight", (hid,), 1.0), ("lm_head.weight", (hid, V), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * w


def rope(x, theta):
    """x [T, H, d] at positions 0..T-1, half-split rotation."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


# ---- attention -----------------------------------------------------------------

def attention(m, w, x, mm, kind, l):
    """x [T, hid] (already normed) -> [T, hid]."""
    t = x.shape[0]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    windowed = kind == "sliding_attention"
    q = rms_norm(mm(x, w["q_proj.weight"]).reshape(t, h, d),
                 w["q_norm.weight"], eps)
    k = rms_norm(mm(x, w["k_proj.weight"]).reshape(t, kvh, d),
                 w["k_norm.weight"], eps)
    v = mm(x, w["v_proj.weight"]).reshape(t, kvh, d)
    fault = m.get("fault")
    if windowed or fault == "rope_global":
        theta = m["rope_parameters"]["rope_theta"]
        q, k = rope(q, theta), rope(k, theta)
    if fault == "no_window" and l == 1:
        windowed = False
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    k_pos = jnp.arange(t)[None, :]
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)

    def block(args):
        q_b, q_pos = args                               # [qb, h, d], [qb]
        s = jnp.einsum("qhd,khd->hqk", q_b, k, precision=HI) / jnp.sqrt(
            jnp.float32(d))
        see = k_pos <= q_pos[:, None]
        if windowed:
            see = see & (k_pos > q_pos[:, None] - m["sliding_window"])
        s = jnp.where(see[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (q.reshape(t // qb, qb, h, d),
                            jnp.arange(t).reshape(t // qb, qb)))
    return mm(o.reshape(t, h * d), w["o_proj.weight"])


# ---- sparse MLP ----------------------------------------------------------------

def route(m, w, u, mm):
    """Combine weights over ALL routed experts: [T, width], zero where an
    expert was not chosen."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(mm(u, w["gate.weight"]))
    _, sel = jax.lax.top_k(s + w["gate.e_score_correction_bias"], k)
    ws = jnp.take_along_axis(s, sel, axis=-1)
    if m["norm_topk_prob"]:
        ws = ws / jnp.sum(ws, -1, keepdims=True)
    ws = ws * m["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(ws)


def sparse_mlp(m, w, u, mm, first=None, shared=True):
    """The layer's output for the experts ``[first, first + held)`` held
    here (``first`` defaults to the file's), plus the shared expert."""
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
        out = out + swiglu(u, w["shared_experts.gate_proj.weight"],
                           w["shared_experts.up_proj.weight"],
                           w["shared_experts.down_proj.weight"], mm)
    return out


def mlp(m, w, u, mm, kind):
    if kind == "dense":
        return swiglu(u, w["gate_proj.weight"], w["up_proj.weight"],
                      w["down_proj.weight"], mm)
    return sparse_mlp(m, w, u, mm)


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
    for l, (attn_kind, mlp_kind) in enumerate(layer_kinds(m)):
        words, x = jax.lax.optimization_barrier((words, x))
        w = layer_weights(src, words, l)
        wa, wm = _under(w, "self_attn."), _under(w, "mlp.")

        def per_seq(xs):
            xs = xs + attention(
                m, wa, rms_norm(xs, w["input_layernorm.weight"], eps), mm,
                attn_kind, l)
            return xs + mlp(
                m, wm, rms_norm(xs, w["post_attention_layernorm.weight"],
                                eps), mm, mlp_kind)

        x = jax.lax.map(per_seq, x)
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
