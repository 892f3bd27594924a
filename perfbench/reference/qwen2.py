"""Plain reference: the Qwen2 decoder (arXiv:2407.10671; HF ``Qwen2Model``)
in straightforward ``jax.numpy`` float32 — no kernels, no cache, no paging,
no batching tricks. It imports nothing of the program.

Layer: x += Wo·attn(RoPE(Wq·n1 + bq), RoPE(Wk·n1 + bk), Wv·n1 + bv),
n1 = RMSNorm(x); x += Wd·(silu(Wg·n2) * Wu·n2), n2 = RMSNorm(x). GQA: each
KV head serves num_heads/num_kv_heads query heads. RoPE is the half-split
("rotate_half") form with base rope_theta. Weights are [in, out].

Departures from the published model: none in the mathematics; depth and
weights are the configuration file's (random from the seed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LAYER_PATTERN = r"^layers\.(\d+)\.(.+)$"


def param_specs(m):
    """[(name, shape, base)] in the program's parameter order and names."""
    hid, inter, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    d = hid // h
    out = [("embed_tokens.weight", (V, hid), 0.0)]
    for l in range(m["num_hidden_layers"]):
        p = f"layers.{l}."
        out += [
            (p + "input_layernorm.weight", (hid,), 1.0),
            (p + "self_attn.q_proj.weight", (hid, h * d), 0.0),
            (p + "self_attn.q_proj.bias", (h * d,), 0.0),
            (p + "self_attn.k_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.k_proj.bias", (kvh * d,), 0.0),
            (p + "self_attn.v_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.v_proj.bias", (kvh * d,), 0.0),
            (p + "self_attn.o_proj.weight", (h * d, hid), 0.0),
            (p + "post_attention_layernorm.weight", (hid,), 1.0),
            (p + "mlp.gate_proj.weight", (hid, inter), 0.0),
            (p + "mlp.up_proj.weight", (hid, inter), 0.0),
            (p + "mlp.down_proj.weight", (inter, hid), 0.0),
        ]
    out.append(("norm.weight", (hid,), 1.0))
    if not m.get("tie_word_embeddings", False):
        out.append(("lm_head.weight", (hid, V), 0.0))
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


def attention(m, w, x, mm):
    """x [T, hid] (already normed) -> [T, hid]; causal softmax attention."""
    t = x.shape[0]
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // h
    q = (mm(x, w["self_attn.q_proj.weight"]) + w["self_attn.q_proj.bias"]
         ).reshape(t, h, d)
    k = (mm(x, w["self_attn.k_proj.weight"]) + w["self_attn.k_proj.bias"]
         ).reshape(t, kvh, d)
    v = (mm(x, w["self_attn.v_proj.weight"]) + w["self_attn.v_proj.bias"]
         ).reshape(t, kvh, d)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * d)
    return mm(o, w["self_attn.o_proj.weight"])


def mlp(w, x, mm):
    g = mm(x, w["mlp.gate_proj.weight"])
    u = mm(x, w["mlp.up_proj.weight"])
    return mm(jax.nn.silu(g) * u, w["mlp.down_proj.weight"])


def hidden_states(m, src, ids, mm=mm_f32):
    """ids [K, T] int32 -> final-normed hidden states [K, T, hid] float32.
    One layer's weights live at a time (``src.layer(l)`` inside a scan)."""
    eps = m["rms_norm_eps"]
    x = jnp.take(src.raw("embed_tokens.weight"), ids, axis=0).astype(
        jnp.float32)

    def one_layer(x, l):
        w = src.layer(l)

        def per_seq(xs):
            xs = xs + attention(
                m, w, rms_norm(xs, w["input_layernorm.weight"], eps), mm)
            return xs + mlp(
                w, rms_norm(xs, w["post_attention_layernorm.weight"], eps),
                mm)

        return jax.lax.map(per_seq, x), None

    x, _ = jax.lax.scan(one_layer, x, jnp.arange(m["num_hidden_layers"]))
    return rms_norm(x, src.get("norm.weight"), eps)


def head_weight(m, src):
    if m.get("tie_word_embeddings", False):
        return src.get("embed_tokens.weight").T
    return src.get("lm_head.weight")


def next_token_rows(m, src, ids, pos, tok, mm=mm_f32):
    """For each sequence k and row r: the logits that predict the token
    after position pos[k, r]. Returns (best logit, logit of tok[k, r],
    argmax) — each [K, R]. Logits are made a sequence at a time, so the
    [R, vocab] block is the largest array."""
    hs = hidden_states(m, src, ids, mm)
    wh = head_weight(m, src)

    def per_seq(args):
        h_k, pos_k, tok_k = args
        lg = mm(jnp.take(h_k, pos_k, axis=0), wh)
        chosen = jnp.take_along_axis(lg, tok_k[:, None], axis=1)[:, 0]
        return jnp.max(lg, -1), chosen, jnp.argmax(lg, -1).astype(jnp.int32)

    return jax.lax.map(per_seq, (hs, pos, tok))
