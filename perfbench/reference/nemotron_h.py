"""Plain reference: the Nemotron-H hybrid decoder (HF ``model_type``
``nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B) in straightforward
``jax.numpy`` float32 — no kernels, no cache, no chunking, no batching
tricks. It imports nothing of the program.

Every block is pre-norm with ONE mixer, chosen by the block's letter in
``hybrid_override_pattern``: ``x = x + mixer(RMSNorm(x; eps))``.

``M`` Mamba-2. ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))`` (a
  depthwise causal conv of width ``conv_kernel`` with bias, zeros before the
  sequence); x [T, H, P], B, C [T, G, N] (head h reads group h // (H/G));
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = h_t C_t + D x_t`` — a plain ``lax.scan`` over time from h = 0;
  ``out = GroupRMSNorm(y * silu(z)) W_out`` over G groups of channels.
``E`` LatentMoE. ``s = sigmoid(u W_g)``; the ``num_experts_per_tok``
  largest of ``s + b``; weights ``s[sel] / sum(s[sel]) * scale``;
  ``v = u W_down``; ``r = sum_e w_e relu(v W1_e)^2 W2_e``;
  ``out = r W_up + relu(u S1)^2 S2``.
``*`` GQA attention, causal softmax, scale 1/sqrt(head_dim), no bias and
  NO rotary embedding (the family applies none).
Then ``RMSNorm`` and an untied head. Weights are [in, out].

The share. The configuration is one chip's share of an expert-parallel
deployment: the router scores all ``router_num_experts`` experts and
normalises over all it chose, but only experts ``[first_held_expert,
first_held_expert + n_routed_experts)`` exist here; what the absent experts
would add is left out, here as in the program, and the partial result goes
on to the next layer. The vocabulary is the slice the file gives.

Departures from the published model: the multi-token-prediction module
(``num_nextn_predict_layers``) is left out — one token per step. That is
the only departure in the mathematics; depth, share and weights are the
configuration file's (random from the seed).
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# the layers are not laid out alike, so nothing is a "layer" to LeafSource:
# every leaf is fetched by name
LAYER_PATTERN = r"(?!)"

# where a leaf's values are centred (weights.py: base + std * normal). A
# norm scale is 1 as everywhere. The Mamba leaves below would leave the
# recurrence dead at base 0 (conv output 0.05, x.B.C 1e-5, under the
# group norm's eps), so they sit where a trained model's do: a smoothing
# conv filter, dt around softplus(-2) = 0.13, A around -exp(-2) = -0.14
# (a memory of some fifty tokens), D = 1.
CONV_BASE, DT_BIAS_BASE, A_LOG_BASE, D_BASE = 0.25, -2.0, -2.0, 1.0

# Faults a control plants (``perfbench/tools/control_hybrid.py``; a run of
# the benchmark never sets one): ``m["fault"]`` names the one part of the
# mathematics that is computed wrongly — "router_bf16" (the router's input
# rounded to bfloat16), "state_bf16" (the SSM state rounded to bfloat16
# after every step), "drop_expert" (the first held expert's output left
# out).
FAULTS = ("router_bf16", "state_bf16", "drop_expert")


def _bf16(a):
    # an explicit op: XLA drops a float32 -> bfloat16 -> float32 round trip
    # (it "allows excess precision"), and the fault would plant nothing
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dims(m):
    H, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N = m["n_groups"], m["ssm_state_size"]
    di = H * P
    return H, P, G, N, di, di + 2 * G * N


def pattern(m):
    return m["hybrid_override_pattern"][:m["num_hidden_layers"]]


def param_specs(m):
    """[(name, shape, base)] in the program's parameter order and names."""
    hid, V = m["hidden_size"], m["vocab_size"]
    H, P, G, N, di, cdim = _dims(m)
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    lat, inter = m["moe_latent_size"], m["moe_intermediate_size"]
    sh = m["moe_shared_expert_intermediate_size"]
    held, width = m["n_routed_experts"], m["router_num_experts"]
    out = [("embeddings.weight", (V, hid), 0.0)]
    for l, kind in enumerate(pattern(m)):
        p = f"layers.{l}."
        out.append((p + "norm.weight", (hid,), 1.0))
        p += "mixer."
        if kind == "M":
            out += [
                (p + "dt_bias", (H,), DT_BIAS_BASE),
                (p + "A_log", (H,), A_LOG_BASE),
                (p + "D", (H,), D_BASE),
                (p + "in_proj.weight", (hid, di + cdim + H), 0.0),
                (p + "conv1d.weight", (m["conv_kernel"], cdim), CONV_BASE),
                (p + "conv1d.bias", (cdim,), 0.0),
                (p + "norm.weight", (di,), 1.0),
                (p + "out_proj.weight", (di, hid), 0.0),
            ]
        elif kind == "E":
            out += [
                (p + "gate.weight", (hid, width), 0.0),
                (p + "gate.e_score_correction_bias", (width,), 0.0),
                (p + "fc1_latent_proj.weight", (hid, lat), 0.0),
                (p + "experts.up_proj", (held, lat, inter), 0.0),
                (p + "experts.down_proj", (held, inter, lat), 0.0),
                (p + "fc2_latent_proj.weight", (lat, hid), 0.0),
                (p + "shared_experts.up_proj.weight", (hid, sh), 0.0),
                (p + "shared_experts.down_proj.weight", (sh, hid), 0.0),
            ]
        else:
            out += [
                (p + "q_proj.weight", (hid, h * d), 0.0),
                (p + "k_proj.weight", (hid, kvh * d), 0.0),
                (p + "v_proj.weight", (hid, kvh * d), 0.0),
                (p + "o_proj.weight", (h * d, hid), 0.0),
            ]
    out += [("norm_f.weight", (hid,), 1.0), ("lm_head.weight", (hid, V), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * w


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


# ---- M -----------------------------------------------------------------------

def mamba2(m, w, u, mm):
    """u [T, hid] (already normed) -> [T, hid]."""
    H, P, G, N, di, cdim = _dims(m)
    T = u.shape[0]
    K = m["conv_kernel"]
    zxd = mm(u, w["in_proj.weight"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + cdim], zxd[:, di + cdim:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cdim), jnp.float32), xbc], 0)
    conv = sum(pad[k:k + T] * w["conv1d.weight"][k] for k in range(K))
    xbc = jax.nn.silu(conv + w["conv1d.bias"])
    x = xbc[:, :di].reshape(T, H, P)
    b = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [T, H]
    a = -jnp.exp(w["A_log"])                                 # [H]

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if m.get("fault") == "state_bf16":
            h = _bf16(h)
        y = jnp.sum(h * c_t[:, None, :], axis=-1) + w["D"][:, None] * x_t
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, b, c, dt))
    y = y.reshape(T, di) * jax.nn.silu(z)
    g = y.reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + m["layer_norm_epsilon"])
    return mm(g.reshape(T, di) * w["norm.weight"], w["out_proj.weight"])


# ---- E -----------------------------------------------------------------------

def route(m, w, u, mm):
    """Combine weights over ALL routed experts: [T, width], zero where an
    expert was not chosen."""
    k = m["num_experts_per_tok"]
    if m.get("fault") == "router_bf16":
        u = _bf16(u)
    s = jax.nn.sigmoid(mm(u, w["gate.weight"]))
    _, sel = jax.lax.top_k(s + w["gate.e_score_correction_bias"], k)
    ws = jnp.take_along_axis(s, sel, axis=-1)
    if m["norm_topk_prob"]:
        ws = ws / jnp.sum(ws, -1, keepdims=True)
    ws = ws * m["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(ws)


def routed_experts(m, w, u, mm, first=None, shared=True):
    """The layer's output for the experts ``[first, first + held)`` held
    here (``first`` defaults to the file's), plus the shared expert."""
    first = m.get("first_held_expert", 0) if first is None else first
    held = w["experts.up_proj"].shape[0]
    cw = route(m, w, u, mm)[:, first:first + held]           # [T, held]
    if m.get("fault") == "drop_expert":
        cw = cw.at[:, 0].set(0.0)
    v = mm(u, w["fc1_latent_proj.weight"])

    def one(acc, e):
        w1, w2, c = e
        return acc + c[:, None] * mm(relu2(mm(v, w1)), w2), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(v),
                        (w["experts.up_proj"], w["experts.down_proj"],
                         cw.T))
    out = mm(r, w["fc2_latent_proj.weight"])
    if shared:
        out = out + mm(relu2(mm(u, w["shared_experts.up_proj.weight"])),
                       w["shared_experts.down_proj.weight"])
    return out


# ---- * -----------------------------------------------------------------------

def attention(m, w, x, mm):
    t = x.shape[0]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    q = mm(x, w["q_proj.weight"]).reshape(t, h, d)
    k = jnp.repeat(mm(x, w["k_proj.weight"]).reshape(t, kvh, d), h // kvh, 1)
    v = jnp.repeat(mm(x, w["v_proj.weight"]).reshape(t, kvh, d), h // kvh, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI).reshape(t, h * d)
    return mm(o, w["o_proj.weight"])


MIXERS = {"M": mamba2, "E": routed_experts, "*": attention}


def layer_weights(src, words, l):
    """(block norm scale, {mixer leaf: value}) of layer l, float32, made
    from ``words`` (the seed's words, handed through a barrier so that a
    layer's weights are made when the layer is reached and not all at the
    program's start)."""
    s = copy.copy(src)
    s.words = words
    q = f"layers.{l}.mixer."
    return s.get(f"layers.{l}.norm.weight"), {
        n[len(q):]: s.get(n) for n, _, _ in src.specs if n.startswith(q)}


def hidden_states(m, src, ids, mm=mm_f32):
    """ids [K, T] int32 -> final-normed hidden states [K, T, hid] float32.
    One layer's weights live at a time."""
    eps = m["layer_norm_epsilon"]
    x = jnp.take(src.raw("embeddings.weight"), ids, axis=0).astype(
        jnp.float32)
    words = src.words
    for l, kind in enumerate(pattern(m)):
        words, x = jax.lax.optimization_barrier((words, x))
        norm_w, w = layer_weights(src, words, l)
        mix = MIXERS[kind]
        x = jax.lax.map(
            lambda xs: xs + mix(m, w, rms_norm(xs, norm_w, eps), mm), x)
    return rms_norm(x, src.get("norm_f.weight"), eps)


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
