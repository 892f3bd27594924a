"""Plain reference: the MiniCPM-SALA decoder (HF ``model_type``
``minicpm_sala``) in straightforward ``jax.numpy`` float32 at HIGHEST
precision — no kernels, no cache, no chunking of the sequence, no pages (the
sequences of a sample are independent rows). It imports nothing of the
program.

``x`` [T, hidden]; ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * w``.

    x_0 = scale_emb * E[ids]
    h = x + alpha * Mixer_l(N(x))      y = h + alpha * SwiGLU(N(h))
    logits = (N(x_L) / (hidden_size / dim_model_base)) W_head

``alpha = scale_depth / sqrt(P)``, ``P`` the published depth (32) whatever
the cut. ``SwiGLU(u) = (silu(u W_g) * u W_u) W_d``. Layer ``l`` is
``mixer_types[l]``:

``minicpm4`` (InfLLM-v2 block-sparse GQA, ``H`` query heads, ``G`` KV
  heads of ``d``, no rotary): ``q, k, v = u W_q, u W_k, u W_v``; q and k
  through ``N`` per head; ``gate = sigmoid(u W_gate)``; ``out = (ctx *
  gate) W_o``. A query at ``t`` in KV group ``j`` attends causally every
  key ``<= t`` if ``t + 1 <= dense_len``; otherwise:
  ``c_i = mean(k_{s i} .. k_{s i + K - 1})`` for ``s i + K - 1 <= t``
  (``K`` sparse_kernel_size, ``s`` sparse_kernel_stride);
  ``score_i = sum over the group's heads of softmax_i(q_h . c_i / sqrt(d))``;
  block ``m`` (keys ``b m .. b m + b - 1``, ``b`` sparse_block_size) scores
  the max over the entries whose keys overlap it; blocks ``< init`` and
  those holding one of the last ``window`` keys ``t - window + 1 .. t``
  score ``+inf``, blocks past ``t`` ``-inf``; the ``topk`` highest blocks
  (``lax.top_k``, ties to the lower ``m``); causal softmax over their keys.
  Here a dense masked softmax over every key, in query blocks, with the
  mask made from that selection.
``lightning-attn`` (decayed linear attention, ``H`` heads of ``d``): ``q, k,
  v = u W_q, u W_k, u W_v``; q and k through ``N`` per head, then the
  half-split rotary embedding over all ``d`` dims (base ``rope_theta``);
  per head from ``S = 0``: ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t =
  (q_t / sqrt(d)) S_t``; ``y = N(o) * sigmoid(u W_gate)`` over all ``H d``
  channels; ``y W_o``. ``lambda_h = exp(-s_h)``, ``s_h = 2^(-8 (h + 1) / H)
  (1 - l' / (P - 1) + 1e-5)``, ``l'`` the layer's PUBLISHED index. The
  recurrence unrolled is ``o_t = sum_{u <= t} lambda_h^(t - u) (q_t . k_u)
  v_u / sqrt(d)``: that sum, in query blocks, is what runs here (a scan of
  33,792 steps a layer would take minutes on the chip); the program's block
  and one-step forms are tested against it.

Weights are [in, out]. The file lists under ``assumed`` what the published
config has no key for.
"""

from __future__ import annotations

import copy
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_PATTERN = r"(?!)"        # two kinds of layer: leaves fetched by name

#: query rows attended at a time: [heads, Q_BLOCK, T] scores are the
#: largest array (32 x 128 x 33792 float32 = 553 MB at the cell's sizes)
Q_BLOCK = 128
#: rows of the MLP at a time ([T_BLOCK, 16384] float32 intermediates)
T_BLOCK = 2048

# Faults a control plants (``perfbench/tools/control_hybrid.py``; a run of
# the benchmark never sets one): ``m["fault"]`` names the one part of the
# mathematics computed wrongly — "dense" (the sparse layers attend every
# key), "topk_half" (half as many blocks), "no_decay" (lambda = 1), and
# "state_bf16" (the lightning state rounded to bfloat16 after every step:
# the recurrence as a scan, the one place it runs as one).
FAULTS = ("dense", "topk_half", "no_decay", "state_bf16")


def kinds(m):
    return list(m["mixer_types"])[:m["num_hidden_layers"]]


def param_specs(m):
    """[(name, shape, base)] in the program's parameter order and names."""
    hid, V, inter = m["hidden_size"], m["vocab_size"], m["intermediate_size"]
    h, g, d = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    lh, ld = m["lightning_nh"], m["lightning_head_dim"]
    out = [("embed_tokens.weight", (V, hid), 0.0)]
    for l, kind in enumerate(kinds(m)):
        p = f"layers.{l}."
        out.append((p + "input_layernorm.weight", (hid,), 1.0))
        if kind == "minicpm4":
            q = p + "self_attn."
            out += [(q + "q_proj.weight", (hid, h * d), 0.0),
                    (q + "k_proj.weight", (hid, g * d), 0.0),
                    (q + "v_proj.weight", (hid, g * d), 0.0),
                    (q + "o_gate.weight", (hid, h * d), 0.0),
                    (q + "o_proj.weight", (h * d, hid), 0.0),
                    (q + "q_norm.weight", (d,), 1.0),
                    (q + "k_norm.weight", (d,), 1.0)]
        else:
            q = p + "lightning_attn."
            out += [(q + "q_proj.weight", (hid, lh * ld), 0.0),
                    (q + "k_proj.weight", (hid, lh * ld), 0.0),
                    (q + "v_proj.weight", (hid, lh * ld), 0.0),
                    (q + "z_proj.weight", (hid, lh * ld), 0.0),
                    (q + "o_proj.weight", (lh * ld, hid), 0.0),
                    (q + "q_norm.weight", (ld,), 1.0),
                    (q + "k_norm.weight", (ld,), 1.0),
                    (q + "o_norm.weight", (lh * ld,), 1.0)]
        out.append((p + "post_attention_layernorm.weight", (hid,), 1.0))
        q = p + "mlp."
        out += [(q + "gate_proj.weight", (hid, inter), 0.0),
                (q + "up_proj.weight", (hid, inter), 0.0),
                (q + "down_proj.weight", (inter, hid), 0.0)]
    out += [("norm.weight", (hid,), 1.0), ("lm_head.weight", (hid, V), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def rope(x, theta):
    """x [T, H, d] at positions 0..T-1: half-split rotation of all d dims."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q_blocks(t):
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    return qb, jnp.arange(t).reshape(t // qb, qb)


# ---- minicpm4 ------------------------------------------------------------------

def overlaps(m, t):
    """[n_blocks, n_off] entry indices overlapping each block, and which are
    real: entry i (keys s i .. s i + K - 1) overlaps block j (keys b j .. b
    j + b - 1) iff s i <= b j + b - 1 and s i + K - 1 >= b j."""
    K, s, b = (m["sparse_kernel_size"], m["sparse_kernel_stride"],
               m["sparse_block_size"])
    n_c = max((t - K) // s + 1, 1)
    n_b = -(-t // b)
    rows = []
    for j in range(n_b):
        lo = max(0, -(-(b * j - K + 1) // s))
        hi = min(n_c - 1, (b * j + b - 1) // s)
        rows.append(list(range(lo, hi + 1)))
    width = max(len(r) for r in rows)
    idx = np.zeros((n_b, width), np.int32)
    ok = np.zeros((n_b, width), bool)
    for j, r in enumerate(rows):
        idx[j, :len(r)] = r
        ok[j, :len(r)] = True
    return jnp.asarray(idx), jnp.asarray(ok), n_c


def chosen_blocks(m, q_b, t_b, c, idx, ok):
    """[QB, G, n_blocks] bool: the blocks each query of the block chose.
    q_b [QB, G, R, d]; t_b [QB] positions; c [n_c, G, d]."""
    K, s, b = (m["sparse_kernel_size"], m["sparse_kernel_stride"],
               m["sparse_block_size"])
    topk = m["sparse_topk"] // (2 if m.get("fault") == "topk_half" else 1)
    n_c, d = c.shape[0], c.shape[-1]
    n_b = idx.shape[0]
    lg = jnp.einsum("qgrd,igd->qgri", q_b, c, precision=HI) / math.sqrt(d)
    real = (jnp.arange(n_c) * s + K - 1)[None, :] <= t_b[:, None]  # [QB, i]
    lg = jnp.where(real[:, None, None], lg, -jnp.inf)
    # a query before the first entry has none: its scores stay 0
    pr = jnp.where(real[:, None, None], jax.nn.softmax(lg, axis=-1), 0.0)
    score = jnp.sum(pr, axis=2)                                # [QB, G, i]
    per = jnp.where(ok, score[..., idx], -jnp.inf).max(-1)     # [QB, G, j]
    j = jnp.arange(n_b)[None, None]
    tt = t_b[:, None, None]
    forced = (j < m["sparse_init_blocks"]) \
        | (b * j + b - 1 >= tt - m["sparse_window_size"] + 1)
    per = jnp.where(b * j > tt, -jnp.inf, jnp.where(forced, jnp.inf, per))
    _, top = jax.lax.top_k(per, topk)
    return jnp.zeros(per.shape, bool).at[
        jnp.arange(per.shape[0])[:, None, None],
        jnp.arange(per.shape[1])[None, :, None], top].set(True)


def sparse_attention(m, w, x, mm):
    """x [T, hid] (already normed) -> [T, hid]."""
    t = x.shape[0]
    h, g, d = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    eps, b = m["rms_norm_eps"], m["sparse_block_size"]
    K, s = m["sparse_kernel_size"], m["sparse_kernel_stride"]
    q = rms_norm(mm(x, w["q_proj.weight"]).reshape(t, g, h // g, d),
                 w["q_norm.weight"], eps)
    k = rms_norm(mm(x, w["k_proj.weight"]).reshape(t, g, d),
                 w["k_norm.weight"], eps)
    v = mm(x, w["v_proj.weight"]).reshape(t, g, d)
    gate = jax.nn.sigmoid(mm(x, w["o_gate.weight"]))
    idx, ok, n_c = overlaps(m, t)
    c = jax.vmap(lambda i: jnp.mean(
        jax.lax.dynamic_slice_in_dim(k, i * s, K), axis=0))(jnp.arange(n_c))
    qb, rows = _q_blocks(t)
    k_pos = jnp.arange(t)

    def block(t_b):
        q_b = q[t_b]                                       # [QB, G, R, d]
        causal = k_pos[None, :] <= t_b[:, None]            # [QB, T]
        if m.get("fault") == "dense":
            allow = jnp.broadcast_to(causal[:, None], (qb, g, t))
        else:
            pick = chosen_blocks(m, q_b, t_b, c, idx, ok)  # [QB, G, n_b]
            keys = jnp.repeat(pick, b, axis=-1)[..., :t]
            dense = (t_b + 1 <= m["sparse_dense_len"])[:, None, None]
            allow = causal[:, None] & (keys | dense)
        sc = jnp.einsum("qgrd,kgd->qgrk", q_b, k, precision=HI) \
            / math.sqrt(d)
        sc = jnp.where(allow[:, :, None], sc, -jnp.inf)
        return jnp.einsum("qgrk,kgd->qgrd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, rows).reshape(t, h * d)
    return mm(o * gate, w["o_proj.weight"])


# ---- lightning-attn --------------------------------------------------------------

def published_depth(m):
    return m["published"]["num_hidden_layers"]


def slopes(m, l):
    """s_h of the layer whose index HERE is ``l``."""
    H, P = m["lightning_nh"], published_depth(m)
    lp = m["first_published_layer"] + l
    h = jnp.arange(H, dtype=jnp.float32)
    return 2.0 ** (-8.0 * (h + 1) / H) * (1.0 - lp / (P - 1) + 1e-5)


def _bf16(a):
    # an explicit op: XLA drops a float32 -> bfloat16 -> float32 round trip
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def lightning(m, w, x, mm, sl):
    """x [T, hid] (already normed) -> [T, hid]; ``sl`` [H] the slopes."""
    t = x.shape[0]
    H, d, eps = m["lightning_nh"], m["lightning_head_dim"], m["rms_norm_eps"]
    fault = m.get("fault")
    q = rope(rms_norm(mm(x, w["q_proj.weight"]).reshape(t, H, d),
                      w["q_norm.weight"], eps), m["rope_theta"]) \
        / math.sqrt(d)
    k = rope(rms_norm(mm(x, w["k_proj.weight"]).reshape(t, H, d),
                      w["k_norm.weight"], eps), m["rope_theta"])
    v = mm(x, w["v_proj.weight"]).reshape(t, H, d)
    if fault == "no_decay":
        sl = jnp.zeros_like(sl)
    if fault == "state_bf16":
        def step(S, a):
            q_t, k_t, v_t = a
            S = _bf16(jnp.exp(-sl)[:, None, None] * S
                      + k_t[:, :, None] * v_t[:, None, :])
            return S, jnp.sum(S * q_t[:, :, None], axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                            (q, k, v))
    else:
        qb, rows = _q_blocks(t)
        k_pos = jnp.arange(t)

        def block(t_b):
            dist = (t_b[:, None] - k_pos[None, :]).astype(jnp.float32)
            dec = jnp.where(dist[None] >= 0, jnp.exp(-sl[:, None, None]
                                                     * dist[None]), 0.0)
            sc = jnp.einsum("qhd,khd->hqk", q[t_b], k, precision=HI) * dec
            return jnp.einsum("hqk,khd->qhd", sc, v, precision=HI)

        o = jax.lax.map(block, rows)
    o = o.reshape(t, H * d)
    y = rms_norm(o, w["o_norm.weight"], eps) \
        * jax.nn.sigmoid(mm(x, w["z_proj.weight"]))
    return mm(y, w["o_proj.weight"])


# ---- the model -----------------------------------------------------------------

def swiglu(m, w, u, mm):
    """u [T, hid] -> [T, hid], T_BLOCK rows at a time."""
    t = u.shape[0]
    tb = min(T_BLOCK, t)
    pad = -t % tb

    def one(ub):
        return mm(jax.nn.silu(mm(ub, w["gate_proj.weight"]))
                  * mm(ub, w["up_proj.weight"]), w["down_proj.weight"])

    up = jnp.pad(u, [(0, pad), (0, 0)]).reshape(-1, tb, u.shape[1])
    return jax.lax.map(one, up).reshape(-1, u.shape[1])[:t]


def layer_weights(src, words, l):
    """{leaf name under ``layers.l.``: value}, float32, made from ``words``
    (handed through a barrier so that a layer's weights are made when the
    layer is reached)."""
    s = copy.copy(src)
    s.words = words
    q = f"layers.{l}."
    return {n[len(q):]: s.get(n) for n, _, _ in src.specs if n.startswith(q)}


def _under(w, prefix):
    return {n[len(prefix):]: a for n, a in w.items() if n.startswith(prefix)}


def alpha(m):
    return m["scale_depth"] / math.sqrt(published_depth(m))


def _bind(fn, m, w, **kw):
    return lambda x, mm: fn(m, w, x, mm, **kw)


def hidden_states(m, src, ids, mm=mm_f32):
    """ids [K, T] int32 -> final-normed, head-scaled hidden states [K, T,
    hid] float32. One layer's weights live at a time; the sequences go one
    at a time."""
    eps, a = m["rms_norm_eps"], alpha(m)
    x = jnp.take(src.raw("embed_tokens.weight"), ids, axis=0).astype(
        jnp.float32) * m["scale_emb"]
    words = src.words
    for l, kind in enumerate(kinds(m)):
        words, x = jax.lax.optimization_barrier((words, x))
        w = layer_weights(src, words, l)
        wm = _under(w, "mlp.")
        if kind == "minicpm4":
            mix = _bind(sparse_attention, m, _under(w, "self_attn."))
        else:
            mix = _bind(lightning, m, _under(w, "lightning_attn."),
                        sl=slopes(m, l))

        def per_seq(xs, mix=mix, w=w, wm=wm):
            xs = xs + a * mix(rms_norm(xs, w["input_layernorm.weight"], eps),
                              mm)
            return xs + a * swiglu(
                m, wm, rms_norm(xs, w["post_attention_layernorm.weight"],
                                eps), mm)

        x = jax.lax.map(per_seq, x)
    return rms_norm(x, src.get("norm.weight"), eps) \
        / (m["hidden_size"] / m["dim_model_base"])


def next_token_rows(m, src, ids, pos, tok, mm=mm_f32):
    """For each sequence k and row r: the logits that predict the token
    after position pos[k, r]. Returns (best logit, logit of tok[k, r],
    argmax) — each [K, R]."""
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
