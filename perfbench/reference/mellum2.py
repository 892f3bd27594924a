"""Plain reference: Mellum 2 (HF ``model_type`` ``mellum``) with its
next-token loss, the router's load-balancing loss, gradients and the AdamW
step, in straightforward ``jax.numpy`` float32 at ``Precision.HIGHEST``. It
imports nothing of the program.

Per layer l (pre-norm, RMSNorm ``x / sqrt(mean(x^2) + eps) * w``):

    h = x + W_o attn_l(rope_l(W_q n1(x)), rope_l(W_k n1(x)), W_v n1(x))
    y = h + sum over the top k of softmax(n2(h) W_r):  p_e SwiGLU_e(n2(h))

- ``attn``: GQA (a KV head serves H / KVH query heads), causal; a
  ``sliding_attention`` layer masks keys at or before ``q - window`` too.
  Both masks are written out over every key.
- ``rope_l``: rotate-half over the whole head at positions 0..S-1 with the
  layer kind's ``rope_parameters`` group: default ``theta^(-2i/d)``, or
  YaRN written from its formula (:func:`yarn_inv_freq`), cos and sin times
  the group's ``attention_factor``.
- Router over ``router_num_experts``; the top ``num_experts_per_tok``
  probabilities renormalised; the sum runs over the experts HELD here
  (``first_held_expert`` .. + ``num_experts``), an explicit loop over them,
  each computed on every token and weighed by its probability where the
  token chose it (0 elsewhere): the pairs of absent experts add nothing.
- Loss: mean CE of ``h_S W_head^T`` at positions :-1 against ids 1:,
  plus ``router_aux_loss_coef * E * sum_e f_e P_e`` over all layers' rows
  (``f_e`` the rows choosing e over rows, ``P_e`` its mean probability:
  the Qwen-MoE form).

Departures, to fit one chip at 2 x 8,192 tokens: each decoder layer, each
query block of attention (128 queries against every key), each held
expert and each block of 1,024 rows of the loss is recomputed in the
backward (``jax.checkpoint``); the Adam moments live on the host (numpy
float32) between steps and the update is made there, leaf by leaf. None of
it changes a number.

Optimizer as the configuration states it: gradients clipped to a global
norm, then AdamW with decoupled weight decay on every leaf and bias
correction, eps outside the root.

``fault`` (controls only; ``perfbench/tools/control_mellum2.py``):
``"window_all_keys"`` (window layers attend every earlier key),
``"drop_expert"`` (the last held expert left out), ``"default_rope"``
(the full layers rotate with the default embedding of their theta). Each
is data of the one compiled step (:func:`layer_consts`), so the clean
step and the three faults share a program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LAYER_PATTERN = r"^layers\.(\d+)\.(.+)$"
#: every expert leaf [16, ...] compared expert by expert (the cell holds
#: 16): the gradient and the update of each held expert is a piece of its
#: own, so one expert's share of a step cannot hide in its leaf's norm
HELD = 16
COMPARE_SPLITS = [(r"mlp\.experts\.(gate|up|down)_proj$", 0, HELD)]
WINDOW, FULL = "sliding_attention", "full_attention"
Q_BLOCK = 128
LOSS_BLOCK = 1024


def param_specs(m):
    hid, V = m["hidden_size"], m["vocab_size"]
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    n, inter = m["num_experts"], m["moe_intermediate_size"]
    out = [("embed_tokens.weight", (V, hid), 0.0)]
    for l in range(m["num_hidden_layers"]):
        p = f"layers.{l}."
        out += [
            (p + "input_layernorm.weight", (hid,), 1.0),
            (p + "self_attn.q_proj.weight", (hid, h * d), 0.0),
            (p + "self_attn.k_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.v_proj.weight", (hid, kvh * d), 0.0),
            (p + "self_attn.o_proj.weight", (h * d, hid), 0.0),
            (p + "post_attention_layernorm.weight", (hid,), 1.0),
            (p + "mlp.gate.weight", (hid, m["router_num_experts"]), 0.0),
            (p + "mlp.experts.gate_proj", (n, hid, inter), 0.0),
            (p + "mlp.experts.up_proj", (n, hid, inter), 0.0),
            (p + "mlp.experts.down_proj", (n, inter, hid), 0.0),
        ]
    out += [("norm.weight", (hid,), 1.0), ("lm_head.weight", (V, hid), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(d, theta, factor, orig, beta_fast, beta_slow):
    """YaRN (Peng et al. 2023, HF ``_compute_yarn_parameters``): dims that
    turn more than ``beta_fast`` times over the original context keep their
    frequency, those under ``beta_slow`` are divided by ``factor``, a linear
    ramp between (the range floored and ceiled)."""
    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(beta_fast)), 0)
    hi = min(math.ceil(dim_of(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        base = theta ** (-2.0 * i / d)
        ramp = min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append(base * ramp / factor + base * (1.0 - ramp))
    return np.asarray(out, np.float32)


def rope_of(m, kind, fault=None):
    """(inv_freq, cos/sin factor) of a layer kind."""
    g = dict(m["rope_parameters"][kind])
    d = m["head_dim"]
    if fault == "default_rope" and kind == FULL:
        g["rope_type"] = "default"
    if g.get("rope_type", "default") == "default":
        inv = np.asarray([g["rope_theta"] ** (-2.0 * i / d)
                          for i in range(d // 2)], np.float32)
        return inv, 1.0
    f = g["factor"]
    att = g.get("attention_factor") or 0.1 * math.log(f) + 1.0
    return yarn_inv_freq(d, g["rope_theta"], f,
                         g["original_max_position_embeddings"],
                         g.get("beta_fast") or 32, g.get("beta_slow") or 1), \
        att


def layer_consts(m, seq, fault=None):
    """Per layer what its kind fixes, as arrays (so one compiled program
    serves the clean step and the faults): the keys a query sees (the
    window, or ``seq``: every earlier key), the rotary frequencies and
    cos/sin factor, and a 0/1 weight per held expert."""
    out = []
    for kind in m["layer_types"][:m["num_hidden_layers"]]:
        inv, att = rope_of(m, kind, fault)
        window = m["sliding_window"] if kind == WINDOW \
            and fault != "window_all_keys" else seq
        keep = np.ones(m["num_experts"], np.float32)
        if fault == "drop_expert":
            keep[-1] = 0.0
        out.append({"window": np.int32(window), "inv": inv,
                    "att": np.float32(att), "keep": keep})
    return out


def rope(x, inv, att):
    """x [B, S, H, D] at positions 0..S-1, rotate-half."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = (jnp.cos(ang) * att)[None, :, None, :]
    sin = (jnp.sin(ang) * att)[None, :, None, :]
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q [B, S, H, D], k/v [B, S, KVH, D]; a query sees the ``window``
    newest keys (``window`` >= S: every earlier key)."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    k_pos = jnp.arange(s)[None, :]
    nb = -(-s // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - s), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, 1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) \
            / jnp.sqrt(jnp.float32(d))
        q_pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
        sc = jnp.where(mask[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(nb))          # [nb, B, QB, H, D]
    out = jnp.moveaxis(out, 0, 1).reshape(b, nb * Q_BLOCK, h, d)
    return out[:, :s]


def router(m, x, wr):
    """x [N, hid] -> (probs [N, E], chosen ids [N, k], weights [N, k])."""
    logits = jnp.matmul(x, wr, precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    return probs, idx, vals


def experts(m, lw, x, idx, vals, keep, mm):
    """The held experts' part: a loop over them, each over every row,
    times its 0/1 ``keep``."""
    wg, wu, wd = (lw["mlp.experts.gate_proj"], lw["mlp.experts.up_proj"],
                  lw["mlp.experts.down_proj"])

    @jax.checkpoint
    def one(out, e):
        wsel = jnp.sum(jnp.where(idx == m["first_held_expert"] + e, vals,
                                 0.0), -1) * keep[e]
        a = jax.nn.silu(mm(x, wg[e])) * mm(x, wu[e])
        return out + wsel[:, None] * mm(a, wd[e]), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(m["num_experts"]))
    return out


def layer(m, lw, c, x, mm):
    """One decoder layer: (x', rows choosing each expert, summed probs)."""
    b, s, hid = x.shape
    h, kvh, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]
    a = rms_norm(x, lw["input_layernorm.weight"], eps)
    q = mm(a, lw["self_attn.q_proj.weight"]).reshape(b, s, h, d)
    k = mm(a, lw["self_attn.k_proj.weight"]).reshape(b, s, kvh, d)
    v = mm(a, lw["self_attn.v_proj.weight"]).reshape(b, s, kvh, d)
    q, k = rope(q, c["inv"], c["att"]), rope(k, c["inv"], c["att"])
    o = attention(q, k, v, c["window"]).reshape(b, s, h * d)
    x = x + mm(o, lw["self_attn.o_proj.weight"])
    u = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    flat = u.reshape(b * s, hid)
    probs, idx, vals = router(m, flat, lw["mlp.gate.weight"])
    y = experts(m, lw, flat, idx, vals, c["keep"], mm)
    chose = jnp.sum(jax.nn.one_hot(idx, m["router_num_experts"],
                                   dtype=jnp.float32), (0, 1))
    return x + y.reshape(b, s, hid), chose, jnp.sum(probs, 0)


def layer_weights(w, l):
    p = f"layers.{l}."
    return {n[len(p):]: a for n, a in w.items() if n.startswith(p)}


def loss_fn(m, w, ids, mm=mm_f32, fault=None, consts=None):
    """ids [B, S] -> the training loss (float32): mean next-token CE plus
    the router's load-balancing term. ``consts``: :func:`layer_consts`
    (made here from ``fault`` if not given)."""
    b, s = ids.shape
    if consts is None:
        consts = layer_consts(m, s, fault)
    x = jnp.take(w["embed_tokens.weight"], ids, axis=0)
    chose = probs = 0.0
    for l in range(m["num_hidden_layers"]):
        x, ch, pr = jax.checkpoint(
            lambda lw, c, x: layer(m, lw, c, x, mm))(
                layer_weights(w, l), consts[l], x)
        chose, probs = chose + ch, probs + pr
    x = rms_norm(x, w["norm.weight"], m["rms_norm_eps"])
    hs = x[:, :-1].reshape(-1, x.shape[-1])
    tgt = ids[:, 1:].reshape(-1)
    n = hs.shape[0]
    nb = -(-n // LOSS_BLOCK)
    hs = jnp.pad(hs, ((0, nb * LOSS_BLOCK - n), (0, 0)))
    tgt = jnp.pad(tgt, (0, nb * LOSS_BLOCK - n))

    @jax.checkpoint
    def ce(i):
        hb = jax.lax.dynamic_slice_in_dim(hs, i * LOSS_BLOCK, LOSS_BLOCK)
        tb = jax.lax.dynamic_slice_in_dim(tgt, i * LOSS_BLOCK, LOSS_BLOCK)
        logits = mm(hb, w["lm_head.weight"].T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        live = i * LOSS_BLOCK + jnp.arange(LOSS_BLOCK) < n
        return jnp.sum(jnp.where(live, lse - hit, 0.0))

    ce_mean = jnp.sum(jax.lax.map(ce, jnp.arange(nb))) / n
    rows = b * s * m["num_hidden_layers"]
    aux = m["router_num_experts"] * jnp.sum((chose / rows) * (probs / rows))
    return ce_mean + m["router_aux_loss_coef"] * aux


def clip_by_global_norm(grads, clip):
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = clip / jnp.maximum(gn, clip)
    return {k: g * scale for k, g in grads.items()}


def train_steps(m, o, w0, batches, norms, mm=mm_f32, batch_fault=None,
                fault=None):
    """Follow the first ``len(batches)`` steps from ``w0``: the losses,
    ``norms`` of each step's clipped gradient, and the final weights (on
    the device). The gradient is one jitted program, called once per
    batch; the AdamW update runs on the host."""
    def grad_step(w, ids, consts):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda ww: loss_fn(m, ww, ids, mm, consts=consts))(w)
        g = clip_by_global_norm(g, o["grad_clip_global_norm"])
        return loss, g, norms(g)

    step = jax.jit(grad_step)
    b1, b2, eps = o["beta1"], o["beta2"], o["epsilon"]
    lr, wd = o["learning_rate"], o["weight_decay"]
    w, mom, var = dict(w0), {}, {}
    losses, gnorms = [], []
    for t, ids in enumerate(batches, start=1):
        if batch_fault is not None:
            ids = batch_fault(ids)
        ids = jnp.asarray(ids)
        loss, g, gn = step(w, ids, layer_consts(m, ids.shape[1], fault))
        losses.append(float(loss))
        gnorms.append(np.asarray(gn))
        for k in list(g):
            gk = np.asarray(g.pop(k), np.float32)
            mk = b1 * mom.get(k, 0.0) + (1 - b1) * gk
            vk = b2 * var.get(k, 0.0) + (1 - b2) * gk * gk
            mom[k], var[k] = mk.astype(np.float32), vk.astype(np.float32)
            wk = np.asarray(w[k], np.float32)
            new = wk * np.float32(1.0 - lr * wd) - np.float32(lr) * (
                mk / np.float32(1 - b1 ** t)) / (
                np.sqrt(vk / np.float32(1 - b2 ** t)) + np.float32(eps))
            w[k] = jnp.asarray(new.astype(np.float32))
    return jnp.asarray(losses), jnp.stack([jnp.asarray(x) for x in gnorms]), w
