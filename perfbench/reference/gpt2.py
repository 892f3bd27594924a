"""Plain reference: GPT-2 (Radford et al. 2019; HF ``GPT2LMHeadModel``) with
its next-token loss, gradients and the AdamW step, in straightforward
``jax.numpy`` float32. It imports nothing of the program.

Block: x += Wp·attn(split(Wqkv·ln1(x) + b)); x += W2·gelu_tanh(W1·ln2(x)).
Learned positions, LayerNorm eps 1e-5, the head is the token embedding
transposed, loss = mean CE of logits[:, :-1] against ids[:, 1:]. Weights are
[in, out] (HF Conv1D). No dropout: the configuration trains without (its
``assumed`` group says why).

Optimizer as the configuration states it: gradients clipped to a global
norm, then AdamW with decoupled weight decay on every leaf and bias
correction, eps outside the root.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LAYER_PATTERN = r"^gpt2\.h\.(\d+)\.(.+)$"
# leaves that hold several of the published model's tensors side by side:
# (name pattern, axis, parts). c_attn is q | k | v; the key's bias has no
# gradient under softmax, the query's and the value's have.
COMPARE_SPLITS = [(r"attn\.c_attn\.(weight|bias)$", -1, 3)]


def param_specs(m):
    hid, inter, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    out = [("gpt2.wte.weight", (V, hid), 0.0),
           ("gpt2.wpe.weight", (m["max_position_embeddings"], hid), 0.0)]
    for l in range(m["num_hidden_layers"]):
        p = f"gpt2.h.{l}."
        out += [
            (p + "ln_1.weight", (hid,), 1.0), (p + "ln_1.bias", (hid,), 0.0),
            (p + "attn.c_attn.weight", (hid, 3 * hid), 0.0),
            (p + "attn.c_attn.bias", (3 * hid,), 0.0),
            (p + "attn.c_proj.weight", (hid, hid), 0.0),
            (p + "attn.c_proj.bias", (hid,), 0.0),
            (p + "ln_2.weight", (hid,), 1.0), (p + "ln_2.bias", (hid,), 0.0),
            (p + "mlp.c_fc.weight", (hid, inter), 0.0),
            (p + "mlp.c_fc.bias", (inter,), 0.0),
            (p + "mlp.c_proj.weight", (inter, hid), 0.0),
            (p + "mlp.c_proj.bias", (hid,), 0.0),
        ]
    out += [("gpt2.ln_f.weight", (hid,), 1.0), ("gpt2.ln_f.bias", (hid,), 0.0)]
    return out


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HI)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(m, w, p, x, mm):
    b, s, hid = x.shape
    h = m["num_attention_heads"]
    d = hid // h
    eps = m["layer_norm_epsilon"]
    a = layer_norm(x, w[p + "ln_1.weight"], w[p + "ln_1.bias"], eps)
    qkv = mm(a, w[p + "attn.c_attn.weight"]) + w[p + "attn.c_attn.bias"]
    q, k, v = [t.reshape(b, s, h, d) for t in jnp.split(qkv, 3, axis=-1)]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(d))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(b, s, hid)
    x = x + mm(o, w[p + "attn.c_proj.weight"]) + w[p + "attn.c_proj.bias"]
    a = layer_norm(x, w[p + "ln_2.weight"], w[p + "ln_2.bias"], eps)
    f = gelu_tanh(mm(a, w[p + "mlp.c_fc.weight"]) + w[p + "mlp.c_fc.bias"])
    return x + mm(f, w[p + "mlp.c_proj.weight"]) + w[p + "mlp.c_proj.bias"]


def loss_fn(m, w, ids, mm=mm_f32):
    """ids [B, S] -> mean next-token cross entropy (float32)."""
    s = ids.shape[1]
    x = jnp.take(w["gpt2.wte.weight"], ids, axis=0) \
        + w["gpt2.wpe.weight"][None, :s]
    for l in range(m["num_hidden_layers"]):
        x = block(m, w, f"gpt2.h.{l}.", x, mm)
    x = layer_norm(x, w["gpt2.ln_f.weight"], w["gpt2.ln_f.bias"],
                   m["layer_norm_epsilon"])
    logits = mm(x[:, :-1], w["gpt2.wte.weight"].T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - hit)


def clip_by_global_norm(grads, clip):
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = clip / jnp.maximum(gn, clip)
    return {k: g * scale for k, g in grads.items()}


def adamw_step(o, w, mom, var, grads, t):
    """One AdamW update (t = 1, 2, ...). ``o``: the optimizer group of the
    configuration file. Returns (w, mom, var)."""
    b1, b2, eps = o["beta1"], o["beta2"], o["epsilon"]
    lr, wd = o["learning_rate"], o["weight_decay"]
    nw, nm, nv = {}, {}, {}
    for k in w:
        g = grads[k]
        nm[k] = b1 * mom[k] + (1 - b1) * g
        nv[k] = b2 * var[k] + (1 - b2) * g * g
        mh = nm[k] / (1 - b1 ** t)
        vh = nv[k] / (1 - b2 ** t)
        nw[k] = w[k] * (1.0 - lr * wd) - lr * mh / (jnp.sqrt(vh) + eps)
    return nw, nm, nv


def one_step(m, o, w, mom, var, ids, t, mm=mm_f32):
    """Loss, the clipped gradient, and the updated state. The batch mean is
    taken a row at a time (the loss is the mean of the rows' own means, all
    rows being equally long), so one row's activations live at a time."""
    def row(r):
        return jax.value_and_grad(lambda ww: loss_fn(m, ww, r[None], mm))(w)

    losses, grads = jax.lax.map(row, ids)
    loss = jnp.mean(losses)
    grads = {k: jnp.mean(g, axis=0) for k, g in grads.items()}
    grads = clip_by_global_norm(grads, o["grad_clip_global_norm"])
    w, mom, var = adamw_step(o, w, mom, var, grads, t)
    return loss, grads, w, mom, var


def train_steps(m, o, w0, batches, norms, mm=mm_f32, batch_fault=None):
    """Follow the first ``len(batches)`` steps from ``w0``: the losses,
    ``norms`` of each step's clipped gradient, and the final weights. One step
    is one jitted program, called once per batch. ``norms`` maps a dict of
    leaves to whatever summary the caller compares. ``batch_fault`` (tests
    and fault readings only) maps a batch to the one a faulty step trains
    on."""
    def fn(w, mom, var, ids, t):
        loss, grads, w, mom, var = one_step(m, o, w, mom, var, ids, t, mm)
        return loss, norms(grads), w, mom, var

    step = jax.jit(fn)
    w = w0
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    var = {k: jnp.zeros_like(v) for k, v in w.items()}
    losses, gnorms = [], []
    for t, ids in enumerate(batches, start=1):
        if batch_fault is not None:
            ids = batch_fault(ids)
        loss, gnorm, w, mom, var = step(w, mom, var, jnp.asarray(ids),
                                        jnp.float32(t))
        losses.append(loss)
        gnorms.append(gnorm)
    return jnp.stack(losses), jnp.stack(gnorms), w
