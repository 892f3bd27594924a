"""Mellum 2 family (HF ``model_type`` ``mellum``; JetBrains
Mellum2-12B-A2.5B): a pre-norm decoder whose attention layers are of two
kinds, chosen layer by layer from ``layer_types`` — ``sliding_attention``
(a query sees its ``sliding_window`` newest keys, itself among them; the
default rotary embedding) and ``full_attention`` (causal over everything;
YaRN rotary embedding) —, each with its own ``rope_parameters`` group. GQA
without bias and without q/k norms. Every MLP is sparse
(``mlp_layer_types``): a softmax router over ``num_experts``, the top
``num_experts_per_tok`` renormalised (``norm_topk_prob``), SwiGLU experts,
no shared expert. RMSNorm, untied head.

What training asks of the program, and how the model says it:

- ``forward(input_ids, labels)`` returns ``(None, loss)``: the mean
  next-token cross entropy through ``ops.fused_ce.lm_head_loss`` (no
  ``[N, V]`` logits), plus ``router_aux_loss_coef`` x the router's
  load-balancing loss over all layers' tokens (the Qwen-MoE form:
  ``E * sum_e f_e P_e``, ``f_e`` the share of (token, layer) rows that
  chose expert e, ``P_e`` its mean probability).
- Window layers run ``scaled_dot_product_attention(window=...)``: on the
  TPU the flash kernel walks only the blocks in the window.
- The sparse block is told which experts it HOLDS (``first_held_expert``,
  ``num_experts_held``): it routes over all ``num_experts`` and computes
  its own experts' part through ``ops.moe.moe_experts_held``, forward and
  backward.
- ``recompute``: each decoder layer under ``incubate.recompute``'s
  checkpoint (the held-share layer's dropless capacity is T x k rows a
  layer, too many to keep for the backward at 8k tokens a sequence).
- What a training step's expert layers did is summed ON THE DEVICE into
  the buffer ``moe_train_stats`` ([token-layer passes, local pairs,
  busiest held expert's pairs]), which the compiled step reassigns;
  ``ops.moe.book_train_stats`` moves it to the process registry once,
  when asked.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, apply
from ..jit.layer_program import LayerPrograms
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import manipulation as M
from ..ops import moe as moe_ops
from ..ops.pallas.rope import rope_inv_freq
from ._leaves import _Base, _Weight

__all__ = ["MellumConfig", "MellumForCausalLM"]

WINDOW, FULL = "sliding_attention", "full_attention"
SPARSE = "sparse"


def _yarn_full():
    return {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
            "original_max_position_embeddings": 8192, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.2772588722239782}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: per layer ``sliding_attention`` / ``full_attention``; None = the
    #: published pattern, three window layers then a full one
    layer_types: tuple | None = None
    #: per layer ``sparse``; None = every layer
    mlp_layer_types: tuple | None = None
    sliding_window: int = 1024
    #: one group per attention kind
    rope_parameters: dict = field(default_factory=lambda: {
        FULL: _yarn_full(),
        WINDOW: {"rope_type": "default", "rope_theta": 500000.0}})
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    num_experts: int = 64                     # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    #: the share of the routed experts this instance holds:
    #: [first_held_expert, first_held_expert + num_experts_held);
    #: None = all of them
    num_experts_held: int | None = None
    first_held_expert: int = 0
    #: each decoder layer recomputed in the backward (training)
    recompute: bool = False
    dtype: str = "float32"
    empty_init: bool = False

    @classmethod
    def mellum2_12b_a2_5b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, sliding_window=8, num_experts=16,
                   num_experts_per_tok=4, moe_intermediate_size=32,
                   rope_parameters={
                       FULL: dict(_yarn_full(), factor=4.0,
                                  original_max_position_embeddings=16,
                                  rope_theta=10000.0),
                       WINDOW: {"rope_type": "default",
                                "rope_theta": 10000.0}})

    @property
    def attention_kinds(self):
        n = self.num_hidden_layers
        kinds = tuple(self.layer_types)[:n] if self.layer_types is not None \
            else tuple(FULL if l % 4 == 3 else WINDOW for l in range(n))
        if len(kinds) != n or set(kinds) - {WINDOW, FULL}:
            raise ValueError(f"layer_types {kinds!r} does not give {n} "
                             f"layers of {(WINDOW, FULL)}")
        return kinds

    def check_mlp_kinds(self):
        kinds = tuple(self.mlp_layer_types or (SPARSE,)
                      * self.num_hidden_layers)[:self.num_hidden_layers]
        if set(kinds) != {SPARSE}:
            raise ValueError(f"mlp_layer_types {kinds!r}: only sparse "
                             f"layers are built (the family's published "
                             f"pattern has no dense one)")

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return moe_ops.held_range(self.first_held_expert,
                                  self.num_experts_held, self.num_experts)


def _rope(q, k, inv, scale):
    """The rotary embedding (rotate-half) at positions 0..S-1 of q [B, S, H,
    D] and k [B, S, KVH, D], in float32, cos and sin times ``scale``."""
    d2 = q.shape[-1] // 2
    ang = jnp.arange(q.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]

    def rot(a):
        af = a.astype(jnp.float32)
        a1, a2 = af[..., :d2], af[..., d2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                               axis=-1).astype(a.dtype)

    return rot(q), rot(k)


class _Norm(_Weight):
    def __init__(self, cfg, n):
        super().__init__(cfg, n, init=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.cfg.rms_norm_eps)


class MellumAttention(_Base):
    """GQA; a window layer sees ``sliding_window`` keys. The rotary
    embedding is its kind's ``rope_parameters`` group."""

    def __init__(self, cfg, kind):
        super().__init__(cfg)
        self.window = int(cfg.sliding_window) if kind == WINDOW else None
        h, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        self.q_proj = _Weight(cfg, cfg.hidden_size, h * d)
        self.k_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.v_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.o_proj = _Weight(cfg, h * d, cfg.hidden_size)
        inv, scale = rope_inv_freq(d, cfg.rope_parameters[kind])
        self._rope = functools.partial(_rope, inv=inv, scale=scale)

    def forward(self, x):
        b, s, _ = x.shape
        c = self.cfg
        h, kvh, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = M.reshape(F.linear(x, self.q_proj.weight), [b, s, h, d])
        k = M.reshape(F.linear(x, self.k_proj.weight), [b, s, kvh, d])
        v = M.reshape(F.linear(x, self.v_proj.weight), [b, s, kvh, d])
        q, k = apply(self._rope, q, k, n_outputs=2, name="rope")
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             window=self.window)
        return F.linear(M.reshape(ctx, [b, s, h * d]), self.o_proj.weight)


class _Router(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.weight = self._p((cfg.hidden_size, cfg.num_experts))


class _Experts(_Base):
    """The held experts' banks: ``gate_proj``, ``up_proj`` [E_held, hid,
    inter], ``down_proj`` [E_held, inter, hid]."""

    def __init__(self, cfg):
        super().__init__(cfg)
        n, hid, inter = cfg.held[1], cfg.hidden_size, \
            cfg.moe_intermediate_size
        self.gate_proj = self._p((n, hid, inter))
        self.up_proj = self._p((n, hid, inter))
        self.down_proj = self._p((n, inter, hid))


@functools.lru_cache(maxsize=None)
def _moe_fn(first, k, norm, compute_dtype):
    """The sparse block, one function for every layer: routing in float32
    over all experts, the held experts' part in ``compute_dtype``. Returns
    (out, stats [tokens, local pairs, busiest held expert's pairs],
    rows per expert [E] that chose it, summed router probabilities [E]);
    the last two make the load-balancing loss."""

    def fn(u, wr, wg, w1, w2):
        shp = u.shape
        flat = u.reshape(-1, shp[-1])
        # routing is a discrete choice: the logits at full float32
        logits = jnp.matmul(flat.astype(jnp.float32), wr.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, w = moe_ops.softmax_top_k_router(logits, k, norm)
        cdt = jnp.dtype(compute_dtype)
        out, st = moe_ops.moe_experts_held(
            flat.astype(cdt), idx, w, w1.astype(cdt), w2.astype(cdt), first,
            w_gate=wg.astype(cdt))
        n_e = wr.shape[1]
        chose = jnp.sum(jax.nn.one_hot(idx, n_e, dtype=jnp.float32), (0, 1))
        probs = jnp.sum(jax.nn.softmax(logits, axis=-1), axis=0)
        stats = jnp.concatenate([jnp.asarray([flat.shape[0]], jnp.float32),
                                 st.astype(jnp.float32)])
        return out.reshape(shp).astype(u.dtype), stats, chose, probs

    return fn


class MellumSparseMoe(_Base):
    """``p = softmax(u W_r)`` in float32 over ALL routed experts; the top
    k, renormalised; ``out = sum_k p_k E_k(u)``, every expert a SwiGLU.
    Only the held experts' pairs are computed here."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.gate = _Router(cfg)
        self.experts = _Experts(cfg)

    def forward(self, u):
        from ..amp.auto_cast import amp_state
        c = self.cfg
        amp = amp_state()
        cdt = jnp.dtype(amp.dtype) if amp.enabled else u._data.dtype
        fn = _moe_fn(c.held[0], c.num_experts_per_tok, c.norm_topk_prob,
                     jnp.dtype(cdt).name)
        e = self.experts
        return apply(fn, u, self.gate.weight, e.gate_proj, e.up_proj,
                     e.down_proj, n_outputs=4, name="mellum_sparse_moe")


class MellumDecoderLayer(_Base):
    """``h = x + Attn(RMS(x))``, ``y = h + MoE(RMS(h))``. Returns (y, and
    the sparse block's three statistics)."""

    def __init__(self, cfg, attn_kind):
        super().__init__(cfg)
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = MellumAttention(cfg, attn_kind)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = MellumSparseMoe(cfg)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        y, stats, chose, probs = self.mlp(self.post_attention_layernorm(x))
        return x + y, stats, chose, probs


class MellumForCausalLM(_Base):
    def __init__(self, config: MellumConfig):
        super().__init__(config)
        self.config = config
        cfg = config
        cfg.check_mlp_kinds()
        self.embed_tokens = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([MellumDecoderLayer(cfg, a)
                                    for a in cfg.attention_kinds])
        self.norm = _Norm(cfg, cfg.hidden_size)
        # [V, hid] as an HF Linear stores it: the loss tail's vocab-first
        # layout
        self.lm_head = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        # one compiled program an attention kind (jit.LayerPrograms)
        self._programs = LayerPrograms()
        self.register_buffer("moe_train_stats",
                             Tensor(jnp.zeros((3,), jnp.int32)))
        moe_ops.track_train_stats(self.moe_train_stats)

    def forward(self, input_ids, labels=None):
        """Logits [B, S, V]; with ``labels``, ``(None, loss)``."""
        cfg = self.config
        x = F.embedding(input_ids, self.embed_tokens.weight)
        stats = chose = probs = None
        remat = cfg.recompute and self.training
        for layer in self.layers:
            x, st, ch, pr = self._programs(
                layer, layer.self_attn.window, x, remat=remat, n_outputs=4,
                name="mellum_decoder_layer")
            stats = st if stats is None else stats + st
            chose = ch if chose is None else chose + ch
            probs = pr if probs is None else probs + pr
        h = self.norm(x)
        if labels is None:
            return F.linear(h, M.transpose(self.lm_head.weight, [1, 0]))
        from ..ops.fused_ce import lm_head_loss
        loss = lm_head_loss(h, self.lm_head.weight, labels, vocab_first=True)
        if self.training:
            self.moe_train_stats.set_data(apply(
                lambda a, b: a + jax.lax.stop_gradient(b).astype(a.dtype),
                self.moe_train_stats, stats, name="mellum_moe_counters",
                differentiable=False)._data)

        def aux(ch, pr, st):
            rows = st[0]            # token-layer passes
            return cfg.num_experts * jnp.sum((ch / rows) * (pr / rows))

        return None, loss + cfg.router_aux_loss_coef * apply(
            aux, chose, probs, stats, name="mellum_router_aux")
