"""EXAONE-MoE family (HF ``model_type`` ``exaone_moe``; K-EXAONE-236B-A23B):
a pre-norm decoder whose attention layers are of TWO kinds, chosen layer by
layer from ``layer_types`` — ``sliding_attention`` (a query sees its
``sliding_window`` newest keys, itself among them; rotary embedding) and
``full_attention`` (causal over everything; NO rotary) — and whose MLPs are
chosen from ``mlp_layer_types``: ``dense`` SwiGLU, or ``sparse``: a sigmoid
router with a selection bias over ``num_experts`` SwiGLU experts, top
``num_experts_per_tok`` of them, beside one shared expert. q and k pass a
per-head RMS norm before the rotation. No bias anywhere; untied head.

What the family asks of the serving engine, and how the model says it:

- :meth:`ExaoneMoeForCausalLM.cache_spec` declares ``WindowKV`` for a
  window layer and ``PagedKV`` for a global one: the engine gives the
  first a per-slot ring sized by the window, the second host-managed pages
  sized by ``max_len``, and hands the model both tables.
- The sparse block is told which experts it HOLDS (``first_held_expert``,
  ``num_experts_held``): it routes over all ``num_experts`` and computes
  its own experts' part (``ops.moe.moe_experts_held`` with a gate matrix).
  The shared expert is whole on every holder.
- Parameters are built in ``config.dtype``; with ``config.empty_init``
  without storage (``models/nemotron_h.py`` says why).
- ``num_nextn_predict_layers`` (a multi-token-prediction drafter) is
  accepted and must be 0: this model has no drafter.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import apply
from ..generation import GenerationMixin
from ..inference.cache_spec import PagedKV, StepCounters, WindowKV
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.attention import sdpa_reference
from ..ops import creation
from ..ops import manipulation as M
from ..ops import moe as moe_ops
from ._leaves import _Base, _Weight
from .llama import _hidden_at, _paged_attention_step

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM"]

WINDOW, GLOBAL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"

#: what a pass through the model counts (``StepCounters``): the expert
#: layer's three, declared where the layer's function lives
COUNTERS = moe_ops.HELD_COUNTERS


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432            # the dense layers' MLP
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: per layer ``sliding_attention`` / ``full_attention``; None = the
    #: published pattern, three window layers then a global one
    layer_types: tuple | None = None
    #: per layer ``dense`` / ``sparse``; None = one leading dense layer
    mlp_layer_types: tuple | None = None
    sliding_window: int = 128
    rope_parameters: dict = field(default_factory=lambda: {
        "rope_theta": 1e6, "rope_type": "default"})
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144      # unread: no rotary table
    initializer_range: float = 0.02
    # sparse MLP
    num_experts: int = 128                     # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: the share of the routed experts this instance holds:
    #: [first_held_expert, first_held_expert + num_experts_held);
    #: None = all of them
    num_experts_held: int | None = None
    first_held_expert: int = 0
    num_nextn_predict_layers: int = 0
    #: parameters are BUILT in this dtype
    dtype: str = "float32"
    #: leaves without storage until real weights are loaded
    #: (``NemotronHConfig.empty_init``: for the one caller that cannot
    #: free the leaves it replaces)
    empty_init: bool = False

    @classmethod
    def k_exaone_236b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=128, hidden_size=64, intermediate_size=96,
                   num_hidden_layers=5, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, sliding_window=8,
                   num_experts=16, num_experts_per_tok=3,
                   moe_intermediate_size=32, max_position_embeddings=256)

    def _per_layer(self, given, default, allowed, what):
        kinds = tuple(given) if given is not None else tuple(
            default(l) for l in range(self.num_hidden_layers))
        kinds = kinds[:self.num_hidden_layers]
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(allowed):
            raise ValueError(f"{what} {kinds!r} does not give "
                             f"{self.num_hidden_layers} layers of {allowed}")
        return kinds

    @property
    def rope_theta(self):
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {self.rope_parameters!r}: only "
                             f"the default rotary embedding is built")
        return float(self.rope_parameters["rope_theta"])

    @property
    def attention_kinds(self):
        return self._per_layer(
            self.layer_types, lambda l: GLOBAL if l % 4 == 3 else WINDOW,
            (WINDOW, GLOBAL), "layer_types")

    @property
    def mlp_kinds(self):
        return self._per_layer(
            self.mlp_layer_types, lambda l: SPARSE if l else DENSE,
            (DENSE, SPARSE), "mlp_layer_types")

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return moe_ops.held_range(self.first_held_expert, self.num_experts_held,
                          self.num_experts)


class _Norm(_Weight):
    def __init__(self, cfg, n):
        super().__init__(cfg, n, init=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.cfg.rms_norm_eps)


def _qk_norm_rope(q, k, qw, kw, pos, eps, theta, rotary_dim=None,
                  centred=False):
    """q [B, S, H, D], k [B, S, KVH, D]: the per-head RMS norm (learned
    scale; ``centred``: the scale is ``1 + w``), then — ``theta`` not None
    — the rotary embedding over the first ``rotary_dim`` of the head's
    dims (None: the whole head) at positions ``pos + [0..S)``. The angles
    are computed from the positions at hand in float32: no table, so
    nothing grows with ``max_position_embeddings``."""

    def norm(a, w):
        af = a.astype(jnp.float32)
        ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
        return af * jax.lax.rsqrt(ms + eps) \
            * (w.astype(jnp.float32) + (1.0 if centred else 0.0))

    qf, kf = norm(q, qw), norm(k, kw)
    if theta is not None:
        rd = q.shape[-1] if rotary_dim is None else int(rotary_dim)
        d2 = rd // 2
        inv = jnp.exp(jnp.arange(d2, dtype=jnp.float32)
                      * (-jnp.log(jnp.float32(theta)) / d2))
        at = pos.astype(jnp.int32).reshape(-1, 1) \
            + jnp.arange(q.shape[1], dtype=jnp.int32)[None, :]
        ang = at.astype(jnp.float32)[..., None] * inv        # [B|1, S, D/2]
        sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]

        def rot(a):
            a1, a2 = a[..., :d2], a[..., d2:rd]
            # dims past ``rd`` pass through unrotated
            return jnp.concatenate(
                [a1 * cos - a2 * sin, a2 * cos + a1 * sin]
                + ([a[..., rd:]] if rd < a.shape[-1] else []), axis=-1)

        qf, kf = rot(qf), rot(kf)
    return qf.astype(q.dtype), kf.astype(k.dtype)


def _attend(q, k, v, pos, *cache, window):
    """Dense attention for the paths outside the engine: causal, through
    ``window`` newest keys if given. With ``cache`` (k, v of [B, max_len,
    KVH, D]) the new tokens are written at ``pos`` first and the caches
    come back too."""
    s = q.shape[1]
    p = pos.astype(jnp.int32).reshape(())
    if cache:
        zero = jnp.zeros((), jnp.int32)
        start = (zero, p, zero, zero)
        k = jax.lax.dynamic_update_slice(cache[0], k.astype(cache[0].dtype),
                                         start)
        v = jax.lax.dynamic_update_slice(cache[1], v.astype(cache[1].dtype),
                                         start)
    q_pos = p + jnp.arange(s)[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    out = sdpa_reference(q, k.astype(q.dtype), v.astype(q.dtype),
                         attn_mask=mask[None, None])
    return (out, k, v) if cache else out


class ExaoneMoeAttention(_Base):
    """GQA with a per-head RMS norm on q and k; a window layer rotates
    them and sees ``sliding_window`` keys, a global layer does neither."""

    def __init__(self, cfg, kind):
        super().__init__(cfg)
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.window = int(cfg.sliding_window) if kind == WINDOW else None
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        self.q_proj = _Weight(cfg, cfg.hidden_size, h * d)
        self.k_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.v_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.o_proj = _Weight(cfg, h * d, cfg.hidden_size)
        self.q_norm = _Weight(cfg, d, init=I.Constant(1.0))
        self.k_norm = _Weight(cfg, d, init=I.Constant(1.0))

    def _out(self, ctx):
        return F.linear(ctx, self.o_proj.weight)

    def forward(self, x, cache=None, pos=None, tables=None):
        b, s, _ = x.shape
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = M.reshape(F.linear(x, self.q_proj.weight), [b, s, h, d])
        k = M.reshape(F.linear(x, self.k_proj.weight), [b, s, kvh, d])
        v = M.reshape(F.linear(x, self.v_proj.weight), [b, s, kvh, d])
        if pos is None:
            pos = creation.zeros([1], dtype="int32")
        theta = float(self.cfg.rope_theta) if self.window else None
        q, k = apply(
            functools.partial(_qk_norm_rope, eps=self.cfg.rms_norm_eps,
                              theta=theta),
            q, k, self.q_norm.weight, self.k_norm.weight, pos,
            n_outputs=2, name="qk_norm_rope")
        if cache is not None and tables is not None:
            return _paged_attention_step(self, q, k, v, cache, pos, tables,
                                         rope=False, proj=self._out,
                                         window=self.window)
        fn = functools.partial(_attend, window=self.window)
        if cache is not None:
            ctx, kc, vc = apply(fn, q, k, v, pos, cache[0], cache[1],
                                n_outputs=3, name="sdpa_cached")
            return self._out(M.reshape(ctx, [b, s, h * d])), (kc, vc)
        ctx = apply(fn, q, k, v, pos, name="sdpa")
        return self._out(M.reshape(ctx, [b, s, h * d]))


def _swiglu(x, wg, wu, wd):
    a = jax.nn.silu(jnp.matmul(x, wg)) * jnp.matmul(x, wu)
    return jnp.matmul(a.astype(x.dtype), wd)


class _Mlp(_Base):
    """SwiGLU: ``W_down(silu(W_gate u) * W_up u)``."""

    def __init__(self, cfg, inter):
        super().__init__(cfg)
        self.gate_proj = _Weight(cfg, cfg.hidden_size, inter)
        self.up_proj = _Weight(cfg, cfg.hidden_size, inter)
        self.down_proj = _Weight(cfg, inter, cfg.hidden_size)

    def forward(self, u):
        return apply(_swiglu, u, self.gate_proj.weight, self.up_proj.weight,
                     self.down_proj.weight, name="swiglu_mlp")


class _Router(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.weight = self._p((cfg.hidden_size, cfg.num_experts))
        self.e_score_correction_bias = self._p(
            (cfg.num_experts,), I.Constant(0.0))


class _Experts(_Base):
    """The held experts' banks: ``gate_proj``, ``up_proj`` [E_held, hid,
    inter], ``down_proj`` [E_held, inter, hid]. Gate and up stay two
    matrices (``CHANGES.md``, PR 33, says what the fused form measured)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        n, hid, inter = cfg.held[1], cfg.hidden_size, \
            cfg.moe_intermediate_size
        self.gate_proj = self._p((n, hid, inter))
        self.up_proj = self._p((n, hid, inter))
        self.down_proj = self._p((n, inter, hid))


@functools.lru_cache(maxsize=None)
def _moe_fn(first, k, norm, scale, has_valid, shared):
    """The sparse block as ONE jitted function shared by its layers: the
    eager first call of a ``to_static`` step then runs it as a compiled
    program (``models/nemotron_h.py`` ``_mamba_fn`` says why)."""

    def fn(u, wr, bias, wg, w1, w2, sg, su, sd, *v):
        shp = u.shape
        flat = u.reshape(-1, shp[-1])
        ok = v[0].reshape(-1) if has_valid else None
        logits = jnp.matmul(flat.astype(jnp.float32),
                            wr.astype(jnp.float32))
        idx, w = moe_ops.sigmoid_top_k_router(logits, bias, k, norm, scale)
        out, st = moe_ops.moe_experts_held(flat, idx, w, w1, w2, first,
                                           valid=ok, w_gate=wg)
        if shared:
            out = out + _swiglu(flat, sg, su, sd)
        n_tok = jnp.sum(ok).astype(jnp.int32) if has_valid \
            else jnp.asarray(flat.shape[0], jnp.int32)
        return out.reshape(shp), jnp.concatenate([n_tok[None], st])

    return jax.jit(fn)


class ExaoneSparseMoe(_Base):
    """``s = sigmoid(u W_r)`` in float32 over ALL routed experts; the top
    k of ``s + b``; ``w = scale * s_sel / sum(s_sel)``; ``out = sum_k w_k
    E_k(u) + S(u)``, every expert a SwiGLU. Only the held experts' pairs
    are computed here."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.gate = _Router(cfg)
        self.experts = _Experts(cfg)
        self.shared_experts = _Mlp(
            cfg, cfg.moe_intermediate_size * cfg.num_shared_experts)

    def forward(self, u, valid=None, shared=True):
        """u [B, S, hid]; ``valid`` [B, S] bool or None. Returns (out,
        stats) with stats int32 [tokens, local pairs, busiest held
        expert's pairs]. ``shared=False`` leaves the shared expert out (a
        holder other than the one that counts it)."""
        c = self.cfg
        has_valid = valid is not None
        fn = _moe_fn(c.held[0], c.num_experts_per_tok, c.norm_topk_prob,
                     float(c.routed_scaling_factor), has_valid, shared)
        se = self.shared_experts
        args = [u, self.gate.weight, self.gate.e_score_correction_bias,
                self.experts.gate_proj, self.experts.up_proj,
                self.experts.down_proj, se.gate_proj.weight,
                se.up_proj.weight, se.down_proj.weight]
        if has_valid:
            args.append(valid)
        return apply(fn, *args, n_outputs=2, name="exaone_sparse_moe",
                     differentiable=False)


class ExaoneMoeDecoderLayer(_Base):
    """``h = x + Attn(RMS(x))``, ``y = h + FFN(RMS(h))``."""

    def __init__(self, cfg, attn_kind, mlp_kind):
        super().__init__(cfg)
        self.sparse = mlp_kind == SPARSE
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        self.self_attn = ExaoneMoeAttention(cfg, attn_kind)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = ExaoneSparseMoe(cfg) if self.sparse \
            else _Mlp(cfg, cfg.intermediate_size)


class ExaoneMoeForCausalLM(_Base, GenerationMixin):
    def __init__(self, config: ExaoneMoeConfig):
        super().__init__(config)
        self.config = config
        cfg = config
        if cfg.num_nextn_predict_layers:
            raise ValueError(
                "num_nextn_predict_layers must be 0: this model has no "
                "multi-token-prediction drafter")
        self.embed_tokens = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([
            ExaoneMoeDecoderLayer(cfg, a, m)
            for a, m in zip(cfg.attention_kinds, cfg.mlp_kinds)])
        self.norm = _Norm(cfg, cfg.hidden_size)
        self.lm_head = _Weight(cfg, cfg.hidden_size, cfg.vocab_size)

    # ---- caches ----------------------------------------------------------

    def cache_spec(self):
        """One entry per attention layer by its kind, then the counters."""
        cfg = self.config
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        return [WindowKV(kvh, d, int(cfg.sliding_window)) if kind == WINDOW
                else PagedKV(kvh, d) for kind in cfg.attention_kinds] \
            + [StepCounters(COUNTERS)]

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        """Contiguous caches for ``generate``: (k, v) of [B, max_len, KV,
        D] per layer, whole-length for a window layer too (the engine's
        rings are what is sized by the window)."""
        cfg = self.config
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        return [creation.zeros([batch_size, max_length,
                                cfg.num_key_value_heads, cfg.head_dim],
                               dtype=dtype)
                for _ in range(2 * cfg.num_hidden_layers)]

    # ---- forward ---------------------------------------------------------

    def forward(self, input_ids, caches=None, pos=None, tables=None,
                logits_at=None):
        """Logits [B, S, V]; with ``caches`` also the new caches. With
        ``logits_at`` (caches path only) the logits are [B, 1, V].

        ``tables=(block_tables, gate, ring_tables)`` is the serving
        engine's paged convention for a model with window layers (``gate``:
        per-slot valid count, or a bool active mask for a one-token step;
        ``pos`` [B, 1] each slot's position); without ``tables`` the
        caches are ``init_kv_cache``'s and every row advances by S from
        scalar ``pos``."""
        x = F.embedding(input_ids, self.embed_tokens.weight)
        paged = tables is not None
        valid = None
        if paged:
            s = input_ids.shape[1]
            valid = apply(
                lambda g: jnp.arange(s, dtype=jnp.int32)[None, :]
                < g.astype(jnp.int32)[:, None], tables[1],
                name="exaone_moe_valid", differentiable=False)
        # arrays of one attention layer: 2, or 4 under quantized KV
        n_kv = 0 if caches is None else \
            (len(caches) - int(paged)) // len(self.layers)
        new, moe_stats = [], None
        for l, layer in enumerate(self.layers):
            u = layer.input_layernorm(x)
            if caches is None:
                y = layer.self_attn(u)
            else:
                y, kv = layer.self_attn(
                    u, cache=tuple(caches[l * n_kv:(l + 1) * n_kv]),
                    pos=pos, tables=tables)
                new += list(kv)
            x = x + y
            u = layer.post_attention_layernorm(x)
            if layer.sparse:
                y, st = layer.mlp(u, valid=valid)
                moe_stats = st if moe_stats is None else moe_stats + st
            else:
                y = layer.mlp(u)
            x = x + y
        if caches is None:
            return F.linear(self.norm(x), self.lm_head.weight)
        if paged and len(caches) > n_kv * len(self.layers):
            new.append(caches[-1] if moe_stats is None else apply(
                lambda c, st: c + st, caches[-1], moe_stats,
                name="exaone_moe_counters", differentiable=False))
        if logits_at is not None:
            x = _hidden_at(x, logits_at)
        return F.linear(self.norm(x), self.lm_head.weight), new
