"""Qwen3-Next family (HF ``model_type`` ``qwen3_next``;
Qwen3-Next-80B-A3B): a pre-norm decoder whose token mixers are of TWO kinds
in one stack — ``linear_attention``, a Gated DeltaNet layer (a short causal
conv, then the gated delta rule over a per-head ``[d_k, d_v]`` state,
``ops.gated_delta``), and every ``full_attention_interval``-th layer
``full_attention``: GQA with an OUTPUT GATE (``q_proj`` is twice as wide:
per head ``[q | gate]``, the context is multiplied by ``sigmoid(gate)``), a
per-head RMS norm on q and k and a PARTIAL rotary embedding (the first
``partial_rotary_factor`` of the head's dims). Every layer's MLP is sparse:
a softmax router over ``num_experts`` SwiGLU experts, top
``num_experts_per_tok``, beside one shared expert behind a scalar gate
``sigmoid(u w_sg)``. RMS norms are zero-centred (scale ``1 + w``) except the
delta-rule layer's gated output norm. No bias anywhere; untied head.

What the family asks of the serving engine, and how the model says it:

- :meth:`Qwen3NextForCausalLM.cache_spec` declares, per layer, two
  ``SlotState`` arrays (the float32 state and the conv's tail) for a
  delta-rule layer and ``PagedKV`` for a full-attention one. Per-slot state
  is kept right inside the forward, by ``SlotState``'s rule: a slot at
  position 0 starts from zero, padded positions and idle slots advance
  nothing.
- A pass of one token per slot (a decode micro-step) takes the one-step
  form of the delta rule (on a TPU the in-place Pallas kernel); a prompt
  chunk takes the chunked form, which hands back the state after each
  row's last valid token.
- The sparse block is told which experts it HOLDS (``first_held_expert``,
  ``num_experts_held``), as ``models/exaone_moe.py`` is.
- Parameters are built in ``config.dtype``; with ``config.empty_init``
  without storage (``models/nemotron_h.py`` says why).
- The published model's multi-token-prediction head is not built:
  ``mtp_num_hidden_layers`` is accepted and must be 0.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import apply
from ..generation import GenerationMixin
from ..inference.cache_spec import PagedKV, SlotState, StepCounters
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import creation
from ..ops import gated_delta as gdn
from ..ops import manipulation as M
from ..ops import moe as moe_ops
from ..ops.mamba2 import causal_conv_carry
from ._leaves import _Base, _Weight
from .exaone_moe import _attend, _qk_norm_rope, _swiglu
from .llama import _hidden_at, _paged_attention_step

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM"]

LINEAR, FULL = "linear_attention", "full_attention"

#: the delta rule's state between steps is float32 whatever the served
#: dtype (the recurrence compounds its rounding over a stream); the conv
#: tail is kept in the activations' dtype
GDN_STATE_DTYPE = "float32"

#: what a pass through the model counts (``StepCounters``): the expert
#: layer's three and the delta-rule layers' two, each declared where its
#: function lives
COUNTERS = moe_ops.HELD_COUNTERS + gdn.GDN_COUNTERS


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    #: layer l is full attention if (l + 1) % full_attention_interval == 0
    full_attention_interval: int = 4
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    max_position_embeddings: int = 262144      # unread: no rotary table
    # full attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta rule
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: positions the chunked form solves at a time (a power of two)
    gdn_chunk: int = 64
    # sparse MLP (every layer)
    num_experts: int = 512                     # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    #: the share of the routed experts this instance holds:
    #: [first_held_expert, first_held_expert + num_experts_held);
    #: None = all of them
    num_experts_held: int | None = None
    first_held_expert: int = 0
    mtp_num_hidden_layers: int = 0
    #: parameters are BUILT in this dtype
    dtype: str = "float32"
    #: leaves without storage until real weights are loaded
    #: (``NemotronHConfig.empty_init``)
    empty_init: bool = False

    @classmethod
    def qwen3_next_80b_a3b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, linear_num_key_heads=2,
                   linear_num_value_heads=4, linear_key_head_dim=16,
                   linear_value_head_dim=8, gdn_chunk=8, num_experts=16,
                   num_experts_per_tok=3, moe_intermediate_size=32,
                   shared_expert_intermediate_size=32,
                   max_position_embeddings=256)

    @property
    def layer_kinds(self):
        n = int(self.full_attention_interval)
        return tuple(FULL if (l + 1) % n == 0 else LINEAR
                     for l in range(self.num_hidden_layers))

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return moe_ops.held_range(self.first_held_expert,
                                  self.num_experts_held, self.num_experts)


class _Norm(_Weight):
    """Zero-centred RMS norm: ``x / rms(x) * (1 + w)`` in float32."""

    def __init__(self, cfg, n):
        super().__init__(cfg, n, init=I.Constant(0.0))

    def forward(self, x):
        eps = self.cfg.rms_norm_eps

        def fn(a, w):
            af = a.astype(jnp.float32)
            ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
            return (af * jax.lax.rsqrt(ms + eps)
                    * (1.0 + w.astype(jnp.float32))).astype(a.dtype)

        return apply(fn, x, self.weight, name="rms_norm_centred")


@functools.lru_cache(maxsize=None)
def _gdn_fn(Hk, Hv, dk, dv, eps, chunk, has_state):
    """The Gated DeltaNet mixer as ONE jitted function of its arrays
    (shared by every layer of these sizes; ``models/nemotron_h.py``
    ``_mamba_fn`` says why it is jitted). Column layout of the two input
    projections, stated once: ``W_qkvz`` = ``[q | k | v | z]``, each part
    head-major (q, k: Hk x dk; v, z: Hv x dv); ``W_ba`` = ``[b | a]``,
    Hv each."""
    kd, vd = Hk * dk, Hv * dv

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    def fn(u, w_qkvz, w_ba, cw, dt_b, a_log, nw, w_out, *rest):
        B_, S = u.shape[0], u.shape[1]
        if has_state:
            st, tail, lens, rst = rest
            tail = jnp.where(rst[:, None, None], 0, tail)
        else:
            st = jnp.zeros((B_, Hv, dk, dv), jnp.float32)
            tail = jnp.zeros((B_, cw.shape[0] - 1, cw.shape[1]), u.dtype)
            lens = jnp.full((B_,), S, jnp.int32)
            rst = None
        qkvz = jnp.matmul(u, w_qkvz)
        ba = jnp.matmul(u, w_ba).astype(jnp.float32)
        z = qkvz[..., 2 * kd + vd:]
        qkv, tail = causal_conv_carry(qkvz[..., :2 * kd + vd], tail, cw,
                                      None, lens)
        qkv = jax.nn.silu(qkv)                              # float32
        rep = Hv // Hk                  # value head h reads key head h // rep
        q = jnp.repeat(l2(qkv[..., :kd].reshape(B_, S, Hk, dk)), rep, 2) \
            * (dk ** -0.5)
        k = jnp.repeat(l2(qkv[..., kd:2 * kd].reshape(B_, S, Hk, dk)),
                       rep, 2)
        v = qkv[..., 2 * kd:].reshape(B_, S, Hv, dv)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            ba[..., Hv:] + dt_b.astype(jnp.float32))
        # both forms advance nothing past a row's length
        if S == 1:
            o, st2 = gdn.gated_delta_step(
                st, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                live=lens > 0, reset=rst)
            o = o[:, None]
        else:
            if rst is not None:
                st = jnp.where(rst[:, None, None, None], 0, st)
            o, st2 = gdn.gated_delta_chunked(st, q, k, v, g, beta,
                                             lengths=lens, chunk=chunk)
        # the gated output norm, per head: RMS(o) * w, then * silu(z)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * nw.astype(jnp.float32)
        y = o * jax.nn.silu(z.astype(jnp.float32).reshape(B_, S, Hv, dv))
        out = jnp.matmul(y.reshape(B_, S, vd).astype(u.dtype), w_out)
        if has_state:
            return out, st2, tail
        return out

    return jax.jit(fn)


class _Conv(_Base):
    """Depthwise causal conv over ``[q | k | v]``, no bias; ``weight`` is
    ``[kernel, channels]`` (``ops.mamba2.causal_conv_carry``'s layout)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.weight = self._p((cfg.linear_conv_kernel_dim, cfg.conv_dim))


class GatedDeltaNet(_Base):
    """``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``; ``[q | k | v]
    = silu(conv([q | k | v]))``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
    softplus(a + dt_bias)``; q, k L2-normalised per head, q scaled by
    ``d_k^-0.5``; the gated delta rule; ``out = (RMS(o) w * silu(z))
    W_out``."""

    def __init__(self, cfg):
        super().__init__(cfg)
        Hv = cfg.linear_num_value_heads
        self.in_proj_qkvz = _Weight(cfg, cfg.hidden_size,
                                    2 * cfg.key_dim + 2 * cfg.value_dim)
        self.in_proj_ba = _Weight(cfg, cfg.hidden_size, 2 * Hv)
        self.conv1d = _Conv(cfg)
        self.dt_bias = self._p((Hv,), I.Constant(0.0))
        self.A_log = self._p((Hv,), I.Constant(0.0))
        self.norm = _Weight(cfg, cfg.linear_value_head_dim,
                            init=I.Constant(1.0))
        self.out_proj = _Weight(cfg, cfg.value_dim, cfg.hidden_size)

    def state_shapes(self):
        c = self.cfg
        return ((c.linear_num_value_heads, c.linear_key_head_dim,
                 c.linear_value_head_dim),
                (c.linear_conv_kernel_dim - 1, c.conv_dim))

    def forward(self, u, state=None, lengths=None, reset=None):
        """u [B, S, hid]. ``state`` (S [B, Hv, dk, dv] f32, tail [B, K-1,
        C]) or None for zero state; ``lengths`` [B] int32 valid counts;
        ``reset`` [B] bool, slots that start from zero state. Returns out,
        or (out, (state, tail)) when a state was given."""
        c = self.cfg
        fn = _gdn_fn(c.linear_num_key_heads, c.linear_num_value_heads,
                     c.linear_key_head_dim, c.linear_value_head_dim,
                     c.rms_norm_eps, c.gdn_chunk, state is not None)
        args = [u, self.in_proj_qkvz.weight, self.in_proj_ba.weight,
                self.conv1d.weight, self.dt_bias, self.A_log,
                self.norm.weight, self.out_proj.weight]
        if state is not None:
            args += [state[0], state[1], lengths, reset]
            out, st, tail = apply(fn, *args, n_outputs=3,
                                  name="gated_delta_mixer",
                                  differentiable=False)
            return out, (st, tail)
        return apply(fn, *args, name="gated_delta_mixer")


class Qwen3NextAttention(_Base):
    """GQA whose context is gated: ``out = (ctx * sigmoid(gate)) W_o`` with
    ``[q | gate]`` per head from one projection; zero-centred per-head RMS
    norm on q and k, then the rotary embedding on the first
    ``rotary_dim`` dims."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        self.q_proj = _Weight(cfg, cfg.hidden_size, h * 2 * d)
        self.k_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.v_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.o_proj = _Weight(cfg, h * d, cfg.hidden_size)
        self.q_norm = _Weight(cfg, d, init=I.Constant(0.0))
        self.k_norm = _Weight(cfg, d, init=I.Constant(0.0))

    def forward(self, x, cache=None, pos=None, tables=None):
        b, s, _ = x.shape
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        q, gate = apply(
            lambda a: (a[..., :d], a[..., d:].reshape(b, s, h * d)),
            M.reshape(F.linear(x, self.q_proj.weight), [b, s, h, 2 * d]),
            n_outputs=2, name="attn_q_gate_split")
        k = M.reshape(F.linear(x, self.k_proj.weight), [b, s, kvh, d])
        v = M.reshape(F.linear(x, self.v_proj.weight), [b, s, kvh, d])
        if pos is None:
            pos = creation.zeros([1], dtype="int32")
        q, k = apply(
            functools.partial(_qk_norm_rope, eps=self.cfg.rms_norm_eps,
                              theta=float(self.cfg.rope_theta),
                              rotary_dim=self.cfg.rotary_dim, centred=True),
            q, k, self.q_norm.weight, self.k_norm.weight, pos,
            n_outputs=2, name="qk_norm_rope")

        def out(ctx):
            gated = apply(
                lambda c, g: (c.astype(jnp.float32) * jax.nn.sigmoid(
                    g.astype(jnp.float32))).astype(c.dtype),
                ctx, gate, name="attn_out_gate")
            return F.linear(gated, self.o_proj.weight)

        if cache is not None and tables is not None:
            return _paged_attention_step(self, q, k, v, cache, pos, tables,
                                         rope=False, proj=out)
        fn = functools.partial(_attend, window=None)
        if cache is not None:
            ctx, kc, vc = apply(fn, q, k, v, pos, cache[0], cache[1],
                                n_outputs=3, name="sdpa_cached")
            return out(M.reshape(ctx, [b, s, h * d])), (kc, vc)
        ctx = apply(fn, q, k, v, pos, name="sdpa")
        return out(M.reshape(ctx, [b, s, h * d]))


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


class _SharedExpert(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        inter = cfg.shared_expert_intermediate_size
        self.gate_proj = _Weight(cfg, cfg.hidden_size, inter)
        self.up_proj = _Weight(cfg, cfg.hidden_size, inter)
        self.down_proj = _Weight(cfg, inter, cfg.hidden_size)


@functools.lru_cache(maxsize=None)
def _moe_fn(first, k, norm, has_valid, shared):
    """The sparse block as ONE jitted function shared by its layers
    (``models/nemotron_h.py`` ``_mamba_fn`` says why)."""

    def fn(u, wr, wg, w1, w2, sg, su, sd, s_gate, *v):
        shp = u.shape
        flat = u.reshape(-1, shp[-1])
        ok = v[0].reshape(-1) if has_valid else None
        logits = jnp.matmul(flat.astype(jnp.float32),
                            wr.astype(jnp.float32))
        idx, w = moe_ops.softmax_top_k_router(logits, k, norm)
        out, st = moe_ops.moe_experts_held(flat, idx, w, w1, w2, first,
                                           valid=ok, w_gate=wg)
        if shared:
            gate = jax.nn.sigmoid(jnp.matmul(flat, s_gate)
                                  .astype(jnp.float32))
            out = out + (gate * _swiglu(flat, sg, su, sd)
                         .astype(jnp.float32)).astype(out.dtype)
        n_tok = jnp.sum(ok).astype(jnp.int32) if has_valid \
            else jnp.asarray(flat.shape[0], jnp.int32)
        return out.reshape(shp), jnp.concatenate([n_tok[None], st])

    return jax.jit(fn)


class Qwen3NextSparseMoe(_Base):
    """``p = softmax(u W_r)`` in float32 over ALL routed experts; the top
    k; ``w = p_sel / sum(p_sel)``; ``out = sum_k w_k E_k(u) + sigmoid(u
    w_sg) S(u)``, every expert a SwiGLU. Only the held experts' pairs are
    computed here."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.gate = _Weight(cfg, cfg.hidden_size, cfg.num_experts)
        self.experts = _Experts(cfg)
        self.shared_expert = _SharedExpert(cfg)
        self.shared_expert_gate = _Weight(cfg, cfg.hidden_size, 1)

    def forward(self, u, valid=None, shared=True):
        """u [B, S, hid]; ``valid`` [B, S] bool or None. Returns (out,
        stats) with stats int32 [tokens, local pairs, busiest held
        expert's pairs]. ``shared=False`` leaves the shared expert out (a
        holder other than the one that counts it)."""
        c = self.cfg
        has_valid = valid is not None
        fn = _moe_fn(c.held[0], c.num_experts_per_tok, c.norm_topk_prob,
                     has_valid, shared)
        se = self.shared_expert
        args = [u, self.gate.weight, self.experts.gate_proj,
                self.experts.up_proj, self.experts.down_proj,
                se.gate_proj.weight, se.up_proj.weight, se.down_proj.weight,
                self.shared_expert_gate.weight]
        if has_valid:
            args.append(valid)
        return apply(fn, *args, n_outputs=2, name="qwen3_next_sparse_moe",
                     differentiable=False)


class Qwen3NextDecoderLayer(_Base):
    """``h = x + Mixer(N(x))``, ``y = h + MoE(N(h))``."""

    def __init__(self, cfg, kind):
        super().__init__(cfg)
        self.kind = kind
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        if kind == LINEAR:
            self.linear_attn = GatedDeltaNet(cfg)
        else:
            self.self_attn = Qwen3NextAttention(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = Qwen3NextSparseMoe(cfg)


class Qwen3NextForCausalLM(_Base, GenerationMixin):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__(config)
        self.config = config
        cfg = config
        if cfg.mtp_num_hidden_layers:
            raise ValueError(
                "mtp_num_hidden_layers must be 0: this model has no "
                "multi-token-prediction head")
        self.embed_tokens = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([Qwen3NextDecoderLayer(cfg, kind)
                                    for kind in cfg.layer_kinds])
        self.norm = _Norm(cfg, cfg.hidden_size)
        self.lm_head = _Weight(cfg, cfg.hidden_size, cfg.vocab_size)

    # ---- caches ----------------------------------------------------------

    def cache_spec(self):
        """One entry per cache array group, in the order ``forward`` takes
        them: per layer by its kind, then the pass counters."""
        cfg = self.config
        spec = []
        for layer in self.layers:
            if layer.kind == LINEAR:
                st, tail = layer.linear_attn.state_shapes()
                spec += [SlotState(st, GDN_STATE_DTYPE),
                         SlotState(tail, None)]
            else:
                spec.append(PagedKV(cfg.num_key_value_heads, cfg.head_dim))
        spec.append(StepCounters(COUNTERS))
        return spec

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        """Contiguous caches for ``generate``: (k, v) of [B, max_len, KV,
        D] for a full-attention layer, (state, tail) for a delta-rule
        one."""
        cfg = self.config
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        caches = []
        for layer in self.layers:
            if layer.kind == LINEAR:
                st, tail = layer.linear_attn.state_shapes()
                caches += [
                    creation.zeros([batch_size] + list(st),
                                   dtype=GDN_STATE_DTYPE),
                    creation.zeros([batch_size] + list(tail), dtype=dtype)]
            else:
                caches += [creation.zeros(
                    [batch_size, max_length, cfg.num_key_value_heads,
                     cfg.head_dim], dtype=dtype) for _ in range(2)]
        return caches

    # ---- forward ---------------------------------------------------------

    def forward(self, input_ids, caches=None, pos=None, tables=None,
                logits_at=None):
        """Logits [B, S, V]; with ``caches`` also the new caches. With
        ``logits_at`` (caches path only; per row the ONE position whose
        logits are wanted) the logits are [B, 1, V].

        ``tables=(block_tables, gate)`` is the serving engine's paged
        convention (``gate``: per-slot valid count, or a bool active mask
        for a one-token step; ``pos`` [B, 1] each slot's position);
        without ``tables`` the caches are ``init_kv_cache``'s and every
        row advances by S from scalar ``pos``."""
        x = F.embedding(input_ids, self.embed_tokens.weight)
        if caches is None:
            for layer in self.layers:
                u = layer.input_layernorm(x)
                x = x + (layer.linear_attn(u) if layer.kind == LINEAR
                         else layer.self_attn(u))
                x = x + layer.mlp(layer.post_attention_layernorm(x))[0]
            return F.linear(self.norm(x), self.lm_head.weight)

        b, s = input_ids.shape[0], input_ids.shape[1]
        paged = tables is not None

        def gates(ids, p, *g):
            if paged:
                lens = g[0].astype(jnp.int32)
                at0 = p.reshape(-1) == 0
            else:
                lens = jnp.full((b,), s, jnp.int32)
                at0 = jnp.broadcast_to(p.reshape(-1)[:1] == 0, (b,))
            valid = jnp.arange(s, dtype=jnp.int32)[None, :] < lens[:, None]
            return lens, at0 & (lens > 0), valid

        lens, reset, valid = apply(
            gates, input_ids, pos, *([tables[1]] if paged else []),
            n_outputs=3, name="qwen3_next_gates", differentiable=False)
        kinds = self.config.layer_kinds
        n_lin = kinds.count(LINEAR)
        # arrays of one attention layer: 2, or 4 under quantized KV
        n_kv = (len(caches) - 2 * n_lin - int(paged)) \
            // max(len(kinds) - n_lin, 1)
        new, i, moe_stats = [], 0, None
        for layer in self.layers:
            u = layer.input_layernorm(x)
            if layer.kind == LINEAR:
                y, st = layer.linear_attn(
                    u, state=(caches[i], caches[i + 1]), lengths=lens,
                    reset=reset)
                new += list(st)
                i += 2
            else:
                y, kv = layer.self_attn(u, cache=tuple(caches[i:i + n_kv]),
                                        pos=pos, tables=tables)
                new += list(kv)
                i += n_kv
            x = x + y
            y, st = layer.mlp(layer.post_attention_layernorm(x),
                              valid=valid)
            moe_stats = st if moe_stats is None else moe_stats + st
            x = x + y
        if paged and i < len(caches):
            def count(c, ln, moe):
                tokens = jnp.sum(ln).astype(jnp.int32) * n_lin
                return c + jnp.concatenate([moe, jnp.stack(
                    [tokens, tokens if s > 1 else jnp.zeros_like(tokens)])])

            new.append(apply(count, caches[i], lens, moe_stats,
                             name="qwen3_next_counters",
                             differentiable=False))
        if logits_at is not None:
            x = _hidden_at(x, logits_at)
        return F.linear(self.norm(x), self.lm_head.weight), new
