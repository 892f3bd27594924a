"""MiniCPM-SALA (HF ``model_type`` ``minicpm_sala``): a pre-norm decoder with
dense SwiGLU MLPs whose token mixers are of TWO kinds in one stack, chosen
layer by layer (``mixer_types``):

- ``minicpm4`` — InfLLM-v2 block-sparse GQA: q, k, v, a per-head RMS norm on
  q and k, NO rotary embedding, a sigmoid output gate per channel; a query
  past ``sparse_dense_len`` attends only the ``sparse_topk`` blocks of keys
  its own scores against the layer's compressed keys choose
  (``ops.sparse_select`` states the rule);
- ``lightning-attn`` — decayed linear attention: q, k, v with a per-head RMS
  norm on q and k and a rotary embedding over the whole head, a per-head
  ``[d, d]`` float32 state ``S <- lambda_h S + k^T v``, ``o = (q / sqrt(d))
  S``, then an RMS norm over all heads and a sigmoid output gate.

MiniCPM's muP scalings: the embedding times ``scale_emb``, each residual
branch times ``scale_depth / sqrt(published depth)``, the final hidden
divided by ``hidden_size / dim_model_base`` before the untied head.

What the family asks of the serving engine, and how the model says it:

- :meth:`MiniCpmSalaForCausalLM.cache_spec` declares, per sparse layer,
  ``PagedKV`` and the layer's ``CompressedKeys`` index, per lightning layer
  one float32 ``SlotState``, then the pass counters. The model keeps both
  per-slot kinds right inside the step program (``SlotState``'s rule: a
  slot at position 0 starts from a zero state; padded positions and idle
  slots advance nothing).
- A pass of one token per slot (a decode micro-step) takes the one-step
  form of the lightning rule (on a TPU the in-place Pallas kernel under its
  no-delta rule); a prompt chunk takes the block form, Mamba-2's SSD with
  one group per head (``ops.mamba2.ssd_chunked``).
- The sparse layers' attention reads the page pools through
  ``ops.sparse_select.sparse_paged_attention``; the page size must be the
  sparse block size, so a block is one page.
- Parameters are built in ``config.dtype``; with ``config.empty_init``
  without storage (``models/nemotron_h.py`` says why).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, apply
from ..inference.cache_spec import (CompressedKeys, PagedKV, SlotState,
                                    StepCounters)
from ..nn import initializer as I
from ..ops import gated_delta as gd
from ..ops import paged_attention as PA
from ..ops import sparse_select as SS
from ..ops.mamba2 import ssd_chunked
from ._leaves import _Base, _Weight
from .exaone_moe import _qk_norm_rope
from .llama import _hidden_at

__all__ = ["MiniCpmSalaConfig", "MiniCpmSalaForCausalLM"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

#: the lightning state between steps is float32 whatever the served dtype
STATE_DTYPE = "float32"

#: what a pass through the model counts (``StepCounters``), declared in
#: ``ops.sparse_select``
COUNTERS = SS.SPARSE_COUNTERS

#: positions the lightning layers' block form solves at a time
LIGHTNING_CHUNK = 128

_PUBLISHED_MIXERS = (
    (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6
    + (SPARSE,) * 3)


@dataclass
class MiniCpmSalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: tuple = _PUBLISHED_MIXERS
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    max_position_embeddings: int = 524288     # unread: no rotary table
    # minicpm4 (sparse) layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    qk_norm: bool = True
    # lightning layers
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    rope_theta: float = 10000.0
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    #: the published depth (the residual scale and the decays read it) and
    #: the published index of this stack's first layer
    published_num_hidden_layers: int = 32
    first_published_layer: int = 0
    # the selection (MiniCPM4's sparse_config)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    #: parameters are BUILT in this dtype
    dtype: str = "float32"
    #: leaves without storage until real weights are loaded
    #: (``NemotronHConfig.empty_init``)
    empty_init: bool = False

    @classmethod
    def minicpm_sala_9b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        """Every selection size small enough that contexts of ~100 tokens
        select, cross the window and the dense length."""
        return cls(vocab_size=128, hidden_size=64, intermediate_size=96,
                   num_hidden_layers=4,
                   mixer_types=(LIGHTNING, SPARSE, LIGHTNING, SPARSE),
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, lightning_nh=4, lightning_nkv=4,
                   lightning_head_dim=16,
                   max_position_embeddings=256, dim_model_base=16,
                   published_num_hidden_layers=8, first_published_layer=2,
                   sparse_kernel_size=8, sparse_kernel_stride=4,
                   sparse_block_size=8, sparse_topk=5, sparse_init_blocks=1,
                   sparse_window_size=16, sparse_dense_len=48)

    @property
    def kinds(self):
        kinds = tuple(self.mixer_types)[:self.num_hidden_layers]
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types {kinds!r} does not give "
                             f"{self.num_hidden_layers} layers of "
                             f"{(SPARSE, LIGHTNING)}")
        return kinds

    @property
    def rule(self):
        return SS.SparseRule(
            self.sparse_kernel_size, self.sparse_kernel_stride,
            self.sparse_block_size, self.sparse_topk, self.sparse_init_blocks,
            self.sparse_window_size, self.sparse_dense_len)

    @property
    def alpha(self):
        return self.scale_depth / math.sqrt(self.published_num_hidden_layers)

    def check(self):
        """Refuse by name what the program does not build."""
        if self.attn_use_rope or not self.lightning_use_rope:
            raise ValueError("built: no rotary embedding on the sparse "
                             "layers, a whole-head one on the lightning "
                             "layers (attn_use_rope false, "
                             "lightning_use_rope true)")
        if not (self.qk_norm and self.use_output_gate
                and self.use_output_norm and self.attn_use_output_gate):
            raise ValueError("built: qk_norm, use_output_gate, "
                             "use_output_norm and attn_use_output_gate on")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("the lightning layers have one k and v head "
                             "per q head (lightning_nkv == lightning_nh)")
        self.rule       # noqa: B018 — refuses an inconsistent selection


def _norm(x, w, eps):
    """``x / sqrt(mean x^2 + eps) * w`` over the last axis, float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)


def decays(cfg, layer):
    """``-log lambda_h`` of layer ``layer`` (its index HERE): MiniMax-Text-01's
    slope rule over the PUBLISHED index, ``2^(-8 (h + 1) / H) (1 - l / (L -
    1) + 1e-5)``."""
    H, L = cfg.lightning_nh, cfg.published_num_hidden_layers
    l = cfg.first_published_layer + layer
    h = jnp.arange(H, dtype=jnp.float32)
    return 2.0 ** (-8.0 * (h + 1) / H) * (1.0 - l / (L - 1) + 1e-5)


@functools.lru_cache(maxsize=None)
def _mlp_fn(eps, alpha):
    """``h + alpha SwiGLU(N(h))`` as ONE jitted function shared by every
    layer (``models/nemotron_h.py`` ``_mamba_fn`` says why it is jitted)."""

    def fn(h, nw, wg, wu, wd):
        u = _norm(h, nw, eps).astype(h.dtype)
        a = jax.nn.silu(jnp.matmul(u, wg).astype(jnp.float32)) \
            * jnp.matmul(u, wu).astype(jnp.float32)
        y = jnp.matmul(a.astype(h.dtype), wd)
        return (h.astype(jnp.float32)
                + alpha * y.astype(jnp.float32)).astype(h.dtype)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _lightning_fn(H, D, eps, theta, chunk, alpha):
    """``x + alpha Lightning(N(x))`` and the new state, as ONE jitted
    function shared by the lightning layers. A one-token pass takes the
    one-step rule, a longer one the block form (SSD: ``x = v``, ``B = k``,
    ``C = q / sqrt(d)``, ``dt`` 1 on valid positions and 0 on padding, ``A
    = -s_h``, no skip term); the block form's state is the step's,
    transposed (``[d_v, d_k]``)."""

    def fn(x, nw, wq, wk, wv, wg, wo, qn, kn, onw, s, st, pos, lens, rst):
        B_, S = x.shape[0], x.shape[1]
        u = _norm(x, nw, eps).astype(x.dtype)
        q = jnp.matmul(u, wq).reshape(B_, S, H, D)
        k = jnp.matmul(u, wk).reshape(B_, S, H, D)
        v = jnp.matmul(u, wv).reshape(B_, S, H, D)
        gate = jax.nn.sigmoid(jnp.matmul(u, wg).astype(jnp.float32))
        q, k = _qk_norm_rope(q, k, qn, kn, pos, eps, theta)
        q = q.astype(jnp.float32) * (D ** -0.5)
        lam = jnp.exp(-s)
        if S == 1:
            o, st = gd.linear_state_step(
                st, q[:, 0], k[:, 0], v[:, 0],
                jnp.broadcast_to(lam, (B_, H)), live=lens > 0, reset=rst)
            o = o[:, None]
        else:
            st = jnp.where(rst[:, None, None, None], 0.0, st)
            dt = (jnp.arange(S, dtype=jnp.int32)[None] < lens[:, None]
                  ).astype(jnp.float32)
            o, h = ssd_chunked(
                jnp.swapaxes(st, -1, -2), v, jnp.broadcast_to(
                    dt[..., None], (B_, S, H)), -s, k, q,
                jnp.zeros((H,), jnp.float32), chunk=chunk)
            st = jnp.swapaxes(h, -1, -2)
        y = _norm(o.reshape(B_, S, H * D), onw, eps) * gate
        out = jnp.matmul(y.astype(x.dtype), wo)
        return (x.astype(jnp.float32)
                + alpha * out.astype(jnp.float32)).astype(x.dtype), st

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _sparse_fn(H, G, D, eps, rule, alpha):
    """``x + alpha SparseAttention(N(x))`` with the page pools, the
    compressed keys and the layer's four counters, as ONE jitted function
    shared by the sparse layers: write the pass's K/V into the pages,
    complete the compressed entries, select, attend."""

    def fn(x, nw, wq, wk, wv, wg, wo, qn, kn, kp, vp, ck, tbl, pos, lens):
        B_, S = x.shape[0], x.shape[1]
        u = _norm(x, nw, eps).astype(x.dtype)
        q = jnp.matmul(u, wq).reshape(B_, S, H, D)
        k = jnp.matmul(u, wk).reshape(B_, S, G, D)
        v = jnp.matmul(u, wv).reshape(B_, S, G, D)
        gate = jax.nn.sigmoid(jnp.matmul(u, wg).astype(jnp.float32))
        q, k = _qk_norm_rope(q, k, qn, kn, pos, eps, None)
        ctx = pos.reshape(-1).astype(jnp.int32)
        kp, vp = PA.paged_prefill_write(kp, vp, k, v, tbl, ctx, lens)
        ck = SS.compressed_update(ck, kp, tbl, ctx, lens, rule,
                                  -(-S // rule.stride))
        blocks = SS.select_blocks(q, ck, ctx, rule, tbl.shape[1])
        o, loaded = SS.sparse_paged_attention(q, kp, vp, tbl, ctx, lens,
                                              blocks, rule)
        y = o.reshape(B_, S, H * D).astype(jnp.float32) * gate
        out = jnp.matmul(y.astype(x.dtype), wo)
        n_sp, n_dn = SS.query_counts(ctx, lens, S, G, rule)
        # the blocks the rule chose for the sparse queries: those of the
        # list that hold keys at or before the query
        p = ctx[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        sp = (jnp.arange(S)[None] < lens[:, None]) & (p + 1 > rule.dense_len)
        chosen = jnp.sum(jnp.where(sp[..., None, None],
                                   blocks * rule.block <= p[..., None, None],
                                   False)).astype(jnp.int32)
        stats = jnp.stack([n_sp, n_dn, chosen, loaded.astype(jnp.int32)])
        return (x.astype(jnp.float32) + alpha * out.astype(jnp.float32)
                ).astype(x.dtype), kp, vp, ck, stats

    return jax.jit(fn)


class _Norm(_Weight):
    def __init__(self, cfg, n):
        super().__init__(cfg, n, init=I.Constant(1.0))


class SparseAttention(_Base):
    """``minicpm4``: q [H x D], k, v [G x D] (no bias), per-head RMS norm on
    q and k, no rotary, gate = sigmoid(u W_gate) per channel; ``(ctx *
    gate) W_o``."""

    def __init__(self, cfg):
        super().__init__(cfg)
        h, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        hid = cfg.hidden_size
        self.q_proj = _Weight(cfg, hid, h * d)
        self.k_proj = _Weight(cfg, hid, g * d)
        self.v_proj = _Weight(cfg, hid, g * d)
        self.o_gate = _Weight(cfg, hid, h * d)
        self.o_proj = _Weight(cfg, h * d, hid)
        self.q_norm = _Norm(cfg, d)
        self.k_norm = _Norm(cfg, d)


class LightningAttention(_Base):
    """``lightning-attn``: q, k, v [H x D] (no bias, no activation),
    per-head RMS norm on q and k then a whole-head rotary embedding; the
    decayed linear state; ``(N(o) * sigmoid(u W_gate)) W_o``."""

    def __init__(self, cfg):
        super().__init__(cfg)
        h, d, hid = cfg.lightning_nh, cfg.lightning_head_dim, cfg.hidden_size
        self.q_proj = _Weight(cfg, hid, h * d)
        self.k_proj = _Weight(cfg, hid, h * d)
        self.v_proj = _Weight(cfg, hid, h * d)
        self.z_proj = _Weight(cfg, hid, h * d)
        self.o_proj = _Weight(cfg, h * d, hid)
        self.q_norm = _Norm(cfg, d)
        self.k_norm = _Norm(cfg, d)
        self.o_norm = _Norm(cfg, h * d)


class _Mlp(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        hid, inter = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _Weight(cfg, hid, inter)
        self.up_proj = _Weight(cfg, hid, inter)
        self.down_proj = _Weight(cfg, inter, hid)


class MiniCpmSalaDecoderLayer(_Base):
    """``h = x + alpha Mixer(N(x))``, ``y = h + alpha SwiGLU(N(h))``."""

    def __init__(self, cfg, kind):
        super().__init__(cfg)
        self.kind = kind
        self.input_layernorm = _Norm(cfg, cfg.hidden_size)
        if kind == SPARSE:
            self.self_attn = SparseAttention(cfg)
        else:
            self.lightning_attn = LightningAttention(cfg)
        self.post_attention_layernorm = _Norm(cfg, cfg.hidden_size)
        self.mlp = _Mlp(cfg)


class MiniCpmSalaForCausalLM(_Base):
    def __init__(self, config: MiniCpmSalaConfig):
        super().__init__(config)
        config.check()
        self.config = cfg = config
        self.embed_tokens = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([MiniCpmSalaDecoderLayer(cfg, kind)
                                    for kind in cfg.kinds])
        self.norm = _Norm(cfg, cfg.hidden_size)
        self.lm_head = _Weight(cfg, cfg.hidden_size, cfg.vocab_size)

    # ---- caches ----------------------------------------------------------

    def cache_spec(self):
        """One entry per cache array group, in the order ``forward`` takes
        them: per layer by its kind, then the pass counters."""
        c = self.config
        spec = []
        for kind in c.kinds:
            if kind == SPARSE:
                spec += [PagedKV(c.num_key_value_heads, c.head_dim),
                         CompressedKeys(c.num_key_value_heads, c.head_dim,
                                        c.sparse_kernel_stride)]
            else:
                spec.append(SlotState((c.lightning_nh, c.lightning_head_dim,
                                       c.lightning_head_dim), STATE_DTYPE))
        spec.append(StepCounters(COUNTERS))
        return spec

    def _fresh_caches(self, b, s):
        """Caches for a forward without the engine's: ``b`` slots of ``s``
        positions, pages ``1 + i * MP ..`` for row ``i`` (page 0 the trash
        page), as the engine would lay them out."""
        c = self.config
        dtype = self.embed_tokens.weight._data.dtype
        bl = c.sparse_block_size
        mp = -(-s // bl)
        w = c.num_key_value_heads * c.head_dim
        caches = []
        for kind in c.kinds:
            if kind == SPARSE:
                caches += [jnp.zeros((1 + b * mp, bl, w), dtype)] * 2
                caches.append(jnp.zeros(
                    (b, -(-s // c.sparse_kernel_stride), w), dtype))
            else:
                d = c.lightning_head_dim
                caches.append(jnp.zeros((b, c.lightning_nh, d, d),
                                        jnp.float32))
        caches.append(jnp.zeros((len(COUNTERS),), jnp.int32))
        tbl = 1 + jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
        return [Tensor(a) for a in caches], Tensor(tbl)

    # ---- forward ---------------------------------------------------------

    def forward(self, input_ids, caches=None, pos=None, tables=None,
                logits_at=None):
        """Logits [B, S, V]; with ``caches`` also the new caches. With
        ``logits_at`` (per row the ONE position whose logits are wanted)
        the logits are [B, 1, V].

        ``tables=(block_tables, gate)`` is the serving engine's paged
        convention (``gate``: per-slot valid count, or a bool active mask
        for a one-token step; ``pos`` [B, 1] each slot's position). Without
        caches the whole sequence is one pass from position 0 over fresh
        caches laid out as the engine lays them out."""
        c = self.config
        b, s = input_ids.shape[0], input_ids.shape[1]
        fresh = caches is None
        if fresh:
            caches, tbl = self._fresh_caches(b, s)
            pos = Tensor(jnp.zeros((b, 1), jnp.int32))
            tables = (tbl, Tensor(jnp.full((b,), s, jnp.int32)))

        def gates(p, g):
            lens = g.astype(jnp.int32)
            return lens, (p.reshape(-1) == 0) & (lens > 0)

        lens, reset = apply(gates, pos, tables[1], n_outputs=2,
                            name="minicpm_sala_gates", differentiable=False)
        x = apply(lambda e, i: (jnp.take(e, i, axis=0).astype(jnp.float32)
                                * c.scale_emb).astype(e.dtype),
                  self.embed_tokens.weight, input_ids, name="embed_scaled")
        rule, alpha, eps = c.rule, c.alpha, c.rms_norm_eps
        new, i, stats = [], 0, None
        for l, layer in enumerate(self.layers):
            nw = layer.input_layernorm.weight
            if layer.kind == SPARSE:
                a = layer.self_attn
                fn = _sparse_fn(c.num_attention_heads, c.num_key_value_heads,
                                c.head_dim, eps, rule, alpha)
                x, kp, vp, ck, st = apply(
                    fn, x, nw, a.q_proj.weight, a.k_proj.weight,
                    a.v_proj.weight, a.o_gate.weight, a.o_proj.weight,
                    a.q_norm.weight, a.k_norm.weight, caches[i],
                    caches[i + 1], caches[i + 2], tables[0], pos, lens,
                    n_outputs=5, name="sparse_attention_mixer",
                    differentiable=False)
                new += [kp, vp, ck]
                stats = st if stats is None else apply(
                    jnp.add, stats, st, name="add", differentiable=False)
                i += 3
            else:
                a = layer.lightning_attn
                fn = _lightning_fn(c.lightning_nh, c.lightning_head_dim, eps,
                                   float(c.rope_theta), LIGHTNING_CHUNK,
                                   alpha)
                x, st = apply(
                    fn, x, nw, a.q_proj.weight, a.k_proj.weight,
                    a.v_proj.weight, a.z_proj.weight, a.o_proj.weight,
                    a.q_norm.weight, a.k_norm.weight, a.o_norm.weight,
                    Tensor(decays(c, l)), caches[i], pos, lens, reset,
                    n_outputs=2, name="lightning_attention_mixer",
                    differentiable=False)
                new.append(st)
                i += 1
            m = layer.mlp
            x = apply(_mlp_fn(eps, alpha), x,
                      layer.post_attention_layernorm.weight,
                      m.gate_proj.weight, m.up_proj.weight,
                      m.down_proj.weight, name="mlp", differentiable=False)
        if stats is not None:
            new.append(apply(jnp.add, caches[i], stats,
                             name="minicpm_sala_counters",
                             differentiable=False))
        else:
            new.append(caches[i])
        if logits_at is not None:
            x = _hidden_at(x, logits_at)
        scale = c.hidden_size / c.dim_model_base
        logits = apply(
            lambda h, w, hw: jnp.matmul(
                (_norm(h, w, eps) / scale).astype(h.dtype), hw),
            x, self.norm.weight, self.lm_head.weight, name="lm_head")
        if fresh:
            return logits
        return logits, new
