"""DeepSeek-V2-style model family: Multi-head Latent Attention (MLA) +
fine-grained MoE with shared experts (BASELINE config 5 names
DeepSeekMoE; reference workloads live in PaddleNLP — mount empty, see
SURVEY.md provenance warning).

TPU-native design notes:
- MLA compresses the KV stream into a small latent (``kv_lora_rank``)
  plus a shared decoupled-RoPE key (``qk_rope_head_dim``); the decode
  cache stores ONLY those two — the memory win that defines MLA. The
  up-projection back to per-head keys/values is a dense matmul (MXU
  food), recomputed per step from the latent.
- Attention q/k head dim (nope+rope) differs from the v head dim, which
  the flash kernel does not support — the MLA core runs as an einsum
  attention with fp32 softmax (XLA fuses the chain); the MoE FFN and all
  projections dominate FLOPs at DeepSeek shapes anyway.
- Routed experts reuse the framework ``MoELayer`` (grouped matmuls,
  ragged all-to-all over the 'expert' mesh axis when fleet EP is
  active); shared experts are a plain SwiGLU MLP added unconditionally
  (the DeepSeek-V2 formulation).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, apply
from ..nn import functional as F
from ..ops import manipulation as M
from ..ops.linalg import matmul
from ..distributed.communication import in_traced_collective
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..incubate.distributed.models.moe import MoELayer
from ..generation import GenerationMixin
from .llama import rope_with_offset

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM"]


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    # MLA geometry
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # FFN / MoE geometry
    intermediate_size: int = 12288       # dense layers
    moe_intermediate_size: int = 1536    # per routed expert
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1       # leading dense layers
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.001
    #: MegaBlocks-style dropless dispatch (see Qwen2MoeConfig)
    moe_dropless: bool = False
    # common
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    tensor_parallel: bool = False

    @classmethod
    def tiny(cls):
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                   num_attention_heads=4, q_lora_rank=32,
                   kv_lora_rank=16, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16,
                   intermediate_size=128, moe_intermediate_size=32,
                   n_routed_experts=8, n_shared_experts=1,
                   num_experts_per_tok=2, first_k_dense_replace=1,
                   routed_scaling_factor=1.0, norm_topk_prob=True,
                   max_position_embeddings=64)

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _lin(cfg, in_f, out_f, *, column, gather_output=False):
    init = nn.initializer.Normal(0.0, cfg.initializer_range)
    attr = nn.ParamAttr(initializer=init)
    if cfg.tensor_parallel:
        if column:
            return ColumnParallelLinear(in_f, out_f, weight_attr=attr,
                                        has_bias=False,
                                        gather_output=gather_output)
        return RowParallelLinear(in_f, out_f, weight_attr=attr,
                                 has_bias=False)
    return nn.Linear(in_f, out_f, weight_attr=attr, bias_attr=False)


#: KV-chunk size of the blockwise MLA path; the exact einsum is kept
#: below 2 chunks of sequence where its one-shot matmul is cheaper.
_MLA_CHUNK = 256


def _mla_core(q, k, v, causal_offset=None, valid_len=None):
    """MLA attention. q/k: [B, Sq, H, Dqk], v: [B, Sk, H, Dv] — the
    q/k vs v head-dim asymmetry breaks the flash kernel's contract, so
    this core is hand-rolled. ``causal_offset`` is the absolute
    position of q's first row (decode: pos; train: 0); ``valid_len``
    masks the padded cache tail (decode).

    Two regimes: short sequences (and the cached decode step) use the
    exact einsum with fp32 softmax; the TRAIN path at
    Sq >= 2*_MLA_CHUNK switches to ``ops.ring_attention.
    chunked_attention`` — blockwise online-softmax, O(Sq*chunk) score
    memory instead of the S x S logits matrix, which is what makes
    MLA's latent-cache memory win real at long context."""
    dqk = q.shape[-1]

    if causal_offset is None and q.shape[1] >= 2 * _MLA_CHUNK:
        from ..ops.ring_attention import chunked_attention

        def fn_chunked(qq, kk, vv):
            return chunked_attention(qq, kk, vv, causal=True,
                                     chunk=_MLA_CHUNK)

        return apply(fn_chunked, q, k, v, name="mla_attention_chunked")

    def fn(qq, kk, vv, *rest):
        import math
        logits = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                            preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(dqk)
        sq, sk = qq.shape[1], kk.shape[1]
        qpos = jnp.arange(sq)
        if rest:                              # decode: absolute offset
            qpos = qpos + rest[0].astype(jnp.int32)
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]  # causal
        if len(rest) > 1:                      # cache validity
            mask = mask & (kpos[None, :] < rest[1].astype(jnp.int32))
        logits = jnp.where(mask[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(vv.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    args = [q, k, v]
    if causal_offset is not None:
        args.append(causal_offset)
        if valid_len is not None:
            args.append(valid_len)
    return apply(fn, *args, name="mla_attention")


class DeepseekV2Attention(nn.Layer):
    """MLA: latent-compressed KV + decoupled RoPE key."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.cfg = cfg
        h, qk, rope = cfg.num_attention_heads, cfg.qk_head_dim, \
            cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            self.q_a_proj = _lin(cfg, cfg.hidden_size, cfg.q_lora_rank,
                                 column=False)
            self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                            cfg.rms_norm_eps)
            self.q_b_proj = _lin(cfg, cfg.q_lora_rank, h * qk,
                                 column=True)
        else:
            self.q_proj = _lin(cfg, cfg.hidden_size, h * qk, column=True)
        # latent + decoupled rope key (shared across heads) in one proj
        self.kv_a_proj_with_mqa = _lin(
            cfg, cfg.hidden_size, cfg.kv_lora_rank + rope, column=False)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank,
                                         cfg.rms_norm_eps)
        self.kv_b_proj = _lin(
            cfg, cfg.kv_lora_rank,
            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), column=True)
        self.o_proj = _lin(cfg, h * cfg.v_head_dim, cfg.hidden_size,
                           column=False)

    def _q(self, x, b, s, pos=None):
        cfg = self.cfg
        if cfg.q_lora_rank:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        else:
            q = self.q_proj(x)
        q = M.reshape(q, [b, s, cfg.num_attention_heads, cfg.qk_head_dim])
        q_nope = q[:, :, :, :cfg.qk_nope_head_dim]
        q_pe = q[:, :, :, cfg.qk_nope_head_dim:]
        zero = Tensor(jnp.zeros((b, 1), jnp.int32))
        q_pe = rope_with_offset(q_pe, pos if pos is not None else zero,
                                cfg.max_position_embeddings,
                                cfg.rope_theta)
        return M.concat([q_nope, q_pe], axis=-1)

    def _latent(self, x, b, s, pos=None):
        """(normed latent [B,S,R], rotated shared key [B,S,1,rope])."""
        cfg = self.cfg
        ckv = self.kv_a_proj_with_mqa(x)
        latent = self.kv_a_layernorm(ckv[:, :, :cfg.kv_lora_rank])
        k_pe = M.reshape(ckv[:, :, cfg.kv_lora_rank:],
                         [b, s, 1, cfg.qk_rope_head_dim])
        zero = Tensor(jnp.zeros((b, 1), jnp.int32))
        k_pe = rope_with_offset(k_pe, pos if pos is not None else zero,
                                cfg.max_position_embeddings,
                                cfg.rope_theta)
        return latent, k_pe

    def _expand_kv(self, latent, b, t):
        """Latent [B,T,R] -> per-head (k_nope [B,T,H,Dn], v [B,T,H,Dv])."""
        cfg = self.cfg
        kv = M.reshape(self.kv_b_proj(latent),
                       [b, t, cfg.num_attention_heads,
                        cfg.qk_nope_head_dim + cfg.v_head_dim])
        return (kv[:, :, :, :cfg.qk_nope_head_dim],
                kv[:, :, :, cfg.qk_nope_head_dim:])

    def forward(self, x, cache=None, pos=None):
        cfg = self.cfg
        b, s, _ = x.shape
        if cache is None:
            q = self._q(x, b, s)
            latent, k_pe = self._latent(x, b, s)
            k_nope, v = self._expand_kv(latent, b, s)
            k = M.concat(
                [k_nope, M.expand(k_pe, [b, s, cfg.num_attention_heads,
                                         cfg.qk_rope_head_dim])],
                axis=-1)
            ctx = _mla_core(q, k, v)
            ctx = M.reshape(ctx, [b, s,
                                  cfg.num_attention_heads * cfg.v_head_dim])
            return self.o_proj(ctx)

        # decode: cache = (latents [B,T,R], k_pe [B,T,1,rope]); the new
        # step's latent writes at ``pos``, attention runs over the whole
        # (masked) latent history re-expanded through kv_b — MLA's
        # cache is the latent, NOT per-head k/v
        lat_cache, pe_cache = cache
        q = self._q(x, b, s, pos=pos)
        latent, k_pe = self._latent(x, b, s, pos=pos)

        def write(buf, val, p):
            # pos arrives as a scalar from the decode loop (one shared
            # position) — normalize scalar/[B,1] alike
            start = jnp.reshape(p, (-1,))[0].astype(jnp.int32)
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), start, axis=1)
        lat_new = apply(lambda bf, vv, pp: write(bf, vv, pp),
                        lat_cache, latent, pos, name="mla_cache_write")
        pe_new = apply(lambda bf, vv, pp: write(bf, vv, pp),
                       pe_cache, k_pe, pos, name="mla_pe_write")
        t = lat_new.shape[1]
        k_nope, v = self._expand_kv(lat_new, b, t)
        k = M.concat(
            [k_nope, M.expand(pe_new, [b, t, cfg.num_attention_heads,
                                       cfg.qk_rope_head_dim])],
            axis=-1)
        valid = pos + s
        ctx = _mla_core(q, k, v, causal_offset=pos, valid_len=valid)
        ctx = M.reshape(ctx, [b, s,
                              cfg.num_attention_heads * cfg.v_head_dim])
        return self.o_proj(ctx), (lat_new, pe_new)


class DeepseekV2MLP(nn.Layer):
    def __init__(self, cfg, intermediate=None):
        super().__init__()
        inter = intermediate or cfg.intermediate_size
        self.gate_proj = _lin(cfg, cfg.hidden_size, inter, column=True)
        self.up_proj = _lin(cfg, cfg.hidden_size, inter, column=True)
        self.down_proj = _lin(cfg, inter, cfg.hidden_size, column=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV2MoE(nn.Layer):
    """Fine-grained routed experts (scaled) + always-on shared experts."""

    def __init__(self, cfg: DeepseekV2Config):
        super().__init__()
        self.scaling = cfg.routed_scaling_factor
        self.moe = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts,
            gate={"top_k": cfg.num_experts_per_tok,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "dropless": getattr(cfg, "moe_dropless", False)})
        self.shared_experts = DeepseekV2MLP(
            cfg, intermediate=cfg.moe_intermediate_size
            * cfg.n_shared_experts)

    def forward(self, x):
        return self.moe(x) * self.scaling + self.shared_experts(x)

    @property
    def aux_loss(self):
        return self.moe.aux_loss


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, cfg: DeepseekV2Config, layer_idx: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          cfg.rms_norm_eps)
        self.self_attn = DeepseekV2Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.is_moe = layer_idx >= cfg.first_k_dense_replace
        self.mlp = DeepseekV2MoE(cfg) if self.is_moe \
            else DeepseekV2MLP(cfg)

    def forward(self, x, cache=None, pos=None):
        if cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x),
                                             cache=cache, pos=pos)
            x = x + attn
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        attn = self.self_attn(self.input_layernorm(x))
        from ..framework import flags
        if flags.flag("FLAGS_fused_rmsnorm_residual"):
            # attention-residual add + post_attention_layernorm as ONE
            # fused kernel (models/llama.py fused-carry comment)
            y, r = F.fused_rms_norm_residual(
                attn, x, self.post_attention_layernorm.weight,
                self.post_attention_layernorm.epsilon)
            return r + self.mlp(y)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class DeepseekV2ForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        if config.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        else:
            self.embed_tokens = nn.Embedding(
                config.vocab_size, config.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList(
            [DeepseekV2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = _lin(config, config.hidden_size,
                            config.vocab_size, column=True,
                            gather_output=True) \
            if not config.tie_word_embeddings else None

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        """MLA cache: (latent [B,T,R], rope-key [B,T,1,rope]) per layer —
        R + rope floats per token instead of 2*H*D (the MLA win; e.g.
        576 vs 32768 at DeepSeek-V2 shapes)."""
        cfg = self.config
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        caches = []
        for _ in range(cfg.num_hidden_layers):
            caches.append(Tensor(jnp.zeros(
                (batch_size, max_length, cfg.kv_lora_rank), dtype)))
            caches.append(Tensor(jnp.zeros(
                (batch_size, max_length, 1, cfg.qk_rope_head_dim),
                dtype)))
        return caches

    def forward(self, input_ids, labels=None, caches=None, pos=None):
        # no ``tables`` parameter: paged/continuous-batching serving is
        # not implemented for MLA yet — passing block tables must fail
        # loudly, not be silently ignored
        x = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for i, layer in enumerate(self.layers):
                x, (lc, pc) = layer(x, cache=(caches[2 * i],
                                              caches[2 * i + 1]),
                                    pos=pos)
                new_caches.extend((lc, pc))
            hidden = self.norm(x)
            logits = self.lm_head(hidden) if self.lm_head is not None \
                else matmul(hidden, self.embed_tokens.weight,
                            transpose_y=True)
            return logits, new_caches
        if self.training and self.config.use_recompute and \
                self.config.router_aux_loss_coef:
            # see qwen2.py: the per-layer aux attribute cannot cross the
            # jax.checkpoint boundary; fail clearly, not as a leaked
            # tracer (inference-only use of a training config is fine)
            raise ValueError(
                "router_aux_loss_coef > 0 with use_recompute=True is "
                "unsupported for training: set router_aux_loss_coef=0.0 "
                "or use_recompute=False.")
        for layer in self.layers:
            if self.config.use_recompute and self.training:
                from ..incubate.recompute import recompute
                x = recompute(layer, x)
            else:
                x = layer(x)
        hidden = self.norm(x)
        tied = self.lm_head is None
        if labels is not None and not in_traced_collective():
            # the head fused with the shifted CE: no [N, V] logits
            # (ops/fused_ce.py); the labeled training forward returns
            # (None, loss). Inside a traced collective the head is
            # vocab-parallel and takes the plain tail below.
            from ..ops.fused_ce import lm_head_loss
            logits = None
            loss = lm_head_loss(hidden, self.embed_tokens.weight if tied
                                else self.lm_head.weight, labels,
                                vocab_first=tied)
        else:
            logits = matmul(hidden, self.embed_tokens.weight,
                            transpose_y=True) if tied \
                else self.lm_head(hidden)
            if labels is None:
                return logits
            shift_logits = logits[:, :-1, :]
            shift_labels = labels[:, 1:]
            loss = F.cross_entropy(
                M.reshape(shift_logits, [-1, self.config.vocab_size]),
                M.reshape(shift_labels, [-1]))
        coef = self.config.router_aux_loss_coef
        if coef:
            # stored aux tracers cannot cross a jax.checkpoint boundary;
            # with coef=0 (recompute runs) the read is skipped entirely
            for layer in self.layers:
                if layer.is_moe and layer.mlp.aux_loss is not None:
                    loss = loss + coef * layer.mlp.aux_loss
        return logits, loss
