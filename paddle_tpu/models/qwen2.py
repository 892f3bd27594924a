"""Qwen2 (dense) and Qwen2-MoE model family — BASELINE config 5 workload
(Qwen2-MoE expert-parallel pretrain; reference workloads live in PaddleNLP,
mount empty, no cites).

Architecture: Llama-style decoder with attention QKV bias; the MoE
variant replaces the MLP with top-k routed experts (grouped-matmul bank,
``paddle_tpu.ops.moe``) plus a shared expert scaled by a sigmoid gate —
the Qwen2-MoE block structure. Expert parallelism engages automatically
via the fleet 'expert' mesh axis inside MoELayer."""

from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from ..ops import manipulation as M
from ..ops.linalg import matmul
from ..distributed.communication import in_traced_collective
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..incubate.distributed.models.moe import MoELayer
from ..generation import GenerationMixin
from .llama import (rope_with_offset, _alloc_kv_caches, _hidden_at,
                    _paged_attention_step)

__all__ = ["Qwen2Config", "Qwen2MoeConfig", "Qwen2ForCausalLM",
           "Qwen2MoeForCausalLMPipe", "Qwen2MoePretrainingCriterion",
           "Qwen2MoeForCausalLM"]


@dataclass
class Qwen2Config:
    vocab_size: int = 151936
    hidden_size: int = 3584
    num_hidden_layers: int = 28
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    intermediate_size: int = 18944
    max_position_embeddings: int = 32768
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    tensor_parallel: bool = False
    sep_parallel: str | None = None
    # roll the decoder stack into one lax.scan (see nn/scan.py)
    scan_layers: bool = True
    # every k-th layer skips remat entirely (0 = off) — see llama.py
    full_save_interval: int = 0
    # weight-only serving quantization switch — see LlamaConfig
    weight_quant: str | None = None

    @classmethod
    def qwen2_7b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=128, max_position_embeddings=128,
                   rope_theta=10000.0)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


@dataclass
class Qwen2MoeConfig(Qwen2Config):
    num_experts: int = 60
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1408
    shared_expert_intermediate_size: int = 5632
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 2.0
    #: MegaBlocks-style dropless dispatch (Pallas grouped matmul): no
    #: capacity, no token drops, and only ~E*128 padding rows of extra
    #: expert compute vs capacity_factor x T*k padded slots. Single
    #: device / GSPMD; under ep_degree > 1 MoELayer keeps the capacity
    #: all-to-all (per-device quotas bound the a2a payload).
    moe_dropless: bool = False

    @classmethod
    def qwen2_moe_a14b(cls):
        return cls(hidden_size=3584, num_hidden_layers=28,
                   num_attention_heads=28, num_key_value_heads=4)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=128, max_position_embeddings=128,
                   rope_theta=10000.0, num_experts=8,
                   num_experts_per_tok=2, moe_intermediate_size=32,
                   shared_expert_intermediate_size=64)


def _lin(cfg, in_f, out_f, *, column, has_bias=False, gather_output=False):
    init = nn.initializer.Normal(0.0, cfg.initializer_range)
    attr = nn.ParamAttr(initializer=init)
    if cfg.tensor_parallel:
        if column:
            return ColumnParallelLinear(in_f, out_f, weight_attr=attr,
                                        has_bias=has_bias,
                                        gather_output=gather_output)
        return RowParallelLinear(in_f, out_f, weight_attr=attr,
                                 has_bias=has_bias)
    return nn.Linear(in_f, out_f, weight_attr=attr,
                     bias_attr=None if has_bias else False)


class Qwen2Attention(nn.Layer):
    """Llama-style GQA attention with QKV bias (the Qwen2 signature)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.q_proj = _lin(cfg, cfg.hidden_size,
                           self.num_heads * self.head_dim, column=True,
                           has_bias=True)
        self.k_proj = _lin(cfg, cfg.hidden_size,
                           self.num_kv_heads * self.head_dim, column=True,
                           has_bias=True)
        self.v_proj = _lin(cfg, cfg.hidden_size,
                           self.num_kv_heads * self.head_dim, column=True,
                           has_bias=True)
        self.o_proj = _lin(cfg, self.num_heads * self.head_dim,
                           cfg.hidden_size, column=False)

    def forward(self, x, cache=None, pos=None, tables=None):
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x),
                      [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x),
                      [b, s, self.num_kv_heads, self.head_dim])
        if cache is not None and tables is not None:
            return _paged_attention_step(self, q, k, v, cache, pos,
                                         tables)
        if cache is not None:
            q = rope_with_offset(q, pos, self.cfg.max_position_embeddings,
                                 self.cfg.rope_theta)
            k = rope_with_offset(k, pos, self.cfg.max_position_embeddings,
                                 self.cfg.rope_theta)
            ctx, k_cache, v_cache = F.sdpa_with_cache(
                q, k, v, cache[0], cache[1], pos)
            ctx = M.reshape(ctx, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(ctx), (k_cache, v_cache)
        from ..incubate.nn.functional import \
            fused_rotary_position_embedding
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, rotary_emb_base=self.cfg.rope_theta)
        if self.cfg.sep_parallel is not None:
            from ..distributed.fleet.meta_parallel.context_parallel import \
                sep_attention
            ctx = sep_attention(q, k, v, causal=True,
                                impl=self.cfg.sep_parallel)
        else:
            ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        ctx = M.reshape(ctx, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(ctx)


class Qwen2MLP(nn.Layer):
    def __init__(self, cfg, intermediate=None):
        super().__init__()
        inter = intermediate or cfg.intermediate_size
        self.gate_proj = _lin(cfg, cfg.hidden_size, inter, column=True)
        self.up_proj = _lin(cfg, cfg.hidden_size, inter, column=True)
        self.down_proj = _lin(cfg, inter, cfg.hidden_size, column=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class Qwen2MoeBlock(nn.Layer):
    """Routed experts + shared expert with sigmoid gate."""

    def __init__(self, cfg: Qwen2MoeConfig):
        super().__init__()
        self.moe = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            gate={"top_k": cfg.num_experts_per_tok,
                  "capacity_factor": cfg.capacity_factor,
                  "norm_topk_prob": cfg.norm_topk_prob,
                  "dropless": getattr(cfg, "moe_dropless", False)})
        self.shared_expert = Qwen2MLP(
            cfg, intermediate=cfg.shared_expert_intermediate_size)
        self.shared_expert_gate = nn.Linear(cfg.hidden_size, 1,
                                            bias_attr=False)

    def forward(self, x):
        routed = self.moe(x)
        shared = self.shared_expert(x)
        gate = F.sigmoid(self.shared_expert_gate(x))
        return routed + gate * shared

    @property
    def aux_loss(self):
        return self.moe.aux_loss


class Qwen2DecoderLayer(nn.Layer):
    def __init__(self, cfg, moe=False):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = Qwen2Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = Qwen2MoeBlock(cfg) if moe else Qwen2MLP(cfg)

    def forward(self, x, cache=None, pos=None, tables=None):
        if cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x),
                                             cache=cache, pos=pos,
                                             tables=tables)
            x = x + attn
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        attn = self.self_attn(self.input_layernorm(x))
        from ..framework import flags
        if flags.flag("FLAGS_fused_rmsnorm_residual"):
            # the attention-residual add + post_attention_layernorm
            # pair lowers into ONE fused kernel (identical math; the
            # Pallas kernel on TPU — see models/llama.py's fused carry
            # for the full both-pairs treatment on the flagship stack)
            y, r = F.fused_rms_norm_residual(
                attn, x, self.post_attention_layernorm.weight,
                self.post_attention_layernorm.epsilon)
            return r + self.mlp(y)
        x = x + attn
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class _Qwen2Base(nn.Layer, GenerationMixin):
    def __init__(self, cfg, moe: bool):
        super().__init__()
        self.config = cfg
        self._moe = moe
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        if cfg.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        else:
            self.embed_tokens = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        self.layers = nn.LayerList([Qwen2DecoderLayer(cfg, moe=moe)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = _lin(cfg, cfg.hidden_size, cfg.vocab_size,
                            column=True, gather_output=True) \
            if not cfg.tie_word_embeddings else None

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        return _alloc_kv_caches(self.config, batch_size, max_length, dtype)

    def forward(self, input_ids, labels=None, caches=None, pos=None,
                tables=None, logits_at=None):
        """``logits_at`` (serving path only): per row the ONE position
        whose logits are wanted; the result is ``[B, 1, V]``."""
        if self._moe and self.training and self.config.use_recompute \
                and self.config.router_aux_loss_coef:
            # raised here (where recompute actually wraps the layers),
            # not at construction: inference-only use of a training
            # config is fine. Without this check the failure is an
            # opaque escaped-tracer error deep in tracing.
            raise ValueError(
                "router_aux_loss_coef > 0 with use_recompute=True is "
                "unsupported for training: the per-layer aux-loss "
                "attribute cannot cross the jax.checkpoint boundary "
                "(the stored tracer would leak). Set "
                "router_aux_loss_coef=0.0 or use_recompute=False.")
        x = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            # 2 pools per layer, or 4 under quantized KV (ISSUE 20)
            stride = len(caches) // len(self.layers)
            for i, layer in enumerate(self.layers):
                x, kv = layer(
                    x, cache=tuple(caches[stride * i:stride * (i + 1)]),
                    pos=pos, tables=tables)
                new_caches.extend(kv)
            if logits_at is not None:
                x = _hidden_at(x, logits_at)
            hidden = self.norm(x)
            logits = self.lm_head(hidden) if self.lm_head is not None else \
                matmul(hidden, self.embed_tokens.weight, transpose_y=True)
            return logits, new_caches
        from ..nn.scan import scan_layers as _scan, can_scan
        # MoE stacks never scan: per-layer aux_loss attributes are read
        # eagerly after the stack (and experts route via shard_map)
        if getattr(self.config, "scan_layers", True) and \
                not self._moe and can_scan(self.layers):
            x = _scan(self.layers, x,
                      remat=self.config.use_recompute and self.training,
                      full_save_interval=getattr(
                          self.config, "full_save_interval", 0))
        else:
            # remat DOSE (same knob as LlamaConfig.full_save_interval):
            # every k-th layer keeps activations whole instead of
            # recomputing — spend leftover HBM on backward speed
            fs = max(int(getattr(self.config, "full_save_interval", 0)),
                     0)
            for i, layer in enumerate(self.layers):
                if self.config.use_recompute and self.training and \
                        not (fs and i % fs == fs - 1):
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
        if self._moe and self.config.router_aux_loss_coef:
            # NOTE: per-layer aux attributes cannot cross a jax.checkpoint
            # boundary (use_recompute wraps each layer; the stored tracer
            # would leak) — run aux-weighted training without recompute,
            # or fold aux out (coef=0)
            coef = self.config.router_aux_loss_coef
            for layer in self.layers:
                aux = layer.mlp.aux_loss
                if aux is not None:
                    loss = loss + coef * aux
        return logits, loss


class Qwen2ForCausalLM(_Qwen2Base):
    def __init__(self, config: Qwen2Config):
        super().__init__(config, moe=False)


class Qwen2MoeForCausalLM(_Qwen2Base):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__(config, moe=True)


# ---------------------------------------------------------------------------
# Pipeline-parallel Qwen2-MoE: the ep x pp composition workload (SURVEY.md
# §2.3 EP row — expert all-to-all dispatch inside the compiled pipeline
# program). Construction order matches _Qwen2Base exactly so same-seed
# models draw identical initial weights (the parity-test basis).
# ---------------------------------------------------------------------------


# The prologue/epilogue/criterion are duck-typed on config fields that
# Qwen2MoeConfig shares with LlamaConfig (vocab_size, hidden_size,
# initializer_range, rms_norm_eps, tensor_parallel) — reuse the llama
# pipe classes rather than duplicating them.
from .llama import (LlamaEmbeddingPipe as Qwen2EmbeddingPipe,
                    LlamaHeadPipe as Qwen2HeadPipe)


class Qwen2MoeDecoderLayerPipe(Qwen2DecoderLayer):
    """Decoder stage for the pipeline body; carries ``config`` so the
    engine can detect MoE/sep participation."""

    def __init__(self, cfg):
        super().__init__(cfg, moe=True)
        self.config = cfg


# Shifted next-token CE — the PLAIN language-model loss (the llama
# criterion is duck-typed on vocab_size only). The router aux loss is an
# eager per-layer attribute in the monolithic model and cannot cross the
# compiled pipeline boundary; pipelined MoE training therefore runs with
# aux folded out (router_aux_loss_coef=0 parity — load balance still
# trains through the dispatch gradient).
from .llama import LlamaPretrainingCriterion as Qwen2MoePretrainingCriterion


def Qwen2MoeForCausalLMPipe(config, **pipeline_kwargs):
    """Build the pipelined Qwen2-MoE as a ``PipelineLayer`` (embedding
    prologue / uniform MoE decoder body / norm+head epilogue)."""
    from ..distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    descs = [LayerDesc(Qwen2EmbeddingPipe, config)] + \
        [LayerDesc(Qwen2MoeDecoderLayerPipe, config)
         for _ in range(config.num_hidden_layers)] + \
        [LayerDesc(Qwen2HeadPipe, config)]
    pipeline_kwargs.setdefault("loss_fn",
                               Qwen2MoePretrainingCriterion(config))
    return PipelineLayer(descs, **pipeline_kwargs)
