"""GPT-2 (BASELINE config 1: 124M LM, CPU-runnable reference model).

Written with the paddle-shaped Layer API; attention goes through
F.scaled_dot_product_attention (flash-attn kernel on TPU)."""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from ..framework.core import Tensor
from .. import nn
from ..nn import functional as F
from ..ops import creation, manipulation as M
from ..generation import GenerationMixin

__all__ = ["GPT2Config", "GPT2Model", "GPT2ForCausalLM"]


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # weight-only serving quantization switch — see LlamaConfig
    weight_quant: str | None = None

    @classmethod
    def small(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   max_position_embeddings=128, hidden_dropout_prob=0.0,
                   attention_dropout_prob=0.0)


class GPT2Attention(nn.Layer):
    def __init__(self, cfg: GPT2Config):
        super().__init__()
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        attr = nn.ParamAttr(initializer=init)
        self.c_attn = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size,
                                weight_attr=attr)
        self.c_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                weight_attr=attr)
        self.attn_dropout = cfg.attention_dropout_prob

    def forward(self, x, cache=None, pos=None, tables=None):
        b, s, e = x.shape
        qkv = self.c_attn(x)
        qkv = M.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        if cache is not None and tables is not None:
            from .llama import _paged_attention_step
            return _paged_attention_step(self, q, k, v, cache, pos,
                                         tables, rope=False,
                                         proj=self.c_proj)
        if cache is not None:
            ctx, k_cache, v_cache = F.sdpa_with_cache(
                q, k, v, cache[0], cache[1], pos)
            ctx = M.reshape(ctx, [b, s, e])
            return self.c_proj(ctx), (k_cache, v_cache)
        ctx = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.attn_dropout,
            training=self.training)
        ctx = M.reshape(ctx, [b, s, e])
        return self.c_proj(ctx)


class GPT2MLP(nn.Layer):
    def __init__(self, cfg: GPT2Config):
        super().__init__()
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        attr = nn.ParamAttr(initializer=init)
        self.c_fc = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                              weight_attr=attr)
        self.c_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                weight_attr=attr)

    def forward(self, x):
        return self.c_proj(F.gelu(self.c_fc(x), approximate=True))


class GPT2Block(nn.Layer):
    def __init__(self, cfg: GPT2Config):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.attn = GPT2Attention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_epsilon)
        self.mlp = GPT2MLP(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, pos=None, tables=None):
        if cache is not None:
            attn, new_cache = self.attn(self.ln_1(x), cache=cache, pos=pos,
                                        tables=tables)
            x = x + attn
            x = x + self.mlp(self.ln_2(x))
            return x, new_cache
        x = x + self.dropout(self.attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class GPT2Model(nn.Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.h = nn.LayerList([GPT2Block(config)
                               for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 config.layer_norm_epsilon)

    def forward(self, input_ids, caches=None, pos=None, tables=None,
                logits_at=None):
        s = input_ids.shape[1]
        positions = creation.arange(0, s, dtype="int64")
        if pos is not None:
            positions = positions + pos.astype("int64")
        x = self.wte(input_ids) + self.wpe(positions)
        if caches is not None:
            new_caches = []
            # 2 pools per layer, or 4 under quantized KV (ISSUE 20)
            stride = len(caches) // len(self.h)
            for i, block in enumerate(self.h):
                x, kv = block(
                    x, cache=tuple(caches[stride * i:stride * (i + 1)]),
                    pos=pos, tables=tables)
                new_caches.extend(kv)
            if logits_at is not None:
                from .llama import _hidden_at
                x = _hidden_at(x, logits_at)
            return self.ln_f(x), new_caches
        x = self.drop(x)
        from ..nn.scan import scan_layers, can_scan
        dropout_live = (self.training
                        and (self.config.hidden_dropout_prob > 0
                             or self.config.attention_dropout_prob > 0))
        if not dropout_live and can_scan(self.h):
            # per-layer RNG (live dropout) forces the unrolled path
            x = scan_layers(self.h, x)
        else:
            for block in self.h:
                x = block(x)
        return self.ln_f(x)


class GPT2ForCausalLM(nn.Layer, GenerationMixin):
    """LM head ties the embedding matrix (GPT-2 convention)."""

    def __init__(self, config: GPT2Config):
        super().__init__()
        self.gpt2 = GPT2Model(config)
        self.config = config

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        cfg = self.config
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        return [creation.zeros([batch_size, max_length,
                                cfg.num_attention_heads, head_dim],
                               dtype=dtype)
                for _ in range(2 * cfg.num_hidden_layers)]

    def forward(self, input_ids, labels=None, caches=None, pos=None,
                tables=None, logits_at=None):
        """``logits_at`` (serving path only): per row the ONE position
        whose logits are wanted; the result is ``[B, 1, V]``."""
        from ..ops.linalg import matmul
        if caches is not None:
            hidden, caches = self.gpt2(input_ids, caches=caches, pos=pos,
                                       tables=tables, logits_at=logits_at)
            logits = matmul(hidden, self.gpt2.wte.weight, transpose_y=True)
            return logits, caches
        hidden = self.gpt2(input_ids)
        logits = matmul(hidden, self.gpt2.wte.weight, transpose_y=True)
        if labels is None:
            return logits
        # shift: predict token t+1 from prefix ≤ t
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        loss = F.cross_entropy(
            M.reshape(shift_logits, [-1, self.config.vocab_size]),
            M.reshape(shift_labels, [-1]))
        return logits, loss
