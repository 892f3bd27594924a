"""Llama family (BASELINE configs 2/4: Llama-3-8B single chip, 70B 4D
hybrid) — the flagship model.

TPU-first: RMSNorm + RoPE + flash attention are the Pallas kernel pack
(SURVEY.md §7 step 5); GQA repeats kv heads inside the kernel; weights use
tensor-parallel layers that carry 'model'-axis NamedSharding when fleet is
initialized with mp_degree > 1, and the whole forward is
sharding-constraint-annotated so GSPMD lays out activations."""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..framework.core import Tensor, apply
from ..framework import flags
from ..distributed.communication import in_traced_collective
from .. import nn
from ..nn import functional as F
from ..ops import creation, manipulation as M
from ..ops.linalg import matmul
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..generation import GenerationMixin

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaForCausalLMPipe", "LlamaPretrainingCriterion"]


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 14336
    max_position_embeddings: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    # "full" remats whole decoder layers; "core_attn" keeps the flash
    # attention core OUT of the remat region (its custom-vjp forward
    # would otherwise re-run inside backward — ~4% of step FLOPs at
    # S=2048; saving the [B,S,H,D] context costs ~21MB/layer bf16).
    # PaddleNLP's recompute_granularity knob, TPU-tuned semantics.
    recompute_granularity: str = "full"
    # apply core_attn to every Nth layer only (1 = all): doses the saved-
    # context memory against HBM headroom — full-depth 2.4B at interval 1
    # OOMs a 16GB v5e by a few hundred MB, interval 2 fits
    core_attn_interval: int = 1
    # every k-th layer skips remat entirely (activations saved whole);
    # 0 = off — the remat-dose knob for spending leftover HBM on speed
    full_save_interval: int = 0
    tensor_parallel: bool = True  # use TP layers (degenerate w/o mesh)
    # context parallelism over the 'sep' mesh axis:
    # None | "ring" | "ulysses" | "allgather" (gathered-K/V CP — the
    # impl that also runs under the explicit 1F1B/ZB-H1 engines)
    sep_parallel: str | None = None
    # Megatron-style SP: keep LN/residual activations sequence-sharded over
    # the 'model' axis (memory win; XLA inserts the gathers)
    sequence_parallel: bool = False
    # roll the decoder stack into one lax.scan (code-size win on TPU;
    # see nn/scan.py) — turn off to unroll (e.g. heterogeneous stacks)
    scan_layers: bool = True
    # weight-only serving quantization (ISSUE 20): None keeps full
    # precision; "weight_only_int8" / "weight_only_int4" route the big
    # projections (qkv/o/gate/up/down + lm_head) through dequant-in-
    # matmul layers when nn.quant.quantize_for_serving runs at load
    weight_quant: str | None = None

    def __post_init__(self):
        # validate at construction so a typo'd granularity fails where
        # it was written, not only when the unrolled remat path runs
        if self.recompute_granularity not in ("full", "core_attn",
                                              "full_attn"):
            raise ValueError(
                f"recompute_granularity="
                f"{self.recompute_granularity!r} is not one of "
                "'full' | 'core_attn' | 'full_attn'")
        if self.weight_quant not in (None, "weight_only_int8",
                                     "weight_only_int4"):
            raise ValueError(
                f"weight_quant={self.weight_quant!r} is not one of "
                "None | 'weight_only_int8' | 'weight_only_int4'")

    @classmethod
    def llama3_8b(cls):
        return cls()

    @classmethod
    def llama3_70b(cls):
        return cls(hidden_size=8192, num_hidden_layers=80,
                   num_attention_heads=64, num_key_value_heads=8,
                   intermediate_size=28672)

    @classmethod
    def llama_1b(cls):
        """Single-v5e-chip bench config (8B does not fit 16GB HBM for
        training)."""
        return cls(vocab_size=32000, hidden_size=2048,
                   num_hidden_layers=16, num_attention_heads=16,
                   num_key_value_heads=8, intermediate_size=5632,
                   max_position_embeddings=4096, rope_theta=10000.0)

    @classmethod
    def llama_2_4b(cls):
        """Largest-fit v5e training config (2.4B params — NOT the
        Llama-2-7B checkpoint shape): with bf16 params+grads
        (2 x 2.4B x 2B = 9.6GB) plus remat'd activations it fills a 16GB
        chip; 8B (16GB params+grads alone) cannot fit — see BASELINE.md."""
        return cls(vocab_size=32000, hidden_size=2560,
                   num_hidden_layers=32, num_attention_heads=20,
                   num_key_value_heads=4, intermediate_size=6912,
                   max_position_embeddings=4096, rope_theta=10000.0,
                   use_recompute=True,
                   # keep the flash core out of remat: 99.4 vs 103.0 ms
                   # on the L4 tuning slice (v5e); +21MB/layer saved ctx,
                   # dosed to every 2nd layer to fit 16GB HBM. Requires
                   # the unrolled stack (also the faster one on-chip).
                   recompute_granularity="core_attn",
                   core_attn_interval=2,
                   scan_layers=False)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=128, max_position_embeddings=128,
                   rope_theta=10000.0)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def rope_with_offset(t, pos, max_pos, theta):
    """RoPE at absolute positions ``pos + [0..S)`` (decode-with-cache path);
    table length is the static ``max_pos`` so the traced offset only picks
    rows."""
    from ..ops.pallas import rope as rope_mod

    def fn(a, p):
        s_tab, c_tab = rope_mod.build_sin_cos(max_pos, a.shape[-1], theta)
        pid = (p.astype(jnp.int32)
               + jnp.arange(a.shape[1], dtype=jnp.int32)[None, :])
        pid = jnp.broadcast_to(pid, (a.shape[0], a.shape[1]))
        return rope_mod.apply_rope(a, s_tab, c_tab, pid)

    return apply(fn, t, pos, name="rope_cached")


def _paged_attention_step(attn, q, k, v, cache, pos, tables, rope=True,
                          proj=None, window=None):
    """Continuous-batching step over the PAGED pool, shared by the
    Llama/Qwen2/GPT2 attention layers: per-slot positions (mixed-length
    streams), trash-page routing for drained slots (serving engine
    path). ``attn`` supplies head geometry; rope=False for learned-
    position models; ``proj`` overrides the output projection
    (defaults to attn.o_proj). ``window``: a layer that attends its
    ``window`` newest keys only (``cache_spec.WindowKV``); its pools are
    per-slot rings addressed through ``tables[2]``, the ring table the
    engine hands a model whose spec has such a layer.

    ``tables`` is ``(block_tables, gate)``. The gate is per-slot
    validity: a boolean active mask (decode convention) or an int32
    VALID count (tokens of the chunk that are real) — both normalize to
    counts, and a single UNIFIED ragged path serves every shape: each
    slot's k/v tokens are written into its pages at ``ctx .. ctx +
    valid - 1`` (padding and inactive slots routed to the reserved
    trash page) and its queries attend causally over the paged history
    through ``ops.paged_attention.ragged_paged_attention`` — one
    attention entry point whether the slot carries a prefill chunk
    (valid > 1), a decode step (valid == 1) or is idle (valid == 0),
    so mixed batches compile ONE program.

    Quantized KV (ISSUE 20): a 4-tuple ``cache`` — ``(k_pages,
    v_pages, k_scales, v_scales)`` with int8/fp8 data pools and f32
    page-parallel scales pools — routes through the quantize-at-write
    / dequant-in-kernel pair instead; the quant mode rides the pool
    dtype, so this compiles the same single program shape per mode."""
    b, s = q.shape[0], q.shape[1]
    tbl, gate = tables[:2]
    if window is not None:
        tbl = tables[2]
    if rope:
        q = rope_with_offset(q, pos, attn.cfg.max_position_embeddings,
                             attn.cfg.rope_theta)
        k = rope_with_offset(k, pos, attn.cfg.max_position_embeddings,
                             attn.cfg.rope_theta)

    if len(cache) == 4:
        def fnq(qa, ka, va, kpa, vpa, ksa, vsa, tba, gatea, cta):
            from ..ops import paged_attention as PA
            ct = cta[:, 0]
            valid = gatea.astype(jnp.int32)
            kpa, vpa, ksa, vsa = PA.paged_prefill_write_quant(
                kpa, vpa, ksa, vsa, ka, va, tba, ct, valid)
            out = PA.ragged_paged_attention(qa, kpa, vpa, tba, ct,
                                            valid, k_scales=ksa,
                                            v_scales=vsa, window=window)
            return out, kpa, vpa, ksa, vsa

        ctx_out, kp2, vp2, ks2, vs2 = apply(
            fnq, q, k, v, cache[0], cache[1], cache[2], cache[3], tbl,
            gate, pos, n_outputs=5, name="paged_decode_attention_quant",
            differentiable=False)
        new_cache = (kp2, vp2, ks2, vs2)
    else:
        def fn(qa, ka, va, kpa, vpa, tba, gatea, cta):
            from ..ops import paged_attention as PA
            ct = cta[:, 0]
            valid = gatea.astype(jnp.int32)
            kpa, vpa = PA.paged_prefill_write(kpa, vpa, ka, va, tba,
                                              ct, valid)
            out = PA.ragged_paged_attention(qa, kpa, vpa, tba, ct,
                                            valid, window=window)
            return out, kpa, vpa

        ctx_out, kp2, vp2 = apply(
            fn, q, k, v, cache[0], cache[1], tbl, gate, pos,
            n_outputs=3, name="paged_decode_attention",
            differentiable=False)
        new_cache = (kp2, vp2)
    ctx_out = M.reshape(ctx_out, [b, s, attn.num_heads * attn.head_dim])
    out_proj = proj if proj is not None else attn.o_proj
    return out_proj(ctx_out), new_cache


def _hidden_at(x, logits_at):
    """Row ``logits_at[b]`` of each sequence's hidden states, ``[B, S, H]
    -> [B, 1, H]``: the serving branches take it BEFORE the final norm
    and the head, so a chunk whose one next token is wanted pays the head
    for one position, not for S."""
    def fn(a, at):
        return jnp.take_along_axis(
            a, at.astype(jnp.int32)[:, None, None], axis=1)

    return apply(fn, x, logits_at, name="hidden_at", differentiable=False)


def _alloc_kv_caches(cfg, batch_size, max_length, dtype):
    """Zero KV caches: per layer (k, v) of [B, max_len, KV, D]."""
    caches = []
    for _ in range(cfg.num_hidden_layers):
        for _kv in range(2):
            caches.append(creation.zeros(
                [batch_size, max_length, cfg.num_key_value_heads,
                 cfg.head_dim], dtype=dtype))
    return caches


def _lin(cfg, in_f, out_f, *, column, gather_output=False,
         input_is_parallel=True):
    init = nn.initializer.Normal(0.0, cfg.initializer_range)
    attr = nn.ParamAttr(initializer=init)
    if cfg.tensor_parallel:
        if column:
            return ColumnParallelLinear(in_f, out_f, weight_attr=attr,
                                        has_bias=False,
                                        gather_output=gather_output)
        return RowParallelLinear(in_f, out_f, weight_attr=attr,
                                 has_bias=False,
                                 input_is_parallel=input_is_parallel)
    return nn.Linear(in_f, out_f, weight_attr=attr, bias_attr=False)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.q_proj = _lin(cfg, cfg.hidden_size,
                           self.num_heads * self.head_dim, column=True)
        self.k_proj = _lin(cfg, cfg.hidden_size,
                           self.num_kv_heads * self.head_dim, column=True)
        self.v_proj = _lin(cfg, cfg.hidden_size,
                           self.num_kv_heads * self.head_dim, column=True)
        self.o_proj = _lin(cfg, self.num_heads * self.head_dim,
                           cfg.hidden_size, column=False)

    def forward(self, x, sin_cos=None, cache=None, pos=None, tables=None):
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
        from ..distributed.fleet.meta_parallel.context_parallel import (
            sep_attention, sep_attention_manual, sep_axis_is_manual)
        sep_manual = (self.cfg.sep_parallel is not None
                      and sep_axis_is_manual())
        if not sep_manual:
            from ..incubate.nn.functional import \
                fused_rotary_position_embedding
            q, k, _ = fused_rotary_position_embedding(
                q, k, None, rotary_emb_base=self.cfg.rope_theta)
        if sep_manual:
            # 5D hybrid: inside the compiled pipeline's manual region
            # the sequence is physically local — rope needs global
            # positions, applied inside the wrapper from the bound
            # 'sep' axis index
            ctx = sep_attention_manual(
                q, k, v, rope_theta=self.cfg.rope_theta,
                causal=True, impl=self.cfg.sep_parallel)
        elif self.cfg.sep_parallel is not None:
            ctx = sep_attention(q, k, v, causal=True,
                                impl=self.cfg.sep_parallel)
        else:
            ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        ctx = M.reshape(ctx, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(ctx)


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = _lin(cfg, cfg.hidden_size, cfg.intermediate_size,
                              column=True)
        self.up_proj = _lin(cfg, cfg.hidden_size, cfg.intermediate_size,
                            column=True)
        self.down_proj = _lin(cfg, cfg.intermediate_size, cfg.hidden_size,
                              column=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        if cfg.sequence_parallel:
            from ..distributed.fleet.utils import \
                mark_as_sequence_parallel_parameter
            for p in self.input_layernorm.parameters():
                mark_as_sequence_parallel_parameter(p)
            for p in self.post_attention_layernorm.parameters():
                mark_as_sequence_parallel_parameter(p)

    def _sp(self, t):
        if not self.cfg.sequence_parallel:
            return t
        from ..distributed.fleet.utils import ScatterOp
        return ScatterOp(t, axis=1)

    def forward(self, x, cache=None, pos=None, tables=None):
        if cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x),
                                             cache=cache, pos=pos,
                                             tables=tables)
            x = x + attn
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        if self.cfg.sequence_parallel or self.cfg.sep_parallel is not None:
            x = x + self.self_attn(self._sp(self.input_layernorm(x)))
            x = x + self.mlp(self._sp(self.post_attention_layernorm(x)))
            return x
        # plain path: composed from the SAME stages core_attn remat uses,
        # so there is exactly one copy of the qkv/rope/residual wiring
        q, k, v = self._qkv_stage(x)
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self._post_stage(x, ctx)

    # ---- core_attn selective remat (see LlamaConfig.recompute_granularity)
    def _qkv_from(self, h):
        """q/k/v projections + rope from an already-normed input —
        the single copy of the projection wiring, shared by the plain,
        core_attn-remat and fused-residual paths."""
        a = self.self_attn
        b, s, _ = h.shape
        q = M.reshape(a.q_proj(h), [b, s, a.num_heads, a.head_dim])
        k = M.reshape(a.k_proj(h), [b, s, a.num_kv_heads, a.head_dim])
        v = M.reshape(a.v_proj(h), [b, s, a.num_kv_heads, a.head_dim])
        from ..incubate.nn.functional import \
            fused_rotary_position_embedding
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, rotary_emb_base=a.cfg.rope_theta)
        return q, k, v

    def _qkv_stage(self, x):
        return self._qkv_from(self.input_layernorm(x))

    def _post_stage(self, x, ctx):
        a = self.self_attn
        b, s, _ = x.shape
        ctx = M.reshape(ctx, [b, s, a.num_heads * a.head_dim])
        x = x + a.o_proj(ctx)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x

    def forward_core_attn_remat(self, x):
        """Remat the projections/norms/MLP but keep the flash-attention
        core OUTSIDE the checkpoint region: its output is a saved
        residual, so backward never re-runs the attention forward (the
        custom-vjp kernel is opaque to the dots_saveable policy)."""
        from ..incubate.recompute import recompute
        a = self.self_attn
        q, k, v = recompute(
            self._qkv_stage, x, n_outputs=3,
            params_from=[self.input_layernorm, a.q_proj, a.k_proj,
                         a.v_proj])
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return recompute(
            self._post_stage, x, ctx,
            params_from=[a.o_proj, self.post_attention_layernorm,
                         self.mlp])

    # ---- fused residual+norm carry (FLAGS_fused_rmsnorm_residual) --------
    # The unfused stack computes ``x1 = x + attn(norm1(x)); x2 = x1 +
    # mlp(norm2(x1))`` — each residual add is immediately followed by
    # an RMSNorm (the next layer's norm1 for the mlp add). The fused
    # path therefore carries the UN-ADDED pair (hidden, residual)
    # between layers so every add+norm pair lowers into ONE fused
    # kernel (ops/pallas/rms_norm.rms_norm_residual on TPU): layer i's
    # mlp output + residual stream fuse into layer i+1's input_layernorm
    # and the attention output + residual fuse into
    # post_attention_layernorm; LlamaModel fuses the final add into the
    # last norm. Addition commutes, so the carry is numerics-identical
    # to the sequential adds.

    def _norm_pair(self, norm, hidden, residual):
        """(normed, summed) for the add+norm pair; a None residual
        (stack entry) degrades to the plain norm with the hidden
        itself as the stream."""
        if residual is None:
            return norm(hidden), hidden
        return F.fused_rms_norm_residual(hidden, residual, norm.weight,
                                         norm.epsilon)

    def forward_fused(self, hidden, residual=None):
        """One decoder layer over the (hidden, residual) carry; returns
        the next un-added pair ``(mlp_out, attn_residual_stream)``."""
        y1, r = self._norm_pair(self.input_layernorm, hidden, residual)
        q, k, v = self._qkv_from(y1)
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self._post_stage_fused(ctx, r)

    def _qkv_stage_fused(self, hidden, residual=None):
        y1, r = self._norm_pair(self.input_layernorm, hidden, residual)
        q, k, v = self._qkv_from(y1)
        return q, k, v, r

    def _post_stage_fused(self, ctx, r):
        a = self.self_attn
        b, s, _ = r.shape
        ctx = M.reshape(ctx, [b, s, a.num_heads * a.head_dim])
        y2, r2 = self._norm_pair(self.post_attention_layernorm,
                                 a.o_proj(ctx), r)
        return self.mlp(y2), r2

    def forward_fused_core_attn_remat(self, hidden, residual):
        """core_attn selective remat over the fused carry: same
        checkpoint regions as :meth:`forward_core_attn_remat`, with the
        fused residual+norm kernels INSIDE them — backward recompute
        re-runs the fused kernels, not an unfused expansion."""
        from ..incubate.recompute import recompute
        a = self.self_attn
        qkv_params = [self.input_layernorm, a.q_proj, a.k_proj, a.v_proj]
        if residual is None:
            q, k, v, r = recompute(self._qkv_stage_fused, hidden,
                                   n_outputs=4, params_from=qkv_params)
        else:
            q, k, v, r = recompute(self._qkv_stage_fused, hidden,
                                   residual, n_outputs=4,
                                   params_from=qkv_params)
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return recompute(
            self._post_stage_fused, ctx, r, n_outputs=2,
            params_from=[a.o_proj, self.post_attention_layernorm,
                         self.mlp])


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
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
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None, pos=None, tables=None,
                skip_layers=None, logits_at=None):
        x = self.embed_tokens(input_ids)
        if caches is not None:
            # skip_layers (speculative decoding, ISSUE 18): the listed
            # decoder layers are passed through entirely — hidden state
            # AND their KV caches flow unchanged — giving a cheap
            # self-speculative draft model over the same weights
            # (LayerSkip-style early exit). Serving-path only.
            skip = frozenset(skip_layers) if skip_layers else frozenset()
            new_caches = []
            # 2 pools per layer (k, v), or 4 under quantized KV
            # (k, v, k_scales, v_scales) — ISSUE 20
            stride = len(caches) // len(self.layers)
            for i, layer in enumerate(self.layers):
                lc = tuple(caches[stride * i:stride * (i + 1)])
                if i in skip:
                    new_caches.extend(lc)
                    continue
                x, kv = layer(x, cache=lc, pos=pos, tables=tables)
                new_caches.extend(kv)
            if logits_at is not None:
                x = _hidden_at(x, logits_at)
            return self.norm(x), new_caches
        if skip_layers:
            raise ValueError("skip_layers requires the caches "
                             "(serving) path")
        from ..nn.scan import scan_layers, can_scan
        if getattr(self.config, "scan_layers", True) and \
                can_scan(self.layers):
            if (getattr(self.config, "recompute_granularity", "full")
                    != "full"
                    and self.config.use_recompute
                    and self.training):
                import warnings
                warnings.warn(
                    "recompute_granularity is ignored under "
                    "scan_layers=True (the scan body remats whole "
                    "layers); set scan_layers=False for selective remat",
                    stacklevel=2)
            # one lax.scan over stacked per-layer weights: code size (the
            # measured TPU bottleneck for unrolled stacks) stays that of
            # a single layer; remat folds in as checkpointed scan body,
            # and the remat DOSE (full_save_interval) as fs-layer scan
            # groups whose last layer saves whole (nn/scan.py)
            x = scan_layers(self.layers, x,
                            remat=self.config.use_recompute
                            and self.training,
                            full_save_interval=getattr(
                                self.config, "full_save_interval", 0))
        else:
            gran = getattr(self.config, "recompute_granularity", "full")
            if gran not in ("full", "core_attn", "full_attn"):
                raise ValueError(
                    f"recompute_granularity={gran!r} is not one of "
                    "'full' | 'core_attn' | 'full_attn'")
            # PaddleNLP's 'full_attn' (save the attention, recompute the
            # rest) maps to the same TPU structure as core_attn
            selective = (
                gran in ("core_attn", "full_attn")
                and self.config.sep_parallel is None
                and not self.config.sequence_parallel)
            interval = max(
                int(getattr(self.config, "core_attn_interval", 1)), 1)
            # remat DOSE: every k-th layer keeps its activations whole
            # (no recompute at all) — spends leftover HBM to cut the
            # backward's re-forward time. 0 = off.
            fs = max(int(getattr(self.config, "full_save_interval", 0)),
                     0)
            # fused residual+norm carry (LlamaDecoderLayer.forward_fused
            # block comment): every add+norm pair — including the final
            # norm — lowers into one fused kernel. Only on the unrolled
            # stack (the on-chip bench path); the scan body keeps the
            # single-tensor carry.
            fused = (flags.flag("FLAGS_fused_rmsnorm_residual")
                     and self.config.sep_parallel is None
                     and not self.config.sequence_parallel)
            if fused:
                hidden, residual = x, None
                from ..incubate.recompute import recompute
                for i, layer in enumerate(self.layers):
                    if self.config.use_recompute and self.training:
                        if fs and i % fs == fs - 1:
                            hidden, residual = layer.forward_fused(
                                hidden, residual)
                        elif selective and i % interval == 0:
                            hidden, residual = \
                                layer.forward_fused_core_attn_remat(
                                    hidden, residual)
                        elif residual is None:
                            hidden, residual = recompute(
                                layer.forward_fused, hidden,
                                n_outputs=2, params_from=layer)
                        else:
                            hidden, residual = recompute(
                                layer.forward_fused, hidden, residual,
                                n_outputs=2, params_from=layer)
                    else:
                        hidden, residual = layer.forward_fused(
                            hidden, residual)
                if residual is None:
                    return self.norm(hidden)
                y, _ = F.fused_rms_norm_residual(
                    hidden, residual, self.norm.weight,
                    self.norm.epsilon)
                return y
            for i, layer in enumerate(self.layers):
                if self.config.use_recompute and self.training:
                    if fs and i % fs == fs - 1:
                        x = layer(x)
                    elif selective and i % interval == 0:
                        x = layer.forward_core_attn_remat(x)
                    else:
                        from ..incubate.recompute import recompute
                        x = recompute(layer, x)
                else:
                    x = layer(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = _lin(config, config.hidden_size,
                                config.vocab_size, column=True,
                                gather_output=True)
        else:
            self.lm_head = None

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        return _alloc_kv_caches(self.config, batch_size, max_length, dtype)

    def forward(self, input_ids, labels=None, caches=None, pos=None,
                tables=None, skip_layers=None, logits_at=None):
        """``logits_at`` (serving path only): per row the ONE position
        whose logits are wanted; the result is ``[B, 1, V]``."""
        if caches is not None:
            hidden, caches = self.llama(input_ids, caches=caches, pos=pos,
                                        tables=tables,
                                        skip_layers=skip_layers,
                                        logits_at=logits_at)
        else:
            hidden = self.llama(input_ids)
        if labels is not None and caches is None and \
                not in_traced_collective():
            # the head fused with the shifted CE: no [N, V] logits
            # (ops/fused_ce.py); the labeled training forward returns
            # (None, loss). Inside a traced collective the head is
            # vocab-parallel and takes the plain tail below.
            from ..ops.fused_ce import lm_head_loss
            if self.lm_head is not None:
                return None, lm_head_loss(hidden, self.lm_head.weight,
                                          labels, vocab_first=False)
            return None, lm_head_loss(hidden, self.llama.embed_tokens.weight,
                                      labels, vocab_first=True)
        if self.lm_head is not None:
            logits = self.lm_head(hidden)
        else:
            logits = matmul(hidden, self.llama.embed_tokens.weight,
                            transpose_y=True)
        if caches is not None:
            return logits, caches
        if labels is None:
            return logits
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        loss = F.cross_entropy(
            M.reshape(shift_logits, [-1, self.config.vocab_size]),
            M.reshape(shift_labels, [-1]))
        return logits, loss


# ---------------------------------------------------------------------------
# Pipeline-parallel Llama (the reference's PaddleNLP LlamaForCausalLMPipe
# shape — BASELINE config 4's 4D hybrid workload). The decoder stack is the
# uniform pipeline body: PipelineParallel stacks the per-layer weights
# [S, ...] over the 'pipe' mesh axis while each layer's TP layers keep their
# 'model'-axis sharding and the optimizer state stays ZeRO-sharded over
# 'sharding' — one compiled program, all four axes live.
# ---------------------------------------------------------------------------


class LlamaEmbeddingPipe(nn.Layer):
    """Pipeline prologue: token embedding (vocab-parallel under TP)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        if cfg.tensor_parallel:
            self.embed_tokens = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))
        else:
            self.embed_tokens = nn.Embedding(
                cfg.vocab_size, cfg.hidden_size,
                weight_attr=nn.ParamAttr(initializer=init))

    def forward(self, input_ids):
        return self.embed_tokens(input_ids)


class LlamaHeadPipe(nn.Layer):
    """Pipeline epilogue: final RMSNorm + LM head -> logits."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = _lin(cfg, cfg.hidden_size, cfg.vocab_size,
                            column=True, gather_output=True)

    def forward(self, hidden):
        return self.lm_head(self.norm(hidden))


class LlamaPretrainingCriterion(nn.Layer):
    """Shifted next-token cross entropy — identical numerics to
    ``LlamaForCausalLM``'s labeled forward, so pipelined training is
    loss-parity-comparable against the monolithic model.

    ``fuses_with_network_loss`` certifies exactly that contract to
    ``hapi.Model``: ``network(x, labels=y)[1]`` equals
    ``criterion(network(x), y)``, so the compiled fit step may route
    labels into the network and let its fused head + cross-entropy
    tail (ops/fused_ce.py) skip the [N, V] logits."""

    fuses_with_network_loss = True

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.vocab_size = cfg.vocab_size

    def forward(self, logits, labels):
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        return F.cross_entropy(
            M.reshape(shift_logits, [-1, self.vocab_size]),
            M.reshape(shift_labels, [-1]))


def LlamaForCausalLMPipe(config: LlamaConfig, **pipeline_kwargs):
    """Build the pipelined Llama as a ``PipelineLayer``.

    Layer construction order (embedding, decoder stack, norm+head) matches
    ``LlamaForCausalLM`` exactly, so with the same seed both models draw
    identical initial weights — the basis of every parity test. Pass
    ``num_virtual_pipeline_stages`` etc. through ``pipeline_kwargs``."""
    from ..distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    descs = [LayerDesc(LlamaEmbeddingPipe, config)] + \
        [LayerDesc(LlamaDecoderLayer, config)
         for _ in range(config.num_hidden_layers)] + \
        [LayerDesc(LlamaHeadPipe, config)]
    pipeline_kwargs.setdefault("loss_fn", LlamaPretrainingCriterion(config))
    pipeline_kwargs.setdefault(
        "recompute_interval", 1 if config.use_recompute else 0)
    return PipelineLayer(descs, **pipeline_kwargs)
