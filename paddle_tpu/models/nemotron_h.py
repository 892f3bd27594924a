"""Nemotron-H family (HF ``model_type`` ``nemotron_h``): a hybrid decoder
whose blocks are chosen letter by letter from ``hybrid_override_pattern`` —
``M`` a Mamba-2 mixer, ``E`` a LatentMoE layer, ``*`` GQA attention. Every
block is pre-norm with ONE mixer: ``x = x + mixer(RMSNorm(x))``; then a
final RMSNorm and an untied head. No bias except the conv's; no rotary
embedding (position comes from the Mamba layers).

What the family asks of the serving engine, and how the model says it:

- :meth:`NemotronHForCausalLM.cache_spec` declares a cache PER LAYER —
  paged K/V for ``*``, two per-slot arrays (SSM state, conv tail) for
  ``M``, nothing for ``E`` — and the engine builds its pools from that.
- Per-slot state is handled inside the forward, by rule: a slot whose
  position is 0 starts from zero state, padded positions and idle slots
  advance nothing. So continuous batching, chunked prompts and
  recompute-after-preemption need no host-side state updates.
- The expert layer is told which experts it HOLDS (``first_held_expert``,
  ``n_routed_experts_held``): it routes over all ``n_routed_experts`` and
  computes its own experts' part (expert parallelism's share, without
  the exchange). The shared expert is whole on every holder.
- Parameters are built in ``config.dtype``: the model never exists in
  float32 unless asked to. With ``config.empty_init`` they have no
  storage until real weights are loaded.
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
from ..ops import mamba2 as ssd
from ..ops import manipulation as M
from ..ops import moe as moe_ops
from ..profiler import metrics as _pmetrics
from ._leaves import _Base, _Weight
from .llama import _hidden_at, _paged_attention_step

__all__ = ["NemotronHConfig", "NemotronHForCausalLM"]

_SUPER_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                  "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

#: the SSM state between steps is float32 whatever the served dtype: the
#: recurrence compounds its rounding over every token of a stream. The
#: conv tail is kept in the activations' dtype
SSM_STATE_DTYPE = "float32"

#: what a pass through the model counts (``StepCounters``); the vocabulary
#: is this model's, so it is declared here and not in the engine
COUNTERS = moe_ops.HELD_COUNTERS + ("state_resets",)
_pmetrics.declare("serving/state_resets", "counter",
                  "slots that started a pass from zero recurrent state "
                  "(a new or replayed request's first prompt chunk)")


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _SUPER_PATTERN
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 262144      # unread: no rotary table
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # LatentMoE
    n_routed_experts: int = 512                # the router's width
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    #: the share of the routed experts this instance holds:
    #: [first_held_expert, first_held_expert + n_routed_experts_held);
    #: None = all of them
    n_routed_experts_held: int | None = None
    first_held_expert: int = 0
    #: parameters are BUILT in this dtype
    dtype: str = "float32"
    #: leaves are created WITHOUT storage — a shape and a dtype, like a
    #: meta tensor (a jax array whose buffer is released at once) — for a
    #: model whose weights are loaded next (``set_state_dict``, a
    #: benchmark's seeded weights): one too large to exist twice in device
    #: memory then never exists there before its real weights do.
    #: Touching a leaf before it is loaded raises. It is here for ONE
    #: caller, perfbench's ``weights.make_all``, which cannot free the
    #: leaves it replaces (PERF.md section 7): it goes when a `benchmark`
    #: issue mends that, and nothing else should come to lean on it.
    empty_init: bool = False

    @classmethod
    def super_120b(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=128, hidden_size=64, num_hidden_layers=5,
                   hybrid_override_pattern="ME*ME", mamba_num_heads=8,
                   mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                   chunk_size=8, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16,
                   n_routed_experts=16, num_experts_per_tok=3,
                   moe_latent_size=32, moe_intermediate_size=48,
                   moe_shared_expert_intermediate_size=64,
                   routed_scaling_factor=2.5, max_position_embeddings=128)

    @property
    def pattern(self):
        p = self.hybrid_override_pattern[:self.num_hidden_layers]
        if len(p) != self.num_hidden_layers or set(p) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} "
                f"does not give {self.num_hidden_layers} layers of M/E/*")
        return p

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def held(self):
        """(first, count) of the routed experts held here."""
        return moe_ops.held_range(self.first_held_expert,
                          self.n_routed_experts_held, self.n_routed_experts)


class _Norm(_Weight):
    def __init__(self, cfg, n):
        super().__init__(cfg, n, init=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self.cfg.layer_norm_epsilon)


def _relu2(a):
    return jnp.square(jnp.maximum(a, 0))


class _Conv(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.weight = self._p((cfg.conv_kernel, cfg.conv_dim))
        self.bias = self._p((cfg.conv_dim,), I.Constant(0.0)) \
            if cfg.use_conv_bias else None


@functools.lru_cache(maxsize=None)
def _mamba_fn(H, P, G, N, eps, chunk, has_bias, has_state):
    """The Mamba-2 mixer as ONE jitted function of its arrays (shared by
    every layer of these sizes). Jitted so that the eager first call of a
    ``to_static`` step runs it as a compiled program: op by op, the
    chunked scan's blocks would all be alive at once."""
    di = H * P

    def fn(u, w_in, cw, *rest):
        rest = list(rest)
        cb = rest.pop(0) if has_bias else None
        dt_b, a_log, d_skip, nw, w_out = rest[:5]
        rest = rest[5:]
        B_, S = u.shape[0], u.shape[1]
        cdim = cw.shape[1]
        if has_state:
            h, tail, lens, rst = rest
            h = jnp.where(rst[:, None, None, None], 0, h)
            tail = jnp.where(rst[:, None, None], 0, tail)
        else:
            h = jnp.zeros((B_, H, P, N), jnp.float32)
            tail = jnp.zeros((B_, cw.shape[0] - 1, cdim), u.dtype)
            lens = jnp.full((B_,), S, jnp.int32)
        zxd = jnp.matmul(u, w_in)
        z, xbc, dt = (zxd[..., :di], zxd[..., di:di + cdim],
                      zxd[..., di + cdim:])
        xbc, tail = ssd.causal_conv_carry(xbc, tail, cw, cb, lens)
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :di].reshape(B_, S, H, P)
        bm = xbc[..., di:di + G * N].reshape(B_, S, G, N)
        cm = xbc[..., di + G * N:].reshape(B_, S, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_b.astype(jnp.float32))
        live = jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
        a = -jnp.exp(a_log.astype(jnp.float32))
        if S == 1:
            y, h2 = ssd.ssd_step(h.astype(jnp.float32), x[:, 0], dt[:, 0],
                                 a, bm[:, 0], cm[:, 0], d_skip)
            y = y[:, None]
        else:
            y, h2 = ssd.ssd_chunked(h, x, dt, a, bm, cm, d_skip,
                                    chunk=chunk)
        y = ssd.gated_group_rms_norm(y.reshape(B_, S, di), z, nw, G,
                                     eps).astype(u.dtype)
        out = jnp.matmul(y, w_out)
        if has_state:
            return out, h2.astype(h.dtype), tail
        return out

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _moe_fn(first, k, norm, scale, has_valid, shared):
    """The LatentMoE layer as one jitted function (see ``_mamba_fn``)."""

    def fn(u, wg, bias, w_dn, w1, w2, w_up, s1, s2, *v):
        shp = u.shape
        flat = u.reshape(-1, shp[-1])
        ok = v[0].reshape(-1) if has_valid else None
        logits = jnp.matmul(flat.astype(jnp.float32),
                            wg.astype(jnp.float32))
        idx, w = moe_ops.sigmoid_top_k_router(logits, bias, k, norm, scale)
        lat = jnp.matmul(flat, w_dn)
        routed, st = moe_ops.moe_experts_held(lat, idx, w, w1, w2, first,
                                              valid=ok)
        out = jnp.matmul(routed, w_up)
        if shared:
            out = out + jnp.matmul(
                _relu2(jnp.matmul(flat, s1)).astype(u.dtype), s2)
        n_tok = jnp.sum(ok).astype(jnp.int32) if has_valid \
            else jnp.asarray(flat.shape[0], jnp.int32)
        return out.reshape(shp), jnp.concatenate([n_tok[None], st])

    return jax.jit(fn)


class Mamba2Mixer(_Base):
    """``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))``; the SSD
    recurrence over ``x, B, C`` with ``dt = softplus(dt + dt_bias)`` and
    ``A = -exp(A_log)``; ``out = GroupRMSNorm(y * silu(z)) W_out``."""

    def __init__(self, cfg):
        super().__init__(cfg)
        H, di = cfg.mamba_num_heads, cfg.d_inner
        self.in_proj = _Weight(cfg, cfg.hidden_size,
                               di + cfg.conv_dim + H)
        self.conv1d = _Conv(cfg)
        self.dt_bias = self._p((H,), I.Constant(0.0))
        self.A_log = self._p((H,), I.Constant(0.0))
        self.D = self._p((H,), I.Constant(1.0))
        self.norm = _Weight(cfg, di, init=I.Constant(1.0))
        self.out_proj = _Weight(cfg, di, cfg.hidden_size)

    def state_shapes(self):
        c = self.cfg
        return ((c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size),
                (c.conv_kernel - 1, c.conv_dim))

    def forward(self, u, state=None, lengths=None, reset=None):
        """u [B, S, hid]. ``state`` (ssm [B, H, P, N], tail [B, K-1, C])
        or None for zero state; ``lengths`` [B] int32 valid counts (None:
        all S); ``reset`` [B] bool, slots that start from zero state.
        Returns out, or (out, (ssm, tail)) when a state was given."""
        c = self.cfg
        has_bias = self.conv1d.bias is not None
        fn = _mamba_fn(c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                       c.ssm_state_size, c.layer_norm_epsilon, c.chunk_size,
                       has_bias, state is not None)
        args = [u, self.in_proj.weight, self.conv1d.weight]
        if has_bias:
            args.append(self.conv1d.bias)
        args += [self.dt_bias, self.A_log, self.D, self.norm.weight,
                 self.out_proj.weight]
        if state is not None:
            args += [state[0], state[1], lengths, reset]
            out, h, tail = apply(fn, *args, n_outputs=3,
                                 name="mamba2_mixer", differentiable=False)
            return out, (h, tail)
        return apply(fn, *args, name="mamba2_mixer")


class _Router(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.weight = self._p((cfg.hidden_size, cfg.n_routed_experts))
        self.e_score_correction_bias = self._p(
            (cfg.n_routed_experts,), I.Constant(0.0))


class _Experts(_Base):
    """The held experts' banks: ``up_proj`` [E_held, latent, inter],
    ``down_proj`` [E_held, inter, latent]; no gate matrix."""

    def __init__(self, cfg):
        super().__init__(cfg)
        n = cfg.held[1]
        self.up_proj = self._p((n, cfg.moe_latent_size,
                                cfg.moe_intermediate_size))
        self.down_proj = self._p((n, cfg.moe_intermediate_size,
                                  cfg.moe_latent_size))


class _SharedExpert(_Base):
    def __init__(self, cfg):
        super().__init__(cfg)
        inter = cfg.moe_shared_expert_intermediate_size
        self.up_proj = _Weight(cfg, cfg.hidden_size, inter)
        self.down_proj = _Weight(cfg, inter, cfg.hidden_size)


class LatentMoE(_Base):
    """Sigmoid router with a selection bias over ALL routed experts (in
    float32); the chosen experts run in a latent between a shared down-
    and up-projection: ``out = (sum_e w_e relu(v W1_e)^2 W2_e) W_up +
    relu(u S1)^2 S2`` with ``v = u W_down``. Only the held experts' pairs
    are computed here (``ops.moe.moe_experts_held``)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.gate = _Router(cfg)
        self.fc1_latent_proj = _Weight(cfg, cfg.hidden_size,
                                       cfg.moe_latent_size)
        self.experts = _Experts(cfg)
        self.fc2_latent_proj = _Weight(cfg, cfg.moe_latent_size,
                                       cfg.hidden_size)
        self.shared_experts = _SharedExpert(cfg)

    def forward(self, u, valid=None, shared=True):
        """u [B, S, hid]; ``valid`` [B, S] bool or None. Returns
        (out, stats) with stats int32 [tokens, local pairs, busiest held
        expert's pairs]. ``shared=False`` leaves the shared expert out
        (a holder other than the one that counts it)."""
        c = self.cfg
        has_valid = valid is not None
        fn = _moe_fn(c.held[0], c.num_experts_per_tok, c.norm_topk_prob,
                     float(c.routed_scaling_factor), has_valid, shared)
        args = [u, self.gate.weight, self.gate.e_score_correction_bias,
                self.fc1_latent_proj.weight, self.experts.up_proj,
                self.experts.down_proj, self.fc2_latent_proj.weight,
                self.shared_experts.up_proj.weight,
                self.shared_experts.down_proj.weight]
        if has_valid:
            args.append(valid)
        return apply(fn, *args, n_outputs=2, name="latent_moe",
                     differentiable=False)


class NemotronHAttention(_Base):
    """GQA, no bias, no rotary embedding."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        self.q_proj = _Weight(cfg, cfg.hidden_size, h * d)
        self.k_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.v_proj = _Weight(cfg, cfg.hidden_size, kvh * d)
        self.o_proj = _Weight(cfg, h * d, cfg.hidden_size)

    def _out(self, ctx):
        return F.linear(ctx, self.o_proj.weight)

    def forward(self, x, cache=None, pos=None, tables=None):
        b, s, _ = x.shape
        q = M.reshape(F.linear(x, self.q_proj.weight),
                      [b, s, self.num_heads, self.head_dim])
        k = M.reshape(F.linear(x, self.k_proj.weight),
                      [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(F.linear(x, self.v_proj.weight),
                      [b, s, self.num_kv_heads, self.head_dim])
        if cache is not None and tables is not None:
            return _paged_attention_step(self, q, k, v, cache, pos, tables,
                                         rope=False, proj=self._out)
        if cache is not None:
            ctx, kc, vc = F.sdpa_with_cache(q, k, v, cache[0], cache[1],
                                            pos)
            ctx = M.reshape(ctx, [b, s, self.num_heads * self.head_dim])
            return self._out(ctx), (kc, vc)
        ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self._out(M.reshape(
            ctx, [b, s, self.num_heads * self.head_dim]))


_MIXERS = {"M": Mamba2Mixer, "E": LatentMoE, "*": NemotronHAttention}


class NemotronHBlock(_Base):
    def __init__(self, cfg, kind):
        super().__init__(cfg)
        self.kind = kind
        self.norm = _Norm(cfg, cfg.hidden_size)
        self.mixer = _MIXERS[kind](cfg)


class NemotronHForCausalLM(_Base, GenerationMixin):
    def __init__(self, config: NemotronHConfig):
        super().__init__(config)
        self.config = config
        cfg = config
        self.embeddings = _Weight(cfg, cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([NemotronHBlock(cfg, kind)
                                    for kind in cfg.pattern])
        self.norm_f = _Norm(cfg, cfg.hidden_size)
        self.lm_head = _Weight(cfg, cfg.hidden_size, cfg.vocab_size)

    # ---- caches ----------------------------------------------------------

    def cache_spec(self):
        """One entry per cache array group, in the order ``forward`` takes
        them: per layer by its letter, then the pass counters."""
        cfg = self.config
        spec = []
        for blk in self.layers:
            if blk.kind == "*":
                spec.append(PagedKV(cfg.num_key_value_heads, cfg.head_dim))
            elif blk.kind == "M":
                ssm, tail = blk.mixer.state_shapes()
                spec.append(SlotState(ssm, SSM_STATE_DTYPE))
                spec.append(SlotState(tail, None))
        spec.append(StepCounters(COUNTERS))
        return spec

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        """Contiguous caches for ``generate``: (k, v) of [B, max_len, KV, D]
        for an attention layer, (ssm, tail) for a Mamba layer."""
        cfg = self.config
        if dtype is None:
            dtype = next(iter(self.parameters())).dtype
        caches = []
        for blk in self.layers:
            if blk.kind == "*":
                caches += [creation.zeros(
                    [batch_size, max_length, cfg.num_key_value_heads,
                     cfg.head_dim], dtype=dtype) for _ in range(2)]
            elif blk.kind == "M":
                ssm, tail = blk.mixer.state_shapes()
                caches += [
                    creation.zeros([batch_size] + list(ssm),
                                   dtype=SSM_STATE_DTYPE),
                    creation.zeros([batch_size] + list(tail), dtype=dtype)]
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
        x = F.embedding(input_ids, self.embeddings.weight)
        if caches is None:
            for blk in self.layers:
                u = blk.norm(x)
                if blk.kind == "E":
                    x = x + blk.mixer(u)[0]
                else:
                    x = x + blk.mixer(u)
            return F.linear(self.norm_f(x), self.lm_head.weight)

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
            n_outputs=3, name="nemotron_h_gates", differentiable=False)
        new, i = [], 0
        moe_stats = None
        # arrays of one attention layer: 2, or 4 under quantized KV
        kinds = self.config.pattern
        n_kv = (len(caches) - 2 * kinds.count("M") - int(paged)) \
            // max(kinds.count("*"), 1)
        for blk in self.layers:
            u = blk.norm(x)
            if blk.kind == "M":
                y, st = blk.mixer(u, state=(caches[i], caches[i + 1]),
                                  lengths=lens, reset=reset)
                new += list(st)
                i += 2
            elif blk.kind == "*":
                y, kv = blk.mixer(u, cache=tuple(caches[i:i + n_kv]),
                                  pos=pos, tables=tables)
                new += list(kv)
                i += n_kv
            else:
                y, st = blk.mixer(u, valid=valid)
                moe_stats = st if moe_stats is None else moe_stats + st
            x = x + y
        if paged and i < len(caches):
            def count(c, rst, *st):
                moe = st[0] if st else jnp.zeros((3,), jnp.int32)
                return c + jnp.concatenate(
                    [moe, jnp.sum(rst).astype(jnp.int32)[None]])

            new.append(apply(count, caches[i], reset,
                             *([moe_stats] if moe_stats is not None else []),
                             name="nemotron_h_counters",
                             differentiable=False))
        if logits_at is not None:
            x = _hidden_at(x, logits_at)
        return F.linear(self.norm_f(x), self.lm_head.weight), new
