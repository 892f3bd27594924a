"""Fused linear + cross-entropy (the Liger/chunked-vocab trick).

The LM loss tail — ``logits = h @ W; ce(logits, labels)`` — materializes
a [N, V] logits tensor (bf16 fwd + f32 softmax + bf16 dlogits in bwd);
at N=4k, V=32k that is ~0.8GB of HBM traffic per step. This op never
materializes full logits.

Layout (round-3 rewrite): chunk over the VOCAB axis, not rows. The
first version scanned row chunks with a [D, V] f32 dW carry — 330MB
read+written every scan step plus thin (M=256) matmuls, measured 10x
slower than the plain CE tail. Vocab chunking keeps every matmul fat
([N, D] x [D, vc]), makes dW a STACKED per-chunk output (no carry
traffic), and the only carries are [N]-vectors (online logsumexp) in
forward and one [N, D] f32 dh accumulator in backward. The forward also
saves the [N] lse so backward does one pass, not two.

Round-8 additions (the training-kernel suite PR):

- The chunk width is a tunable surface (``"fused_ce"``), resolved with
  the standard precedence: an explicit ``FLAGS_fused_ce_chunk_v``
  (env/set_flags) > tuner cache > the ``_CHUNK_V`` module default
  (tests still monkeypatch ``_CHUNK_V`` to shrink chunks).
- The per-chunk softmax stats (max/exp-sum/target-gather) and the
  backward's dlogits construction route through Pallas inner kernels
  (``ops/pallas/ce_chunk.py``) on TPU, so the scan body's elementwise
  work stays in VMEM instead of round-tripping the f32 logits block
  between HLOs; ``force_pallas_inner`` pins the kernels on for
  CPU-interpret parity tests (the ``fused_parity`` gate).
"""

from __future__ import annotations

import functools
import threading as _threading

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["fused_linear_cross_entropy", "fused_ce_cost",
           "force_chunk_v", "force_pallas_inner"]

#: vocab columns per chunk (the surface DEFAULT). 1024 is the measured
#: peak-memory sweet spot at the bench tail geometry (N=8k, D=2k,
#: V=32k: 539MB vs 799MB at 4096 — the f32 logits block and its
#: elementwise temps scale with the chunk; below 1024 the matmuls
#: start going thin and the scan trip count balloons). [N, 1024] x
#: MXU tiles stay fat; the "fused_ce" tunable surface sweeps
#: 512-8192 so --autotune re-picks per shape/chip.
_CHUNK_V = 1024

_forced_tls = _threading.local()


class force_chunk_v:
    """Context manager pinning the vocab-chunk width for tuner trials
    (this thread only) — same contract as flash_attention.force_blocks:
    candidates pin HERE, not through set_flags (which would mark the
    flag user-explicit and defeat override > cache > default)."""

    def __init__(self, chunk_v):
        self._val = int(chunk_v)

    def __enter__(self):
        self._prev = getattr(_forced_tls, "chunk_v", None)
        _forced_tls.chunk_v = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.chunk_v = self._prev
        return False


class force_pallas_inner:
    """Force the Pallas chunk-stats/dlogits inner kernels regardless of
    backend (CPU runs them in interpret mode) — the fused_parity gate
    and the kernel-vs-oracle tests run under this."""

    def __enter__(self):
        self._prev = getattr(_forced_tls, "pallas_inner", None)
        _forced_tls.pallas_inner = True
        return self

    def __exit__(self, *exc):
        _forced_tls.pallas_inner = self._prev
        return False


def _resolve_chunk_v(d, v, dtype) -> int:
    """Chunk-width resolution: forced (trials) > explicit flag (env /
    set_flags — ``flag_source`` distinguishes) > tuner cache > the
    module default."""
    forced = getattr(_forced_tls, "chunk_v", None)
    if forced is not None:
        return int(forced)
    try:
        from ..framework import flags
        if flags.flag_source("FLAGS_fused_ce_chunk_v") != "default":
            val = int(flags.flag("FLAGS_fused_ce_chunk_v"))
            if val > 0:
                return val
    except KeyError:
        pass
    try:
        from ..tuner import lookup
        cfg = lookup("fused_ce", {"d": int(d), "v": int(v)}, str(dtype))
        if cfg:
            return int(cfg.get("chunk_v", _CHUNK_V))
    except Exception:
        pass
    return int(_CHUNK_V)


def _use_pallas_inner() -> bool:
    if getattr(_forced_tls, "pallas_inner", None):
        return True
    try:
        from ..framework import flags
        if not flags.flag("FLAGS_fused_ce_pallas_inner"):
            return False
    except KeyError:
        pass
    if jax.default_backend() != "tpu":
        return False
    # under a fleet mesh the inner kernels are not wrapped in a
    # shard_map (the [N, vc] block is column-sharded with a
    # vocab-parallel head): by rule, the XLA inner path there
    from .pallas._mesh import kernel_placement
    use_kernel, mesh = kernel_placement()
    return use_kernel and mesh is None


def _chunk_grid(v, chunk_v):
    """(cv, c): static chunk width (clamped to the vocab) and chunk
    count. Chunk ``ci`` covers columns ``[start, start + cv)`` with
    ``start = min(ci*cv, v - cv)`` — the LAST chunk's start clamps
    back so every slice stays in bounds and the weight is NEVER padded
    (the old ``_pad_w`` concatenated a full [D, V_pad] copy of w into
    temp memory every call); the tail chunk instead OVERLAPS its
    predecessor and masks the already-counted prefix columns
    (``col < lo``) out of the stats/grads."""
    cv = min(int(chunk_v), int(v))
    return cv, -(-int(v) // cv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_linear_cross_entropy(h, w, labels, ignore_index=-100):
    """mean CE of ``h @ w`` against ``labels`` without materializing
    logits. h: [N, D] (any float dtype), w: [D, V], labels: [N] int;
    rows with ``ignore_index`` contribute nothing (an all-ignored batch
    yields loss 0, not NaN)."""
    loss, _ = _flce_fwd(h, w, labels, ignore_index)
    return loss


def _flce_fwd(h, w, labels, ignore_index):
    n = h.shape[0]
    v = w.shape[1]
    cv, c = _chunk_grid(v, _resolve_chunk_v(w.shape[0], v, h.dtype))
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0).astype(jnp.int32)
    pallas_inner = _use_pallas_inner()

    def chunk(carry, ci):
        m, s, tgt = carry
        start = jnp.minimum(ci * cv, v - cv)
        lo = ci * cv - start          # overlap prefix, 0 except tail
        wc = lax.dynamic_slice(w, (0, start), (w.shape[0], cv))
        logits = (h @ wc).astype(jnp.float32)        # [N, vc]
        local = safe - start
        if pallas_inner:
            # one VMEM pass: chunk max / exp-sum / target gather (the
            # overlap prefix masked inside the kernel), then the
            # online-softmax carry update on [N] vectors only
            from .pallas.ce_chunk import chunk_stats
            m_c, s_c, t_c = chunk_stats(logits, local, lo)
            m_new = jnp.maximum(m, m_c)
            s = s * jnp.exp(m - m_new) + s_c * jnp.exp(m_c - m_new)
            tgt = tgt + t_c
            return (m_new, s, tgt), None
        col = jnp.arange(cv)
        lg = jnp.where(col[None, :] >= lo, logits, -jnp.inf)
        m_new = jnp.maximum(m, lg.max(-1))
        s = s * jnp.exp(m - m_new) \
            + jnp.where(col[None, :] >= lo,
                        jnp.exp(logits - m_new[:, None]), 0.0).sum(-1)
        in_chunk = (local >= lo) & (local < cv)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, cv - 1)[:, None], -1)[:, 0]
        tgt = tgt + jnp.where(in_chunk, picked, 0.0)
        return (m_new, s, tgt), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, s, tgt), _ = lax.scan(chunk, init, jnp.arange(c))
    lse = m + jnp.log(s)
    count = jnp.sum(valid.astype(jnp.float32))
    per = jnp.where(valid, lse - tgt, 0.0)
    loss = jnp.sum(per) / jnp.maximum(count, 1.0)
    return loss, (h, w, labels, lse, count)


def _flce_bwd(ignore_index, res, g):
    h, w, labels, lse, count = res
    d, v = w.shape
    cv, c = _chunk_grid(v, _resolve_chunk_v(d, v, h.dtype))
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0).astype(jnp.int32)
    scale = (g / jnp.maximum(count, 1.0)).astype(jnp.float32)
    vmask = valid.astype(jnp.float32) * scale      # [N]
    pallas_inner = _use_pallas_inner()

    def chunk(dh_acc, ci):
        start = jnp.minimum(ci * cv, v - cv)
        lo = ci * cv - start
        wc = lax.dynamic_slice(w, (0, start), (w.shape[0], cv))
        logits = (h @ wc).astype(jnp.float32)
        local = safe - start
        col = jnp.arange(cv)
        if pallas_inner:
            from .pallas.ce_chunk import chunk_dlogits
            dlogits = chunk_dlogits(logits, lse, local, vmask, lo,
                                    out_dtype=h.dtype)
        else:
            # iota-compare instead of jax.nn.one_hot: the f32
            # [N, cv+1] one-hot was a peak-memory term of its own
            p = jnp.where(col[None, :] >= lo,
                          jnp.exp(logits - lse[:, None]), 0.0)
            hit = ((col[None, :] == local[:, None])
                   & (col[None, :] >= lo)).astype(jnp.float32)
            dlogits = ((p - hit) * vmask[:, None]).astype(h.dtype)
        dh_acc = dh_acc + (dlogits @ wc.T).astype(jnp.float32)
        # [D, cv] stacked out, cast ONCE to the weight dtype here:
        # chunks partition the vocab axis (overlap prefix discarded in
        # the reconstruction below), so per-chunk casting applies the
        # same single f32->w.dtype rounding a final cast would — and
        # the stacked ys buffer is written once per step, NOT a scan
        # carry (CPU XLA double-buffers carries; an earlier [D, V]
        # dw carry measured ~2x this formulation's peak). The round-3
        # 10x-slowdown carry was an f32 full-buffer ADD — different
        # traffic pattern again.
        dw_c = (h.astype(jnp.float32).T
                @ dlogits.astype(jnp.float32)).astype(w.dtype)
        return dh_acc, dw_c

    dh, dw_chunks = lax.scan(chunk, jnp.zeros(h.shape, jnp.float32),
                             jnp.arange(c))
    if c == 1:
        dw = dw_chunks[0]
    else:
        # chunks 0..c-2 tile [0, (c-1)*cv); the clamped tail covers
        # [v - cv, v) — drop its (static-size) overlap prefix
        body = jnp.moveaxis(dw_chunks[:-1], 0, 1).reshape(d,
                                                          (c - 1) * cv)
        keep = (c - 1) * cv - (v - cv)
        dw = jnp.concatenate([body, dw_chunks[-1][:, keep:]], axis=1)
    return dh.astype(h.dtype), dw, None


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)


# -- tunable surface ---------------------------------------------------------

def _register_fused_ce_surface():
    from ..tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        v = int(shape.get("v", 1 << 30))
        return [{"chunk_v": cv} for cv in (512, 1024, 2048, 4096, 8192)
                if cv <= max(v, 1024)]

    register_surface(TunableSurface(
        name="fused_ce",
        params=("chunk_v",),
        default={"chunk_v": _CHUNK_V},
        candidates=_candidates,
        is_valid=lambda config, shape: (config["chunk_v"] % 128 == 0
                                        and config["chunk_v"] > 0),
        describe="Vocab-chunk width of the fused linear+cross-entropy "
                 "scan (trades matmul width against the live f32 "
                 "[N, chunk_v] logits block). Shape key: hidden d, "
                 "vocab v. FLAGS_fused_ce_chunk_v set explicitly "
                 "overrides any cached value."))


_register_fused_ce_surface()


def fused_ce_cost(n, d, v, train=False):
    """Static FLOPs/bytes for one fused-CE call (profiler cost-
    accounting surface). Model FLOPs only, like every estimator here:
    the backward's logits RE-matmul is real hardware work but remat-
    class recompute, deliberately not counted (profiler/cost module
    docstring)."""
    from ..profiler.cost import fused_linear_ce_cost
    return fused_linear_ce_cost(int(n), int(d), int(v), train=train)
