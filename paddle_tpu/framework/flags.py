"""Runtime flag registry — the role of Paddle's gflags-workalike
(``paddle/phi/core/flags.h`` / ``PHI_DEFINE_EXPORTED_*``, UNVERIFIED).

Flags are defined in Python, ingested from ``FLAGS_*`` environment variables
at import, readable/mutable at runtime via ``get_flags``/``set_flags``
(mirroring ``paddle.get_flags``/``paddle.set_flags``).

Tuner interplay (docs/autotune.md): every flag records its value's
*source* — ``"default"`` (the define_flag literal), ``"env"`` (a
``FLAGS_*`` environment variable at import) or ``"set"`` (a runtime
``set_flags`` call). Knobs that are also tunable surfaces (e.g.
``FLAGS_flash_attn_block_q/kv``) resolve with the precedence

    explicit user value (env or set_flags)  >  tuner cache  >  default

so an operator pinning a block size always wins over a searched
config, and a searched config only ever replaces the built-in default
(:func:`flag_source` is how call sites distinguish the cases).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "flag_source",
           "scoped_default"]

_lock = threading.Lock()
_registry: dict[str, dict] = {}


def _parse_env(value: str, typ):
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    return typ(value)


def define_flag(name: str, default: Any, help: str = "",
                typ: type | None = None,
                on_change: Callable[[Any], None] | None = None) -> None:
    """Define ``FLAGS_<name>``. Reads initial value from env if present."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    typ = typ if typ is not None else type(default)
    value = default
    source = "default"
    env = os.environ.get(name)
    if env is not None:
        try:
            value = _parse_env(env, typ)
            source = "env"
        except (TypeError, ValueError):
            pass
    with _lock:
        _registry[name] = {"value": value, "default": default, "help": help,
                           "type": typ, "on_change": on_change,
                           "source": source}


def flag(name: str) -> Any:
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _registry[name]["value"]


def flag_source(name: str) -> str:
    """Where the flag's current value came from: ``"default"`` |
    ``"env"`` | ``"set"``. Anything but ``"default"`` is an explicit
    user choice, which beats tuner-cache values (module docstring)."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _registry[name].get("source", "default")


def get_flags(flags: str | list[str] | None = None) -> dict[str, Any]:
    if flags is None:
        return {k: v["value"] for k, v in _registry.items()}
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f if f.startswith("FLAGS_") else "FLAGS_" + f
        out[key] = _registry[key]["value"]
    return out


def set_flags(flags: dict[str, Any]) -> None:
    for k, v in flags.items():
        key = k if k.startswith("FLAGS_") else "FLAGS_" + k
        with _lock:
            if key not in _registry:
                # Paddle tolerates unknown flags with a warning; we register.
                _registry[key] = {"value": v, "default": v, "help": "",
                                  "type": type(v), "on_change": None,
                                  "source": "set"}
                continue
            ent = _registry[key]
            ent["value"] = ent["type"](v) if not isinstance(v, ent["type"]) else v
            ent["source"] = "set"
            cb = ent["on_change"]
        if cb is not None:
            cb(v)


class scoped_default:
    """Context manager: give ``name`` a different DEFAULT for the scope.

    The new value applies only while the flag's current value came from
    the ``define_flag`` literal — an explicit env var or ``set_flags``
    call always wins (the module-docstring precedence), and the source
    stays ``"default"`` so tuner-cache resolution is unaffected. Value
    and source are restored on exit. This is how ``Model.fit`` turns
    ``FLAGS_fused_linear_cross_entropy`` on for the compiled hot path
    without overriding an operator's explicit choice."""

    def __init__(self, name: str, value: Any):
        self._name = name if name.startswith("FLAGS_") else \
            "FLAGS_" + name
        self._value = value
        self._applied = False

    def __enter__(self):
        cb = val = None
        with _lock:
            ent = _registry[self._name]
            self._prev = ent["value"]
            if ent["source"] == "default":
                ent["value"] = val = ent["type"](self._value)
                self._applied = True
                cb = ent["on_change"]
        # fire on_change outside the lock, same contract as set_flags —
        # callback-maintained state must track the scoped value too
        if self._applied and cb is not None:
            cb(val)
        return self

    def __exit__(self, *exc):
        cb = None
        restored = False
        with _lock:
            ent = _registry[self._name]
            # only roll back our own write: a set_flags inside the scope
            # is an explicit user choice and must survive
            if self._applied and ent["source"] == "default":
                ent["value"] = self._prev
                restored = True
                cb = ent["on_change"]
        if restored and cb is not None:
            cb(self._prev)
        return False


# -- core flags (mirroring commonly-used FLAGS_* names where sensible) ------
define_flag("FLAGS_check_nan_inf", False,
            "Check outputs for NaN/Inf after each op (debug).")
define_flag("FLAGS_cudnn_deterministic", False,
            "Determinism knob (XLA is deterministic by default; accepted for "
            "compatibility).")
define_flag("FLAGS_use_stride_kernel", False, "Accepted for compatibility.")
define_flag("FLAGS_embedding_deterministic", 0, "Accepted for compatibility.")
define_flag("FLAGS_allocator_strategy", "auto_growth",
            "Allocator strategy (PJRT owns allocation on TPU).")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92,
            "Accepted for compatibility; PJRT flag controls TPU memory.")
define_flag("FLAGS_log_level", 1, "Framework log verbosity.")


def _toggle_host_trace(value):
    # lazy import: flags load before the profiler package exists. The
    # flag toggle never writes files; use profiler.disable() directly
    # for an export on stop.
    from ..profiler import disable, enable
    enable() if value else disable(export=False)


define_flag("FLAGS_enable_host_trace", False,
            "Structured host trace layer (paddle_tpu.profiler.trace): "
            "spans/gauges recorded process-wide, chrome-trace export on "
            "disable. Same switch as PADDLE_PROFILER_TRACE=1.",
            on_change=_toggle_host_trace)
define_flag("FLAGS_host_trace_level", 1,
            "Reserved verbosity knob for the host trace layer (parity "
            "with the reference profiler's FLAGS_host_trace_level; the "
            "structured tracer currently records all spans when "
            "enabled).")
define_flag("FLAGS_tpu_matmul_precision", "default",
            "Matmul precision: default|high|highest (maps to jax precision).")
define_flag("FLAGS_enable_pallas_kernels", True,
            "Use Pallas kernels (flash-attn, rms_norm, rope) when on TPU.")
# 256/512 measured best on v5e at hidden 2560 under remat (59.3% vs
# 57.4% MFU at 512/512 on the 4-layer tuning slice, 2026-07-31; the
# earlier 512/512 pick was tuned on the no-remat 0.89B config). Both
# kernels clamp to the padded sequence length. These are tunable
# surfaces ("flash_attention", paddle_tpu.tuner): an explicit env /
# set_flags value wins over a tuner-cache entry, which wins over the
# defaults here (flag_source distinguishes them).
define_flag("FLAGS_flash_attn_block_q", 256, "Pallas flash-attn q block.")
define_flag("FLAGS_flash_attn_block_kv", 512, "Pallas flash-attn kv block.")
define_flag("FLAGS_recompute_policy", "dots_saveable",
            "jax.checkpoint policy for recompute()/use_recompute: "
            "dots_saveable (default) | nothing_saveable | "
            "dots_with_no_batch_dims_saveable | everything_saveable.")
define_flag("FLAGS_flash_attn_pallas_bwd", True,
            "Flash-attn backward via the hand-written Pallas dkv/dq "
            "kernels (False = blockwise lax.scan recompute fallback).")
define_flag("FLAGS_use_pallas_ragged_attention", 1,
            "Every paged-attention entry point (the serving batching "
            "step, and ops.paged_attention.paged_attention = the same "
            "call at one query token): use the Pallas ragged "
            "paged-attention kernel (mixed prefill+decode, ONE "
            "program) on TPU (0 = jnp gather/softmax reference path).")
# These are a tunable surface ("ragged_paged_attention",
# paddle_tpu.tuner): an explicit env / set_flags value wins over a
# tuner-cache entry, which wins over the defaults here.
define_flag("FLAGS_ragged_attn_q_block", 16,
            "Ragged paged-attention: stream tokens per q program.")
define_flag("FLAGS_ragged_attn_kv_pages", 0,
            "Ragged paged-attention: KV pages per DMA compute block "
            "(0 = sized to the static shape by the kernel's module: 512 "
            "keys for a decode step, 128-256 for a prefill group).")
define_flag("FLAGS_fused_linear_cross_entropy", False,
            "LM training loss: chunked fused lm_head-matmul +"
            " cross-entropy that never materializes [N, V] logits "
            "(ops/fused_ce.py); the labeled forward then returns "
            "(None, loss). Module default OFF for the bare labeled "
            "forward, but hapi.Model.fit(compiled=True) turns it on "
            "for the compiled hot path via flags.scoped_default (the "
            "memory headroom is what buys bigger per-chip batches "
            "there); an explicit env/set_flags value wins either way. "
            "fit(compiled=False) stays the eager UNFUSED parity "
            "oracle.")
define_flag("FLAGS_fused_ce_chunk_v", 1024,
            "Fused linear+CE vocab-chunk width. This is a tunable "
            "surface ('fused_ce', paddle_tpu.tuner): an explicit env/"
            "set_flags value wins over a tuner-cache entry, which wins "
            "over this default (flag_source distinguishes).")
define_flag("FLAGS_fused_ce_pallas_inner", True,
            "Fused linear+CE: run the per-chunk softmax stats and "
            "backward dlogits through the Pallas inner kernels "
            "(ops/pallas/ce_chunk.py) on TPU, keeping the scan body's "
            "elementwise work in VMEM (0 = pure jnp scan body).")
define_flag("FLAGS_fused_rmsnorm_residual", True,
            "Decoder hot path: fuse each residual-add with the "
            "following RMSNorm (ops/pallas/rms_norm.rms_norm_residual "
            "on TPU; identical-math jnp pairing elsewhere). The Llama "
            "unrolled stack carries a (hidden, residual) pair so BOTH "
            "norm+residual pairs per layer fuse; Qwen2/DeepSeek fuse "
            "the post-attention pair in place.")
define_flag("FLAGS_fused_swiglu", True,
            "MLP hot path: silu(gate)*up through the fused Pallas "
            "SwiGLU kernel on TPU (one VMEM pass fwd, fused dgate/dup "
            "bwd, no silu intermediate saved); jnp composition "
            "elsewhere.")
