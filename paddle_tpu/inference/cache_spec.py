"""What a served model keeps between steps, declared per layer.

``model.cache_spec()`` returns a list of entries in the order the model's
``forward(caches=...)`` takes its arrays; :class:`ContinuousBatchingEngine`
builds its pools, their reset and the page audit from it. A model without
the method gets :func:`uniform_kv_spec`: one paged K/V pair per layer, the
layout every dense decoder here has.

The kinds: :class:`PagedKV` (host-managed pages, sized by ``max_len``),
:class:`WindowKV` (a per-slot ring sized by the layer's window; its
docstring states the ring's rule, once), :class:`SlotState` (per-slot
recurrent state) and :class:`StepCounters` (what the model counts per
pass)."""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["PagedKV", "WindowKV", "SlotState", "StepCounters",
           "uniform_kv_spec", "spec_of", "ring_pages"]


@dataclass(frozen=True)
class PagedKV:
    """One attention layer's paged K/V: two pools ``(num_pages, page_size,
    kv_heads * head_dim)`` — page axis first, a token's heads side by side
    (``ops.paged_attention.kv_pool_shape``, the one layout the write and
    the attention kernel share) — and under quantized KV two float32
    scales pools ``(num_pages, kv_heads, page_size)`` after them."""
    kv_heads: int
    head_dim: int


@dataclass(frozen=True)
class WindowKV:
    """One attention layer whose query ``i`` sees keys ``i - window + 1 ..
    i`` only: two pools in :class:`PagedKV`'s layout (and its scales pools
    under quantized KV), sized by the WINDOW and not by ``max_len``. Each
    slot owns a ring of ``R`` pages (:func:`ring_pages`): the pools are
    ``(num_slots * R + 1, page_size, kv_heads * head_dim)``, page 0 the
    trash page, and logical page ``j`` of slot ``b`` is page ``1 + b * R +
    j % R`` — a STATIC table the step program builds from that rule and
    hands the model as ``tables[2]``, so the write and the kernel address
    it like a global table and the host allocator never sees it. A token
    is overwritten ``R`` pages later, after it left every window that can
    still be asked for; a replayed request refills its ring by prefill.
    Like :class:`SlotState` a ring belongs to its slot: nothing of it can
    be shared, forked or shipped, so an engine whose spec has one serves
    without prefix cache, speculative decoding and migration."""
    kv_heads: int
    head_dim: int
    window: int


def ring_pages(window, chunk, page_size):
    """Pages of one slot's ring: enough for the keys the first query of a
    ``chunk``-token pass sees and the chunk itself, wherever the page
    boundaries fall (``window + chunk - 1`` consecutive tokens touch at
    most this many pages)."""
    return -(-(int(window) + int(chunk) - 2) // int(page_size)) + 1


@dataclass(frozen=True)
class SlotState:
    """One array ``(num_slots, *shape)`` of per-slot recurrent state.
    ``dtype`` None = the model's own. The MODEL keeps it right inside the
    step program: a slot at position 0 starts from zero, an idle slot's
    row is left as it is; the engine only allocates and rebuilds it.
    Nothing of it is paged, so nothing of it can be shared, copied on
    write or shipped: an engine whose spec has one serves without prefix
    cache, speculative decoding and migration."""
    shape: tuple
    dtype: str | None = None


@dataclass(frozen=True)
class StepCounters:
    """One int32 array ``(len(names),)`` the model ADDS to in every pass.
    The engine hands the step program zeros and reads the sums back in the
    step's one packed fetch, into counters ``serving/<name>``. The
    vocabulary is the model's: its module declares each name with its help
    text (``profiler.metrics.declare("serving/<name>", "counter", ...)``);
    the engine knows none of them and refuses an undeclared one."""
    names: tuple


def uniform_kv_spec(cfg):
    # MHA models (e.g. GPT2) carry no kv-head/head-dim fields
    kvh = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    d = getattr(cfg, "head_dim", cfg.hidden_size // cfg.num_attention_heads)
    return [PagedKV(kvh, d)] * cfg.num_hidden_layers


def spec_of(model):
    fn = getattr(model, "cache_spec", None)
    return list(fn()) if fn is not None else uniform_kv_spec(model.config)
