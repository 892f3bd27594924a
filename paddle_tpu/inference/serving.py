"""Continuous-batching LLM serving engine over paged KV caches.

Reference role: the serving layer PaddleNLP/FastDeploy put on top of
Paddle Inference (dynamic batching + paged/ragged KV attention for mixed-
length streams; reference mount empty, no cites — SURVEY.md §2.1
inference row, PAPERS.md ragged-paged-attention).

TPU-native design — the vLLM recipe restructured for XLA's static-shape
world: ONE compiled batching-step program for the whole scheduler turn,
built on the ragged paged-attention entry point (PAPERS.md "Ragged
Paged Attention"). It computes the positions whose result the turn
uses: a loop over groups of prefilling slots, its trip count the turn's
own data, streams each group's next ``prefill_chunk`` prompt tokens
through one ``[group, prefill_chunk]`` forward whose head reads one row
per slot, and samples where a prompt completes; then ``decode_chunk``
in-program decode micro-steps via ``lax.scan`` advance the decoding
slots. Prefill→decode transition happens ON DEVICE inside the program
(a slot whose prompt ends in the loop joins the scan after micro-step
0), so steady-state ``compiled_programs`` == 1. A speculative engine
(``spec_decode=True``) runs a sibling program in its place: one mixed
pass that verifies host-proposed drafts (:meth:`_unified_spec_static`).

Structure:

- The KV cache is a global PAGE POOL per layer ([num_pages,
  page_size, KVH * D]); each admitted request owns a page list (its block
  table row). Page 0 is a reserved trash page for drained slots.
- PREFIX CACHE (ISSUE 12, default on): completed prefills publish
  their full prompt pages into a radix index keyed by token blocks at
  ``page_size`` granularity; an admitted request whose prompt prefix
  is resident ATTACHES the existing physical pages (refcounted,
  read-shared) and chunk-prefills only its unseen suffix — a fully-
  cached prompt COW-forks the last shared page to recompute its final
  token's logits. Eviction is refcount-aware LRU over unreferenced
  cache pages, composed with the deferred-free discipline below; the
  ``PADDLE_TPU_SERVING_AUDIT`` invariant extends to shared pages
  (free + private + cache + deferred + trash == num_pages, refcounts
  exact).
- A fixed number of SLOTS (the batch dimension) keeps every compiled
  shape static. Admission = host-side: allocate pages from the free
  list and mark the slot PREFILLING.
- Prefill is CHUNKED and BATCHED through the paged pool: every
  prefilling slot (up to ``admit_batch`` of them) advances
  ``prefill_chunk`` prompt tokens per step — k/v are written into the
  slot's pages incrementally and the chunk's queries attend causally
  over the paged history (``ops.paged_attention.ragged_paged_attention``).
  Every prompt length flows through the same program, beside the
  decoding slots, so a long prompt does not stall active streams.
- Between steps the host scheduler drains finished slots (eos or token
  budget), frees their pages, and admits queued requests into the freed
  slots — mixed-length streams flow through without ever reshaping the
  compiled program.
- Hot state (last token / context length / active mask / RNG key / page
  pools) is DEVICE-RESIDENT between programs: steps chain device state
  asynchronously, and each step's ONE packed int32 fetch carries every
  token it emitted (a prompt's first token among them) plus the
  ctx/active mirrors.
- What the HOST decides — block-table rows, context limits, stop tokens,
  the turn's prompt chunks — lives in numpy arrays and rides the turn's
  ONE upload (:class:`_TurnUpload`); admission and eviction run no
  device program. A slot they rebind also stages a context reset, which
  the step program applies to its chained ctx/active state before
  anything else.
- Two pumps drive the step: :meth:`step` (dispatch, then harvest; what
  ``ApiServer``, the fleet replicas and the benchmark call) and
  :meth:`run` (the same turn, with the successor dispatched before the
  harvest).
- Per-request latency accounting rides the scheduler: TTFT (arrival →
  first token on host) and smoothed inter-token latency, exposed as
  p50/p99 gauges next to the occupancy/overlap counters from PR 2, plus
  a compiled-signature counter (``compiled_programs``) that the
  compile-budget CI gate asserts on.
"""

from __future__ import annotations

import contextlib
import time
import zlib
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, no_grad
from ..profiler import flight_recorder as _frec
from ..profiler import metrics as _pmetrics
from ..profiler.trace import get_trace_log as _get_trace_log
from ..profiler.trace import trace_span as _span
from .reliability import (MAX_HOPS as _MAX_HOPS, DeadlineExceeded,
                          RequestCancelled, RequestQuarantined,
                          record_hop)

__all__ = ["ContinuousBatchingEngine", "ServedRequest",
           "record_hop", "request_trace_summary"]

# the serving metric vocabulary (docs/observability.md table;
# tools/check_metric_names.py lints these literals). Each engine owns
# a PRIVATE MetricsRegistry instance of these — two engines in one
# process never cross-pollute.
_pmetrics.declare("serving/chunks", "counter",
                  "compiled step programs dispatched (unified and "
                  "speculative steps)")
_pmetrics.declare("serving/chunk_slot_steps", "counter",
                  "slot-steps dispatched (num_slots x chunk length, "
                  "active or not)")
_pmetrics.declare("serving/active_slot_steps", "counter",
                  "slot-steps belonging to slots that could advance at "
                  "dispatch")
_pmetrics.declare("serving/tokens_emitted", "counter",
                  "generated tokens delivered to requests")
_pmetrics.declare("serving/prefills", "counter",
                  "requests admitted into a slot")
_pmetrics.declare("serving/prefills_overlapped", "counter",
                  "admissions made while a compiled program was in "
                  "flight (overlap pipeline)")
_pmetrics.declare("serving/prefill_waves", "counter",
                  "programs that carried prompt tokens")
_pmetrics.declare("serving/chunks_empty", "counter",
                  "harvested programs that delivered no tokens "
                  "(unpredictable eos stops)")
_pmetrics.declare("serving/unified_steps", "counter",
                  "batching-step programs dispatched (speculative "
                  "steps included)")
_pmetrics.declare("serving/requests_completed", "counter",
                  "requests finished (eos or length)")
_pmetrics.declare("serving/run_seconds", "counter",
                  "wall seconds spent inside scheduler turns (step() "
                  "calls and run()'s loop iterations)")
_pmetrics.declare("serving/prefill_tokens", "counter",
                  "prompt tokens carried by dispatched programs (sum "
                  "of the per-slot chunk lengths)")
_pmetrics.declare("serving/prefill_positions", "counter",
                  "prompt positions computed by dispatched programs, "
                  "filled or not (the unified step: groups run x rows "
                  "a group x prefill_chunk; a speculative step's pass: "
                  "num_slots x prefill_chunk)")
_pmetrics.declare("serving/step_uploads", "counter",
                  "host arrays shipped to the device by dispatched "
                  "steps (1 a step; a self-speculative draft ships one "
                  "more)")
_pmetrics.declare("serving/staged_slot_updates", "counter",
                  "slot rows whose host-decided state (table row, "
                  "limit, stop token, context reset) rode a step's "
                  "upload: admissions + device clears")
_pmetrics.declare("serving/ttft_ms", "histogram",
                  "request arrival -> first token on host, ms (bounded "
                  "reservoir; p50/p99 exposed via gauges())")
_pmetrics.declare("serving/itl_ms", "histogram",
                  "smoothed inter-token latency per request with >=2 "
                  "tokens, ms (bounded reservoir)")
_pmetrics.declare("obs/overhead_frac", "gauge",
                  "fraction of run() wall time spent inside "
                  "observability instrumentation, self-measured — "
                  "per-engine on its private registry, fleet-tier on "
                  "the federated registry (the <2% pinned contract)")
# ISSUE 10 reliability vocabulary: overload is a first-class mode, so
# its economics are first-class metrics
_pmetrics.declare("serving/preempt_evictions", "counter",
                  "active sequences evicted on page exhaustion and "
                  "requeued for recompute-style re-prefill")
_pmetrics.declare("serving/preempt_pages_reclaimed", "counter",
                  "KV pages reclaimed by preemption evictions")
_pmetrics.declare("serving/preempt_recompute_tokens", "counter",
                  "previously generated tokens re-prefilled when a "
                  "preempted request was re-admitted")
_pmetrics.declare("serving/requests_cancelled", "counter",
                  "requests completed with RequestCancelled")
_pmetrics.declare("serving/deadline_ttft_expired", "counter",
                  "requests that missed their TTFT deadline before "
                  "producing a first token")
_pmetrics.declare("serving/deadline_total_expired", "counter",
                  "requests that exceeded their total deadline "
                  "(mid-stream or queued)")
_pmetrics.declare("serving/quarantined", "counter",
                  "requests completed with RequestQuarantined after "
                  "repeated step-failure implication")
_pmetrics.declare("serving/containments", "counter",
                  "step-level fault containments (a failed compiled "
                  "step converted to slot/page reset + requeue instead "
                  "of engine death)")
_pmetrics.declare("serving/shed_rejections", "counter",
                  "submissions rejected at the admission door "
                  "(Overloaded, with a computed retry-after)")
_pmetrics.declare("serving/shed_retry_after_s", "gauge",
                  "retry-after seconds attached to the most recent "
                  "Overloaded rejection")
# ISSUE 19 pressure gauges: the LIVE signals the autoscaler and
# /statusz read — the counters above are monotonic history, these are
# "now" (set per gauge emission, and per fleet turn on fleet replicas)
_pmetrics.declare("serving/queue_depth", "gauge",
                  "requests currently waiting in the admission queue "
                  "(not yet in a slot)")
_pmetrics.declare("serving/shed_rate", "gauge",
                  "admission sheds per second over the controller's "
                  "trailing window (AdmissionController.shed_rate)")
# ISSUE 12 prefix-cache vocabulary: shared-prefix reuse is the serving
# capacity story, so its economics are first-class metrics
_pmetrics.declare("serving/prefix_cache_hits", "counter",
                  "admissions that attached >=1 cached prefix page "
                  "(suffix-only prefill)")
_pmetrics.declare("serving/prefix_cache_misses", "counter",
                  "admissions that found no cached prefix page")
_pmetrics.declare("serving/prefix_cache_tokens_saved", "counter",
                  "prompt tokens whose prefill was skipped by "
                  "attaching cached prefix pages")
_pmetrics.declare("serving/prefix_cache_evictions", "counter",
                  "unreferenced cache pages reclaimed by the "
                  "refcount-aware LRU under allocation pressure")
_pmetrics.declare("serving/prefix_cache_cow_forks", "counter",
                  "copy-on-write page forks (a sequence had to write "
                  "into a fully-shared page)")
_pmetrics.declare("serving/prefix_cache_pages", "gauge",
                  "physical pages currently owned by the prefix-cache "
                  "radix index (referenced + evictable)")

# -- disaggregated prefill/decode: engine-side migration counters (ISSUE 17)
_pmetrics.declare("disagg/migrated_out", "counter",
                  "requests a prefill-role engine exported to a decode "
                  "replica after sampling their first token")
_pmetrics.declare("disagg/kv_pages_exported", "counter",
                  "full prompt-KV pages serialized into migration "
                  "payloads (per-pool crc32-checksummed)")
_pmetrics.declare("disagg/kv_imported_pages", "counter",
                  "migrated KV pages written into the destination "
                  "engine's pools and seeded into its prefix-cache "
                  "radix index")
_pmetrics.declare("disagg/kv_import_dedup_pages", "counter",
                  "migrated KV pages already resident at the "
                  "destination (idempotent re-delivery or shared "
                  "prefix) — skipped, not rewritten")
_pmetrics.declare("disagg/kv_import_crc_rejects", "counter",
                  "migrated KV page blocks rejected at import "
                  "(checksum mismatch or malformed payload); the "
                  "request still replays correctly from its prompt")

# -- quantized serving: pool geometry gauges (ISSUE 20)
_pmetrics.declare("serving/kv_quant_bits", "gauge",
                  "bits per stored KV element in the page pools "
                  "(16 = bf16/f32 full precision, 8 = int8/fp8 "
                  "quantized)")
_pmetrics.declare("serving/kv_quant_pool_bytes", "gauge",
                  "total bytes of the KV DATA page pools across all "
                  "layers (the capacity denominator quantization "
                  "shrinks)")
_pmetrics.declare("serving/kv_quant_scale_pool_bytes", "gauge",
                  "total bytes of the page-parallel f32 scales pools "
                  "(0 when kv_quant='none') — the quantization "
                  "overhead term in the capacity math")

# -- per-layer cache spec: recurrent state (a model's pass counters are
# declared by the model: cache_spec.StepCounters)
_pmetrics.declare("serving/state_pool_bytes", "gauge",
                  "total bytes of the per-slot recurrent-state arrays "
                  "(cache_spec.SlotState; 0 for a paged-KV-only model)")
_pmetrics.declare("serving/window_pool_bytes", "gauge",
                  "total bytes of the window layers' ring pools "
                  "(cache_spec.WindowKV: sized by the window, not by "
                  "max_len; 0 for a model without window layers)")

# -- speculative decoding: draft/verify economics (ISSUE 18)
_pmetrics.declare("spec/steps", "counter",
                  "speculative unified-step programs dispatched "
                  "(draft + ragged verify in one compiled step)")
_pmetrics.declare("spec/tokens_drafted", "counter",
                  "draft tokens fed into verification chunks")
_pmetrics.declare("spec/tokens_accepted", "counter",
                  "draft tokens the target distribution accepted "
                  "(committed in place, ctx advanced over their KV)")
_pmetrics.declare("spec/tokens_rejected", "counter",
                  "draft tokens rejected at verification and rolled "
                  "back (their in-flight KV writes are left "
                  "unreachable behind ctx and overwritten in place)")

#: prompt positions one group of the unified step's prefill loop computes
#: (rows a group = this // prefill_chunk, at most num_slots: 8 at chunk
#: 128). A matmul that streams bf16 weights does T FLOP per weight byte
#: for T positions and the v5e's ridge is 197e12 / 819e9 = 240, so 1,024
#: positions keep every projection compute-bound with a factor of four
#: to spare, while a turn of chat or offline traffic (5-6 prefilling
#: slots of 64) fits one group. Not an option: the loop's trip count
#: follows the turn, this only sizes one trip.
PREFILL_GROUP_POSITIONS = 1024

#: the historical ``_stats`` key set, preserved verbatim — now backed
#: by ``serving/*`` registry counters
_STAT_KEYS = ("chunks", "chunk_slot_steps", "active_slot_steps",
              "tokens_emitted", "prefills", "prefills_overlapped",
              "prefill_waves", "chunks_empty", "unified_steps",
              "requests_completed", "run_seconds",
              "prefill_tokens", "prefill_positions",
              "step_uploads", "staged_slot_updates",
              # ISSUE-10 reliability counters ride the same view so
              # reset_gauges()/as_dict() cover them uniformly
              "preempt_evictions", "preempt_pages_reclaimed",
              "preempt_recompute_tokens", "requests_cancelled",
              "deadline_ttft_expired", "deadline_total_expired",
              "quarantined", "containments", "shed_rejections",
              # ISSUE-12 prefix-cache counters
              "prefix_cache_hits", "prefix_cache_misses",
              "prefix_cache_tokens_saved", "prefix_cache_evictions",
              "prefix_cache_cow_forks")


class _StatsView:
    """Dict-shaped view over the engine's registry counters: the
    ``_stats`` surface predates the metrics registry and tests index
    it (``eng._stats["active_slot_steps"]``), so the migration keeps
    the mapping protocol while the registry holds the truth."""

    __slots__ = ("_c",)

    def __init__(self, registry):
        self._c = {k: registry.counter("serving/" + k)
                   for k in _STAT_KEYS}

    def __getitem__(self, k):
        return self._c[k].value

    def __setitem__(self, k, v):
        self._c[k].set(v)

    def inc(self, k, n=1):
        self._c[k].inc(n)

    def __iter__(self):
        return iter(self._c)

    def keys(self):
        return self._c.keys()

    def as_dict(self):
        return {k: c.value for k, c in self._c.items()}


class _TurnUpload:
    """The ONE host array a dispatched program takes: every field the
    host decided this turn, int32, laid end to end in a flat vector.
    :meth:`host` hands out a fresh buffer with a writable view a field;
    :meth:`split` is the program's prologue, the same fields sliced out
    of the uploaded array (static offsets, so it costs the program a few
    reshapes)."""

    def __init__(self, fields):
        self._at, self.size = {}, 0
        for name, shape in fields:
            n = int(np.prod(shape, dtype=np.int64))
            self._at[name] = (self.size, n, tuple(shape))
            self.size += n

    def host(self):
        buf = np.zeros((self.size,), np.int32)
        return buf, self.split(buf)

    def split(self, arr):
        return {name: arr[o:o + n].reshape(shape)
                for name, (o, n, shape) in self._at.items()}


def _apply_slot_resets(reset, ctx, act):
    """A step program's prologue over its chained state: a slot the
    host rebound since the last launch (``reset >= 0``; -1 keeps) starts
    at that context and does not decode."""
    return jnp.where(reset >= 0, reset, ctx), act & (reset < 0)


class _PrefixCacheNode:
    """One cached FULL KV page of a token prefix (ISSUE 12): a node of
    the radix index over prompt-token blocks at ``page_size``
    granularity. The tree position encodes the whole prefix — two
    sequences reach the same node iff their first ``depth *
    page_size`` tokens are identical, so a node's page content
    (KV for those positions) is exact by construction, not
    probabilistic. ``ref`` counts slots currently attached
    (read-sharing the page); 0 means resident-but-evictable. The
    refcount chain is monotone root→leaf (every attachment references
    a contiguous prefix from the root), which is what makes
    leaf-first LRU eviction safe: a ref-0 node's whole subtree is
    ref-0."""

    __slots__ = ("key", "page", "parent", "children", "ref", "stamp")

    def __init__(self, key, page, parent):
        self.key = key          # the page's token block (bytes)
        self.page = page        # physical page id it owns
        self.parent = parent
        self.children = {}      # token-block bytes -> child node
        self.ref = 0            # attached readers (slots)
        self.stamp = 0          # LRU clock (engine _pc_clock)


#: copy-on-write fork: duplicate one physical page across EVERY
#: layer's k/v pool in ONE compiled dispatch (dst becomes a private
#: writable copy of the shared src) — per-pool launches would put
#: 2 x num_layers sequential dispatches on the TTFT-critical
#: admission path.
_pc_copy_page = jax.jit(lambda pools, src, dst:
                        [p.at[dst].set(p[src]) for p in pools])


#: KV-page import (ISSUE 17): write ALL of a migrated request's
#: accepted pages into every layer's k/v pool in ONE compiled
#: dispatch. ``dst`` is an int32 vector of page indices and each
#: pool's ``data`` stacks the matching page contents along the page
#: axis ([n, page_size, kv_heads * head_dim]) — per-page dispatches put
#: ~2 x num_layers x pages_per_request sequential launches on the
#: migration pump, the pump's dominant cost. The page count per
#: request is bounded by max_len/page_size, so the compile set stays
#: small. Functional update, so the write chains behind every
#: in-flight program in the device stream exactly like the COW fork
#: above — an import never races a dispatched step.
_kv_write_pages = jax.jit(lambda pools, dst, data:
                          [p.at[dst].set(d)
                           for p, d in zip(pools, data)])


#: version of the migration payload (``_migrate_out``). 2: a page's
#: exported array is the pool's own ``[page_size, kv_heads * head_dim]``
#: (scales ``[kv_heads, page_size]``); version 1 shipped
#: ``[kv_heads, page_size, head_dim]`` and is refused at import.
KV_PAYLOAD_VERSION = 2


#: the priority band EXTERNAL requests are clamped into by the HTTP
#: front door (inference/api_server.py): higher wins admission order
#: and may preempt. In-process callers may use any int — the band only
#: bounds what an untrusted client can claim over the wire.
PRIORITY_RANGE = (0, 15)


@dataclass(eq=False)
class ServedRequest:
    request_id: int
    prompt: np.ndarray                 # [S] int
    max_new_tokens: int
    eos_token_id: int | None = None
    tokens: list = field(default_factory=list)   # generated ids
    finished: bool = False
    finish_reason: str | None = None   # "eos" | "length" | "cancelled"
    #                                  # | "deadline" | "quarantined"
    # latency accounting (seconds, perf_counter clock)
    t_arrive: float = 0.0              # add_request
    t_admit: float = 0.0               # admitted into a slot
    t_prefill_done: float = 0.0        # prompt fully streamed
    t_first: float = 0.0               # first token visible host-side
    t_done: float = 0.0                # finished
    #: lifecycle-trace sampling decision (engine trace_sample_rate)
    traced: bool = False
    # ---- lifecycle control (ISSUE 10) --------------------------------
    #: higher wins admission order; a strictly-higher-priority arrival
    #: may preempt running lower-priority sequences for pages/slots
    priority: int = 0
    #: seconds from arrival within which the first token must land
    #: (None = no TTFT deadline)
    ttft_deadline_s: float | None = None
    #: seconds from arrival within which the request must finish
    deadline_s: float | None = None
    #: cancellation requested; honored at the next scheduler turn
    cancelled: bool = False
    #: typed failure (RequestCancelled / DeadlineExceeded /
    #: RequestQuarantined); None for a normal completion
    error: Exception | None = None
    #: times this request was evicted and requeued for recompute
    preemptions: int = 0
    #: containment blame: failed steps this request rode; crossing the
    #: engine's max_strikes quarantines it
    strikes: int = 0
    # ---- fleet-level trace context (ISSUE 13) ------------------------
    #: one trace id per CLIENT request, minted by the fleet router and
    #: shared by every attempt (hedge duplicates, failover replays);
    #: None for a standalone engine (its request_id is the trace)
    trace_id: int | None = None
    #: the cross-replica hop list — admission, preemption/replay,
    #: salvage, failover re-admission, hedge launch, completion — each
    #: hop a small dict {kind, t, replica?, ...}. Hedge copies SHARE
    #: the primary's list object, so the winner and the cancelled
    #: loser interleave into one timeline (bounded; see _hop)
    hops: list = field(default_factory=list)
    #: hops dropped past the bound (a preemption storm must not grow
    #: a request's memory without limit)
    hops_dropped: int = 0
    #: SLO accounting label (profiler/slo.py): attainment windows and
    #: burn-rate alerts partition by tenant
    tenant: str | None = None

    def cancel(self):
        """Request cancellation. Safe from any thread; the engine
        honors it at its next scheduler turn — pages are freed and the
        request completes with ``RequestCancelled`` (tokens already
        emitted are kept)."""
        self.cancelled = True


def request_trace_summary(req) -> dict:
    """The condensed end-to-end trace of a finished request — what the
    :class:`~paddle_tpu.profiler.trace.RequestTraceLog` stores and
    ``/statusz`` renders for the N slowest recent traces. One trace id
    covers every attempt (preemption replays, failover re-admissions,
    the hedge winner AND its cancelled loser all hop into the same
    list)."""
    tid = req.trace_id if req.trace_id is not None else req.request_id
    t0 = req.t_arrive
    hops = list(req.hops or ())
    # overflow is counted IN the shared list (a hedge copy may have
    # been the object that hit the cap — see reliability.record_hop)
    dropped = hops[-1]["dropped"] if hops \
        and hops[-1].get("kind") == "truncated" else req.hops_dropped
    return {
        "trace_id": int(tid),
        "latency_ms": round((req.t_done - t0) * 1e3, 3)
        if req.t_done else 0.0,
        "ttft_ms": round((req.t_first - t0) * 1e3, 3)
        if req.t_first else None,
        "tokens": len(req.tokens),
        "finish_reason": req.finish_reason,
        "error": type(req.error).__name__
        if req.error is not None else None,
        "tenant": req.tenant,
        "priority": int(req.priority),
        "preemptions": int(req.preemptions),
        "hops": [dict(h) for h in hops],
        "hops_dropped": int(dropped),
    }


class ContinuousBatchingEngine:
    """Schedules mixed-length generation streams through ONE compiled
    unified batching-step program (ragged mixed prefill+decode). Greedy
    or temperature sampling.

    model: any CausalLM Layer implementing ``forward(ids, caches=, pos=,
    tables=)`` + ``init_kv_cache`` — Llama, Qwen2 (incl. MoE), and GPT2
    all qualify. num_slots is the batch size; total pool memory =
    num_pages * page_size tokens of KV per layer.

    ``pools`` holds one persistable Tensor per pool for the engine's
    lifetime. The compiled step reassigns them, so ``to_static`` donates
    their buffers to it: a pool's array read before a step (``p._data``)
    is deleted by that step. Read a pool through its Tensor, or keep
    ``np.asarray(p._data)`` / ``p.clone()`` where a value must outlive
    a step."""

    def __init__(self, model, num_slots=4, page_size=16, num_pages=None,
                 max_len=512, decode_chunk=None,
                 eos_token_id=None, greedy=True, temperature=1.0,
                 seed=0, prefill_chunk=None, admit_batch=None,
                 trace_sample_rate=0.01, latency_reservoir=2048,
                 max_strikes=2, max_containments=8, audit=None,
                 prefix_cache=None, role="both", spec_decode=False,
                 spec_k=None, spec_draft=None, kv_quant="none"):
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"unknown engine role {role!r}")
        if kv_quant not in ("none", "int8", "fp8"):
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             "(expected 'none', 'int8' or 'fp8')")
        self.kv_quant = kv_quant
        # disaggregation role (ISSUE 17): a "prefill" engine runs
        # chunked prefill to completion, samples the first token, then
        # EXPORTS the finished full KV pages + request state into
        # ``migrations_out`` instead of decoding — the router moves the
        # record to a decode-capable engine, where import_migration()
        # seeds the prefix cache and replays through the recompute
        # path. "decode"/"both" engines behave identically at this
        # layer (a decode engine can still prefill — that IS the
        # cross-role failover path); the role only changes routing
        # preference and the prefill engine's drain behavior.
        self.role = role
        self.model = model
        cfg = model.config
        self.cfg = cfg
        # weight-only serving quantization (ISSUE 20): a config with
        # weight_quant set gets its big projections converted to
        # dequant-in-matmul form once, at engine construction
        # (quantize_for_serving is idempotent — a pre-converted model
        # or a second engine over the same model is a no-op)
        if getattr(cfg, "weight_quant", None):
            from ..nn.quant import quantize_for_serving
            quantize_for_serving(model)
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_slot = -(-self.max_len // self.page_size)
        # +1: page 0 is the reserved trash page
        self.num_pages = int(num_pages) if num_pages is not None else \
            self.num_slots * self.pages_per_slot + 1
        # also the KV-pool dtype below AND the tuner-cache key's dtype
        # component — one probe so the two can never diverge. First
        # FLOATING param: a weight-quantized model carries int8 buffers
        # whose dtype must not leak into the activation/pool dtype.
        dtype = next(p._data.dtype for p in model.parameters()
                     if jnp.issubdtype(p._data.dtype, jnp.floating))
        # chunk-ladder knobs left as None resolve through the autotuner
        # cache ("serving_chunks" surface, keyed by slots/max_len/page —
        # registered at the bottom of this module), then fall back to
        # the static derivations; an explicit argument always wins
        tuned = {}
        if decode_chunk is None or prefill_chunk is None \
                or admit_batch is None:
            from ..tuner import lookup
            tuned = lookup("serving_chunks",
                           {"slots": self.num_slots,
                            "max_len": self.max_len,
                            "page": self.page_size}, str(dtype)) or {}
        if decode_chunk is None:
            decode_chunk = int(tuned.get("decode_chunk", 0)) or 16
        self.decode_chunk = int(decode_chunk)
        if prefill_chunk is None:
            prefill_chunk = int(tuned.get("prefill_chunk", 0)) or 128
        self.prefill_chunk = max(1, min(int(prefill_chunk), self.max_len))
        if admit_batch is None:
            admit_batch = int(tuned.get("admit_batch", 0)) or self.num_slots
        self.admit_batch = max(1, min(int(admit_batch), self.num_slots))
        self.eos = -1 if eos_token_id is None else int(eos_token_id)
        self.greedy = bool(greedy)
        self.temperature = float(temperature)

        # the pools, from the model's cache spec (inference/cache_spec.py;
        # a model that declares none gets one paged K/V pair per layer).
        # A flat list like dense caches, in the model's own order;
        # geometry kept so step-failure containment can rebuild the
        # pools from scratch (_reset_device_state). Per PagedKV entry:
        # (key_pages, value_pages), and under quantized KV (ISSUE 20)
        # two extra pools after them — the page-parallel f32 scales
        # pools (key_scales, value_scales), shape (num_pages, kvh,
        # page_size): one scale per (token, kv head), page axis at index
        # 0 like the data pools (ops.paged_attention.kv_pool_shape and
        # kv_scales_shape state both), so every page operation (COW page
        # copy, migration export/crc, batched import landing pads)
        # composes over the paged pools unchanged. Per WindowKV entry the
        # same pools over ``num_slots`` rings of ``_ring`` pages (+ a
        # trash page) whatever ``max_len``, kinds "wkv"/"wscale": their
        # table is a rule (cache_spec.WindowKV), not host state, and no
        # page operation walks them. Per SlotState entry:
        # one (num_slots, ...) array the MODEL keeps right in-program; per
        # CompressedKeys entry the same, its rows sized by max_len.
        # A StepCounters entry: one int32 vector the step program zeroes
        # and reports in its packed fetch.
        from ..ops.paged_attention import kv_pool_shape, kv_scales_shape
        from .cache_spec import (CompressedKeys, PagedKV, SlotState,
                                 StepCounters, WindowKV, ring_pages,
                                 spec_of)
        self._pool_dtype = dtype if kv_quant == "none" else jnp.dtype(
            jnp.int8 if kv_quant == "int8" else jnp.float8_e4m3fn)
        self._pool_shapes, self._pool_dtypes, self._pool_kinds = [], [], []
        self._counter_names, self._counter_pool = (), None
        spec = spec_of(model)
        #: pages of a slot's ring in every window layer's pools (0: the
        #: spec has no window layer): the largest window's, and never more
        #: than a slot's whole table row
        self._ring = min(self.pages_per_slot, max(
            (ring_pages(e.window, self.prefill_chunk, self.page_size)
             for e in spec if isinstance(e, WindowKV)), default=0))
        for ent in spec:
            if isinstance(ent, (PagedKV, WindowKV)):
                ring = isinstance(ent, WindowKV)
                geom = (ent.kv_heads, self.num_slots * self._ring + 1
                        if ring else self.num_pages, self.page_size)
                self._pool_shapes += [kv_pool_shape(*geom,
                                                    ent.head_dim)] * 2
                self._pool_dtypes += [self._pool_dtype] * 2
                self._pool_kinds += ["wkv" if ring else "kv"] * 2
                if kv_quant != "none":
                    self._pool_shapes += [kv_scales_shape(*geom)] * 2
                    self._pool_dtypes += [jnp.float32] * 2
                    self._pool_kinds += ["wscale" if ring else "scale"] * 2
            elif isinstance(ent, SlotState):
                self._pool_shapes.append(
                    (self.num_slots,) + tuple(ent.shape))
                self._pool_dtypes.append(
                    jnp.dtype(ent.dtype) if ent.dtype else dtype)
                self._pool_kinds.append("state")
            elif isinstance(ent, CompressedKeys):
                self._pool_shapes.append(
                    (self.num_slots, -(-self.max_len // ent.stride),
                     ent.kv_heads * ent.head_dim))
                self._pool_dtypes.append(dtype)
                self._pool_kinds.append("state")
            elif isinstance(ent, StepCounters):
                if self._counter_pool is not None:
                    raise ValueError("a cache spec holds at most one "
                                     "StepCounters entry")
                known = _pmetrics.catalog()
                unknown = [n for n in ent.names
                           if known.get("serving/" + n, ("",))[0]
                           != "counter"]
                if unknown:
                    raise ValueError(
                        f"StepCounters names {unknown} are not declared: "
                        f"the model's module declares each as a "
                        f"'serving/<name>' counter with its help text")
                self._counter_names = tuple(ent.names)
                self._counter_pool = len(self._pool_shapes)
                self._pool_shapes.append((len(ent.names),))
                self._pool_dtypes.append(jnp.int32)
                self._pool_kinds.append("counters")
            else:
                raise TypeError(f"unknown cache spec entry {ent!r}")
        self._n_pools = len(self._pool_shapes)
        #: pools with a page axis (index 0): what COW, export and import
        #: walk
        self._paged = [i for i, k in enumerate(self._pool_kinds)
                       if k in ("kv", "scale")]
        #: what a slot keeps OUTSIDE the host-managed pages — recurrent
        #: state (cache_spec.SlotState), window rings (WindowKV) — named
        #: for the refusals; nothing of it can be shared, forked or
        #: shipped, so such an engine serves without prefix cache,
        #: speculative decoding and migration
        self._slot_owned = " and ".join(
            what for kind, what in (("state", "per-slot recurrent state"),
                                    ("wkv", "per-slot window rings"))
            if kind in self._pool_kinds)
        if self._slot_owned:
            if role == "prefill":
                raise ValueError(
                    f"role='prefill' exports finished prompt pages; this "
                    f"model keeps {self._slot_owned} beside its pages, "
                    f"which a migration does not carry yet")
            if spec_decode or spec_k is not None or spec_draft is not None:
                raise ValueError(
                    f"speculative decoding rolls a slot back to its last "
                    f"accepted token; this model keeps {self._slot_owned}, "
                    f"which has no rollback yet")
        # persistable: state the step programs reassign, so to_static
        # donates them (the class docstring). These Tensor objects stay
        # the engine's for its lifetime — a compiled step holds its
        # state by identity — and everything that replaces a pool
        # rebinds their data in place
        self.pools = [Tensor(jnp.zeros(s, dt)) for s, dt in
                      zip(self._pool_shapes, self._pool_dtypes)]
        for p in self.pools:
            p.persistable = True
        # static pool-geometry facts for the gauges
        self._kv_quant_bits = 8 * jnp.dtype(self._pool_dtype).itemsize
        _bytes = lambda kind: sum(
            int(np.prod(s)) * jnp.dtype(dt).itemsize
            for s, dt, k in zip(self._pool_shapes, self._pool_dtypes,
                                self._pool_kinds) if k == kind)
        self._kv_pool_bytes = _bytes("kv")
        self._kv_scale_pool_bytes = _bytes("scale")
        self._state_pool_bytes = _bytes("state")
        self._window_pool_bytes = _bytes("wkv") + _bytes("wscale")

        self._free_pages = deque(range(1, self.num_pages))
        # host-side slot bookkeeping (admission decisions, drain)
        B, MP = self.num_slots, self.pages_per_slot
        self.tables = np.zeros((B, MP), np.int32)
        self.ctx = np.zeros((B,), np.int32)       # mirror (packed fetch)
        self.active = np.zeros((B,), bool)        # mirror (packed fetch)
        self.limits = np.zeros((B,), np.int32)    # ctx budget per slot
        self.slot_eos = np.full((B,), -1, np.int32)  # per-request eos
        self.slot_req: list[ServedRequest | None] = [None] * B
        self.slot_pages: list[list] = [[] for _ in range(B)]
        # the ADMISSION prompt per slot: the request's prompt, plus —
        # for a preempted request re-admitted for recompute — every
        # token it had already generated (vLLM recompute preemption:
        # chunked prefill is token-identical to the decode it replays,
        # so the stream continues exactly where the eviction cut it)
        self._slot_prompt: list[np.ndarray | None] = [None] * B
        # chunked-prefill progress: a slot whose prompt is still being
        # streamed into its pages is PREFILLING — inactive for decode,
        # ineligible for drain
        self._prefilling = np.zeros((B,), bool)
        self._prefill_off = np.zeros((B,), np.int32)   # tokens dispatched
        self._act_target = np.zeros((B,), bool)  # activate on completion
        # host prediction of device ctx (exact for length-limited slots;
        # an eos stop only ever makes it an overestimate) — drives the
        # is-a-step-worth-it test (_worth_step)
        self._pred_ctx = np.zeros((B,), np.int32)
        # monotone program-dispatch counter + per-slot activation seq:
        # a step dispatched BEFORE the one a slot's prompt ended in has
        # a stale view of that slot, so its ctx/active mirrors must not
        # be applied at harvest
        self._seq = 0
        self._act_since = np.zeros((B,), np.int64)

        # device-resident hot state, chained through the step programs'
        # outputs (never round-trips between steps)
        self._dev_tok = jnp.zeros((B,), jnp.int32)
        self._dev_ctx = jnp.zeros((B,), jnp.int32)
        self._dev_act = jnp.zeros((B,), bool)
        # what the host decides for a slot (tables, limits, slot_eos
        # above) reaches the device in the next dispatch's ONE upload,
        # and with it a per-slot reset of the chained ctx/active state
        # (-1 keeps; admission stages its start context, an eviction 0;
        # the last write before a launch wins)
        self._slot_reset = np.full((B,), -1, np.int32)
        self._staged_rows = 0     # slot rows staged since the last launch

        self.queue: deque[ServedRequest] = deque()
        self.completed: list[ServedRequest] = []
        # disaggregation (ISSUE 17): exported (request, kv payload)
        # records awaiting router pickup, and — per exported request —
        # the prefix-cache node chain pinned against eviction until
        # the destination acks the import (release_exported); the page
        # audit counts these pins as live attachments
        self.migrations_out: deque = deque()
        self._exported_pins: dict[int, list] = {}
        self._next_id = 0
        self._seed = int(seed)
        self._key = jax.random.PRNGKey(seed)
        # ---- reliability state (ISSUE 10) ----------------------------
        # pages reclaimed from an EVICTED (still device-active) slot are
        # quarantined until every compiled program dispatched before the
        # eviction has been harvested: an in-flight program still writes
        # the old owner's kv through its dispatch-time block table, and
        # handing the pages to a new request in the same turn would
        # interleave two owners' writes. (gate_seq, pages) entries.
        self._deferred_free: list[tuple[int, list]] = []
        self._last_fetch_dispatch_seq = 0   # newest fetched-program seq
        self._last_harvest_seq = 0          # newest harvested seq
        # admission order degrades to plain FIFO (the historical
        # contract) until a non-zero priority is ever seen
        self._has_priorities = False
        # the per-turn reap's O(queue) sweep only runs once lifecycle
        # control (a deadline or an engine-level cancel) is in play —
        # plus a periodic sweep so a direct ServedRequest.cancel() on
        # a QUEUED handle (a plain flag the engine cannot observe
        # eagerly) is still honored within a bounded number of turns
        self._lifecycle_seen = False
        self._reap_turn = 0
        # completions produced OUTSIDE the drain pass (already-complete
        # replays adopted at admission) — drained into the next turn's
        # done list so run()/step() callers still see them
        self._done_pending: list[ServedRequest] = []
        # step-failure containment: blame threshold + containment
        # budget (an engine failing every step escapes to the
        # supervisor instead of looping forever). The budget resets at
        # every run() entry; a bare step() loop spends it until the
        # next run().
        self.max_strikes = int(max_strikes)
        self.max_containments = int(max_containments)
        self._containments_run = 0
        # page-accounting audit (PADDLE_TPU_SERVING_AUDIT=1, on in
        # tests): free + Σ slot pages + deferred + trash == num_pages
        # after every drain/preempt/cancel, so reclamation bugs fail
        # loudly instead of leaking quietly
        from ..profiler import _env_bool
        self._audit = _env_bool("PADDLE_TPU_SERVING_AUDIT") \
            if audit is None else bool(audit)
        # ---- prefix cache (ISSUE 12) ---------------------------------
        # radix index over FULL pages of prompt-token blocks: an
        # admitted request whose prompt prefix is resident attaches
        # the existing physical pages (refcounted, read-shared) and
        # only prefills its unseen suffix. Default ON; the env knob
        # or prefix_cache=False restores exclusive-page behavior.
        self._prefix_cache = _env_bool("PADDLE_TPU_PREFIX_CACHE", True) \
            if prefix_cache is None else bool(prefix_cache)
        if self._slot_owned:
            # a hit would skip tokens whose recurrent state, or whose
            # window layers' K/V, nobody stored
            self._prefix_cache = False
        self._pc_root = _PrefixCacheNode(None, 0, None)   # sentinel
        self._pc_nodes: dict[int, _PrefixCacheNode] = {}  # page -> node
        self._pc_clock = 0                                # LRU stamps
        #: per-slot attached cache nodes, in table-row order — the
        #: slot's block table is [shared pages..., private pages...]
        self.slot_shared: list[list] = [[] for _ in range(B)]
        self._compiled = set()         # distinct compiled signatures
        # ONE batching-step program (a loop over groups of prompt rows +
        # decode_chunk decode micro-steps); per-slot count of
        # dispatched-but-unharvested steps that may emit tokens for the
        # slot — drain defers while any are in flight
        self._n_decode = max(0, self.decode_chunk - 1)
        self._unified_fn = None
        # rows of one group of the unified step's prefill loop, and the
        # length of the padded row list the step program is handed
        self._group = max(1, min(self.num_slots, PREFILL_GROUP_POSITIONS
                                 // self.prefill_chunk))
        self._group_rows = -(-self.num_slots // self._group) * self._group
        self._emits_inflight = np.zeros((B,), np.int32)
        # the ONE upload of a turn, per step program: the prompt chunks
        # (_stage_prompt_chunks), the slot state the host decides
        # (_slot_fields), and the program's own (the unified step's
        # compacted row list and its length, a speculative step's draft
        # counts)
        chunks = [("ids", (B, self.prefill_chunk)), ("nq", (B,)),
                  ("last", (B,)), ("tgt", (B,))]
        self._unified_up = _TurnUpload(
            chunks + self._slot_fields()
            + [("rows", (self._group_rows,)), ("n_rows", ())])
        self._spec_up = _TurnUpload(
            chunks + self._slot_fields() + [("nd", (B,))])
        # ---- speculative decoding (ISSUE 18) -------------------------
        # a drafting decode slot rides 1 + K tokens (pending + drafts)
        # through the SAME ragged mixed pass as a short prefill-shaped
        # chunk; distribution-exact rejection sampling over the target
        # logits commits the accepted prefix in place. Knobs left None
        # resolve through the autotuner cache ("spec_decode" surface,
        # registered at the bottom of this module) then static
        # defaults; an explicit argument always wins.
        self._spec = bool(spec_decode) or spec_k is not None \
            or spec_draft is not None
        self._spec_k = 0
        self._spec_source = None
        self._spec_fn = None
        if self._spec:
            stuned = {}
            if spec_k is None or spec_draft is None:
                from ..tuner import lookup
                stuned = lookup("spec_decode",
                                {"slots": self.num_slots,
                                 "max_len": self.max_len,
                                 "page": self.page_size},
                                str(dtype)) or {}
            if spec_k is None:
                spec_k = int(stuned.get("k", 0)) or 4
            if spec_draft is None:
                spec_draft = stuned.get("source") or "ngram"
            # the verify chunk reuses the tuned [B, prefill_chunk] ids
            # plane — no new compiled shape, so K+1 must fit in it
            if self.prefill_chunk < 2:
                raise ValueError("speculative decoding needs "
                                 "prefill_chunk >= 2 to carry a "
                                 "verification chunk")
            self._spec_k = max(1, min(int(spec_k),
                                      self.prefill_chunk - 1))
            from .spec_decode import get_draft_source
            self._spec_source = get_draft_source(spec_draft)

        # perf observability (profiler subsystem): a PRIVATE typed
        # metrics registry behind the :meth:`gauges` surface — slot
        # occupancy, admission/prefill overlap, tok/s, latency
        # percentiles. Counters maintained unconditionally; latency
        # samples live in BOUNDED reservoirs (a long-lived engine's
        # memory stays flat over millions of completions — the lists
        # this replaces grew without limit); mirrored into the trace
        # layer only when tracing is enabled.
        self.metrics = _pmetrics.MetricsRegistry()
        self._stats = _StatsView(self.metrics)
        self._h_ttft = self.metrics.histogram(
            "serving/ttft_ms", capacity=int(latency_reservoir))
        self._h_itl = self.metrics.histogram(
            "serving/itl_ms", capacity=int(latency_reservoir))
        self._g_overhead = self.metrics.gauge("obs/overhead_frac")
        self._g_pc_pages = self.metrics.gauge(
            "serving/prefix_cache_pages")
        self._g_queue_depth = self.metrics.gauge("serving/queue_depth")
        self._g_kvq_bits = self.metrics.gauge("serving/kv_quant_bits")
        self._g_kvq_pool_bytes = self.metrics.gauge(
            "serving/kv_quant_pool_bytes")
        self._g_kvq_scale_bytes = self.metrics.gauge(
            "serving/kv_quant_scale_pool_bytes")
        self._g_state_bytes = self.metrics.gauge(
            "serving/state_pool_bytes")
        self._g_window_bytes = self.metrics.gauge(
            "serving/window_pool_bytes")
        # the model's own pass counters (cache_spec.StepCounters)
        self._c_model = {n: self.metrics.counter("serving/" + n)
                         for n in self._counter_names}
        self._c_migrated_out = self.metrics.counter(
            "disagg/migrated_out")
        self._c_kv_exported = self.metrics.counter(
            "disagg/kv_pages_exported")
        self._c_kv_imported = self.metrics.counter(
            "disagg/kv_imported_pages")
        self._c_kv_dedup = self.metrics.counter(
            "disagg/kv_import_dedup_pages")
        self._c_kv_rejects = self.metrics.counter(
            "disagg/kv_import_crc_rejects")
        self._c_spec_steps = self.metrics.counter("spec/steps")
        self._c_spec_drafted = self.metrics.counter(
            "spec/tokens_drafted")
        self._c_spec_accepted = self.metrics.counter(
            "spec/tokens_accepted")
        self._c_spec_rejected = self.metrics.counter(
            "spec/tokens_rejected")
        # observability self-measurement: seconds spent inside
        # instrumentation on the hot path (gauges()["obs_overhead_frac"]
        # = _obs_s / run_seconds; pinned < 2% by test)
        self._obs_s = 0.0
        # per-request lifecycle tracing: every Nth request (by id) gets
        # its spans reconstructed into the chrome trace at completion —
        # hot-path cost for a traced request is a few float stamps
        self._trace_every = int(round(1.0 / trace_sample_rate)) \
            if trace_sample_rate and trace_sample_rate > 0 else 0
        self._overlap_admission = False

    # ---- public API ------------------------------------------------------

    def add_request(self, prompt_ids, max_new_tokens,
                    eos_token_id=None, priority=0,
                    ttft_deadline_s=None, deadline_s=None,
                    tenant=None) -> int:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        with _span("serving/add_request", request_id=self._next_id,
                   prompt_len=prompt.size):
            self._check_fits(prompt.size, int(max_new_tokens))
            req = ServedRequest(
                self._next_id, prompt, int(max_new_tokens),
                eos_token_id if eos_token_id is not None
                else (self.eos if self.eos >= 0 else None),
                priority=int(priority),
                ttft_deadline_s=ttft_deadline_s,
                deadline_s=deadline_s,
                tenant=tenant)
            req.t_arrive = time.perf_counter()
            self._next_id += 1
            if req.priority:
                self._has_priorities = True
            if ttft_deadline_s is not None or deadline_s is not None:
                self._lifecycle_seen = True
            self.queue.append(req)
        return req.request_id

    def _check_fits(self, prompt_len, max_new):
        if prompt_len + max_new > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({max_new}) exceeds engine max_len {self.max_len}")
        # reject what the pool can NEVER satisfy — otherwise run() would
        # spin forever waiting for pages that cannot exist
        need = -(-(prompt_len + max_new) // self.page_size)
        if need > self.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.num_pages - 1} allocatable")

    def _queue_snapshot(self):
        """Copy the queue for a cross-thread lookup. ``list(deque)``
        is NOT atomic — a scheduler mutation mid-copy raises
        mutated-during-iteration — so retry; the queue quiesces within
        a turn, making livelock practically impossible. The handle's
        own ``cancel()`` (a bool set) remains the truly lock-free
        any-thread surface."""
        while True:
            try:
                return list(self.queue)
            except RuntimeError:
                continue

    def request(self, request_id) -> ServedRequest | None:
        """The live ServedRequest handle for an id — queued, running,
        or completed (the cancel()/error/priority surface)."""
        for req in self._queue_snapshot():
            if req is not None and req.request_id == request_id:
                return req
        for req in list(self.slot_req):
            if req is not None and req.request_id == request_id:
                return req
        for req in list(self.completed):
            if req.request_id == request_id:
                return req
        return None

    def cancel(self, request_id) -> bool:
        """Cancel a queued or running request: takes effect at the next
        scheduler turn (pages freed mid-prefill or mid-decode, typed
        ``RequestCancelled`` completion, tokens already emitted kept).
        Returns False for an unknown or already-finished request.
        Only live containers are scanned — cancelling a finished
        request is a no-op, so lookup cost never grows with the
        engine's completed history."""
        for req in self._queue_snapshot() + list(self.slot_req):
            if req is not None and req.request_id == request_id:
                if req.finished:
                    return False
                req.cancel()
                self._lifecycle_seen = True
                return True
        return False

    def requeue(self, req: ServedRequest):
        """Adopt a ServedRequest salvaged from a torn-down engine
        (EngineSupervisor restart): idempotent replay — the prompt plus
        every token already delivered re-prefills through the recompute
        path, so the stream continues exactly where the dead engine
        left it. A request that already has its full stream (it crashed
        between harvest and drain) completes immediately."""
        if req.finished:
            self.completed.append(req)
            return
        self._check_fits(req.prompt.size, req.max_new_tokens)
        self._next_id = max(self._next_id, req.request_id + 1)
        if req.priority:
            self._has_priorities = True
        if req.ttft_deadline_s is not None \
                or req.deadline_s is not None or req.cancelled:
            self._lifecycle_seen = True
        self.queue.append(req)

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any()) \
            or bool(self._prefilling.any())

    def handoff(self):
        """Elasticity/drain hook (ISSUE 11): evict every unfinished
        occupant for recompute-style replay and empty the queue;
        returns the unfinished requests in arrival order — pages
        reclaimed audit-clean, tokens already emitted kept — for
        adoption by a sibling engine (the ServingFleet's deadline-
        bounded scale-down and failover paths). The engine is left
        empty and reusable."""
        out = []
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None or req.finished:
                continue
            req.preemptions += 1
            self._evict_slot(slot, requeue=False, reason="handoff")
            out.append(req)
        while self.queue:
            req = self.queue.popleft()
            if not req.finished:
                out.append(req)
        out.sort(key=lambda r: (r.t_arrive, r.request_id))
        self._audit_pages("handoff")
        return out

    # ---- disaggregated prefill/decode: KV-page migration (ISSUE 17) ------
    #
    # A ``role="prefill"`` engine never activates decode (see
    # _stage_slot): a slot streams its prompt, samples the first token
    # in-program and goes inactive, and the drain pass exports it —
    # full prompt-KV pages plus the request (first token kept) — into
    # ``migrations_out`` for the router. The destination seeds the
    # pages into ITS prefix-cache radix index and requeues the request,
    # so admission attaches them exactly like a prefix-cache hit at
    # full match length and re-prefills only the unseen suffix: greedy
    # streams are token-identical to the colocated engine by the same
    # recompute-replay contract every failover path already leans on,
    # and a lost/damaged transfer degrades to plain prompt replay, not
    # a wrong stream.

    def _should_migrate(self, slot, req):
        """True when a drained slot's request should leave this engine
        for a decode replica instead of completing here: prefill role,
        decode budget left, stream not already over (instant-eos and
        single-token requests complete locally like any engine's)."""
        if self.role != "prefill" or req.finished or req.cancelled:
            return False
        if getattr(req, "no_migrate", False):
            # the fleet found no decode-capable replica for this
            # request: complete it colocated (cross-role degradation,
            # never a migrate/replay livelock)
            return False
        if len(req.tokens) >= req.max_new_tokens:
            return False
        eos = req.eos_token_id
        if eos is not None and req.tokens and req.tokens[-1] == eos:
            return False
        return True

    def _migrate_out(self, slot, req):
        """Export a prefill-complete slot: serialize its FULL prompt-KV
        pages (per-pool crc32 per page), pin the published prefix
        against eviction until the destination acks, free the slot, and
        park (request, payload) for the router. The request does NOT
        complete here — it leaves the engine still live."""
        eff = self._slot_prompt[slot]
        ps = self.page_size
        row = self.tables[slot]
        blocks = []
        for lvl in range(len(eff) // ps):
            page = int(row[lvl])
            # np.asarray forces the device sync; a drained slot is
            # inactive in every dispatched program (its writes are
            # trash-page-guarded), so the fetched content is the final
            # prefill output even under the pipelined driver
            data = [np.asarray(a[page]) for a in self._paged_arrays()]
            blocks.append({
                "tokens": np.asarray(
                    eff[lvl * ps:(lvl + 1) * ps], np.int32),
                "data": data,
                "crc": [zlib.crc32(np.ascontiguousarray(d).tobytes())
                        for d in data],
            })
        payload = {"version": KV_PAYLOAD_VERSION,
                   "rid": int(req.request_id),
                   "eff_len": int(len(eff)), "page_size": ps,
                   "n_pools": len(self._paged),
                   "dtype": str(self._pool_dtype),
                   "kv_quant": self.kv_quant,
                   "blocks": blocks}
        # deferred-free discipline (ISSUE 17): the source's published
        # prefix stays pinned until release_exported — a transfer that
        # dies mid-flight replays against warm source pages
        chain = self._pc_match(eff)
        if chain:
            self._pc_pin(chain)
            self._exported_pins[int(req.request_id)] = chain
        record_hop(req, "migrate_out",
                   replica=getattr(self, "_fleet_replica_id", None),
                   pages=len(blocks), tokens=len(req.tokens))
        _t_obs = time.perf_counter()
        self._c_migrated_out.inc()
        self._c_kv_exported.inc(len(blocks))
        _frec.record_event("migrate_out", req=req.request_id,
                           slot=slot, pages=len(blocks))
        self._obs_s += time.perf_counter() - _t_obs
        self._release_pages(self.slot_pages[slot], safe=True)
        self._clear_slot(slot)
        self.migrations_out.append((req, payload))

    def take_migrations(self):
        """Drain the outbound migration queue: (request, payload)
        pairs in export order, for the router (or the worker RPC seam)
        to deliver to a decode replica."""
        out = []
        while self.migrations_out:
            out.append(self.migrations_out.popleft())
        return out

    def release_exported(self, request_id):
        """Destination ack: unpin a migrated request's exported prefix
        pages on the SOURCE engine (they stay resident as ordinary
        evictable cache — that residency is the warm-prefix win for
        repeated prompts). Idempotent; returns whether a pin existed."""
        chain = self._exported_pins.pop(int(request_id), None)
        if chain is None:
            return False
        self._pc_unpin(chain)
        self._audit_pages("release_exported")
        return True

    def import_migration(self, req, payload):
        """Adopt a migrated request WITH its shipped KV: verify each
        block's checksums, write accepted pages into the pools (one
        compiled functional dispatch for the whole request — chains
        behind any in-flight program, the COW discipline), seed them
        into the
        prefix-cache radix index as evictable residents, then requeue
        the request. Admission then attaches the seeded chain like any
        prefix-cache hit. Idempotent: blocks already resident dedup;
        ANY malformed/damaged block stops seeding (the chain must stay
        root-contiguous) and the request still replays correctly from
        whatever prefix landed. A payload of another version than
        :data:`KV_PAYLOAD_VERSION` lands nothing and the result says so
        under ``"refused"``. Returns import counts."""
        if self._slot_owned:
            raise ValueError(
                f"import_migration lands shipped prompt pages; this model "
                f"keeps {self._slot_owned} beside its pages, which no "
                f"migration carries yet (requeue() replays the tokens)")
        imported = dedup = rejected = 0
        pending = []          # (page, [per-pool np page content])
        refused = None
        if (isinstance(payload, dict)
                and payload.get("version") != KV_PAYLOAD_VERSION):
            refused = (f"kv payload version {payload.get('version')!r}: "
                       f"this engine reads version {KV_PAYLOAD_VERSION}")
        ok = (self._prefix_cache and isinstance(payload, dict)
              and refused is None
              and payload.get("page_size") == self.page_size
              and payload.get("n_pools") == len(self._paged)
              and payload.get("dtype") == str(self._pool_dtype)
              # geometry handshake: quantized pages only land in a
              # same-kv_quant pool (a mixed pair falls back to the
              # tokens-only recompute path — the requeue below)
              and payload.get("kv_quant", "none") == self.kv_quant)
        if ok:
            self._pc_clock += 1
            cur = self._pc_root
            for blk in payload.get("blocks") or []:
                toks = np.asarray(blk["tokens"],
                                  np.int32).reshape(-1)
                if toks.size != self.page_size:
                    rejected += 1
                    break
                key = toks.tobytes()
                child = cur.children.get(key)
                if child is not None:
                    child.stamp = self._pc_clock
                    cur = child
                    dedup += 1
                    continue
                data = blk.get("data") or []
                crcs = blk.get("crc")
                if len(data) != len(self._paged) or (
                        crcs is not None
                        and [zlib.crc32(np.ascontiguousarray(
                                d).tobytes()) for d in data]
                        != [int(c) for c in crcs]):
                    rejected += 1
                    break
                alloc = self._alloc_pages(1)
                if alloc is None:
                    break        # pool pressure: partial seed is fine
                page = alloc[0]
                pending.append(
                    (page, [np.ascontiguousarray(d) for d in data]))
                node = _PrefixCacheNode(key, page, cur)
                node.stamp = self._pc_clock
                cur.children[key] = node
                self._pc_nodes[page] = node
                cur = node
                imported += 1
        if pending:
            # defer the device write until every block has been
            # verified/alloc'd, then land the whole request in one
            # batched dispatch (nothing dispatches between alloc and
            # here — the engine is single-threaded, so a node briefly
            # pointing at an unwritten page is unobservable). Pad to
            # the per-request page bound with copies of the last page
            # so every import shares ONE compiled shape — duplicate
            # scatter indices carrying identical content are
            # order-independent, and per-count shapes would recompile
            # mid-pump, putting XLA compiles on the migration path.
            width = max(len(pending), self.pages_per_slot)
            padded = pending + [pending[-1]] * (width - len(pending))
            dst = jnp.asarray([p for p, _ in padded], jnp.int32)
            stacked = [jnp.asarray(
                np.stack([d[i] for _, d in padded], axis=0),
                self._pool_dtypes[pi]) for i, pi in enumerate(self._paged)]
            self._set_paged(_kv_write_pages(self._paged_arrays(), dst,
                                            stacked))
        _t_obs = time.perf_counter()
        if imported:
            self._c_kv_imported.inc(imported)
        if dedup:
            self._c_kv_dedup.inc(dedup)
        if rejected:
            self._c_kv_rejects.inc(rejected)
        counts = {"imported": imported, "dedup": dedup,
                  "rejected": rejected}
        if refused is not None:
            counts["refused"] = refused
        _frec.record_event("migrate_in", req=req.request_id, **counts)
        self._obs_s += time.perf_counter() - _t_obs
        record_hop(req, "migrate_in",
                   replica=getattr(self, "_fleet_replica_id", None),
                   **counts)
        self.requeue(req)
        self._audit_pages("kv_import")
        return counts

    def step(self):
        """Admit what fits, advance every slot one scheduler turn (one
        batching-step program), drain finished slots. Returns the
        requests completed by this step. Step failures hit the same
        containment boundary as :meth:`run`."""
        with self._turn():
            self._admit()
            try:
                rec = self._dispatch_turn()
                if rec is not None:
                    self._harvest_step(rec)
            except Exception as exc:  # noqa: BLE001 — containment boundary
                if not self._containable(exc):
                    raise
                return self._contain_step_failure(exc) + self._drain()
            return self._drain()

    def _dispatch_turn(self):
        """Launch the turn's program — the speculative step on a
        speculative engine, else the unified step — and return its
        in-flight record for :meth:`_harvest_step`; None when no slot
        would advance. ``step()``, the driver's idle turn and its
        pipelined successor all dispatch through here."""
        if not self._worth_step():
            return None
        return self._dispatch_spec_step() if self._spec \
            else self._dispatch_step()

    @contextlib.contextmanager
    def _turn(self):
        """One scheduler turn — a ``step()`` call or one iteration of
        :meth:`_run_driver`'s loop: the ``serving/step`` span (``seq``:
        the newest dispatched program when the turn closed) and the
        wall seconds ``gauges()`` divides by, whoever pumps."""
        t0 = time.perf_counter()
        with _span("serving/step") as sp:
            try:
                yield
            finally:
                sp.set_args(seq=self._seq)
                self._stats["run_seconds"] += time.perf_counter() - t0

    def run(self):
        """Drive until every queued request completes; returns them in
        completion order.

        Pipelined: the NEXT step is dispatched before the previous
        step's packed output is fetched — device state chains
        asynchronously, so the harvest round-trip and the whole
        admission pass (host bookkeeping: the slot state it decides
        rides the next dispatch's upload) execute while the successor
        runs on device. A slot that finished inside the previous step
        is inactive in the successor (its device active flag is already
        False), so the overlap never decodes garbage, and a slot
        admitted meanwhile streams its prompt in the step after. The
        successor is SKIPPED when the host can prove it would do no
        work: no prefilling slot exists and every active slot's
        predicted budget is exhausted (``chunks_empty`` measures the
        residue, eos stops the host cannot predict).

        A speculative engine runs the same loop SERIALLY: drafts are
        functions of the harvested token history (n-gram lookup) or of
        the post-harvest device state (self-spec), so a successor
        dispatched before the harvest would draft from a stale stream.
        The round trip it un-hides is amortized by the ~K tokens each
        step emits instead of one."""
        return self._run_driver()

    def _run_driver(self):
        """The scheduler loop behind :meth:`run`.

        Reliability structure (ISSUE 10): every compiled-step
        dispatch/harvest runs inside the containment boundary — a step
        exception quarantines the implicated request(s) and resets
        slots/pages instead of killing the engine. Pure overload never
        stalls: a no-progress turn with occupied slots evicts the
        youngest, lowest-priority occupant for recompute (a wedged slot
        cannot hold the pool hostage); the stall ``RuntimeError``
        survives only as the watchdog-backed deadlock diagnostic for a
        pool that is exhausted with NO occupant left to evict (a true
        leak)."""
        done = []
        inflight = None
        deadlock_evictions = 0
        max_deadlock = max(8, 2 * self.num_slots)
        # the containment budget is PER RUN: a healthy later run must
        # not inherit an earlier run's spent budget
        self._containments_run = 0

        def contained(exc, cohort=None):
            """Quarantine/requeue for a containable compiled-step
            failure; None when the failure must escape (audit
            assertion, budget spent — the EngineSupervisor's job).
            ``cohort``: the failed program's dispatch-time request
            snapshot, for accurate blame."""
            if not self._containable(exc):
                return None
            return self._contain_step_failure(exc, cohort=cohort)

        _wd_token = _frec.arm("serving run loop")
        try:
            while True:
                with self._turn():
                    # watchdog progress mark: a hung device fetch or a
                    # scheduler livelock stops the beats and the flight
                    # recorder dumps a diagnosable bundle (owner-token
                    # scoped: another component's beats cannot mask us)
                    _frec.beat(_wd_token)
                    if inflight is not None:
                        # the successor first (a speculative engine
                        # has none: it runs serially): the device never
                        # idles while the host harvests/drains/admits.
                        # Containment wraps ONLY the compiled dispatch/
                        # harvest — a host-side scheduler bug in
                        # _admit/_drain/_reap is not a per-request fault
                        # and must surface, not be laundered into strikes
                        try:
                            nxt = None if self._spec \
                                else self._dispatch_turn()
                        except Exception as exc:  # noqa: BLE001
                            extra = contained(exc)
                            if extra is None:
                                raise
                            inflight = None
                            done.extend(extra)
                            continue
                        try:
                            self._harvest_step(inflight)
                        except Exception as exc:  # noqa: BLE001
                            # blame the HARVESTED program's dispatch-time
                            # cohort (rec[1]), not whoever occupies the
                            # slots now
                            extra = contained(exc, cohort=inflight[1])
                            if extra is None:
                                raise
                            inflight = None
                            done.extend(extra)
                            continue
                        done.extend(self._drain())
                        # admissions overlap nxt's on-device run — the
                        # gauge distinguishing overlapped / serialized
                        self._overlap_admission = nxt is not None
                        try:
                            self._admit()
                        finally:
                            self._overlap_admission = False
                        inflight = nxt
                        continue
                    n_before = len(done)
                    self._admit()
                    done.extend(self._drain())
                    try:
                        inflight = self._dispatch_turn()
                    except Exception as exc:  # noqa: BLE001
                        extra = contained(exc)
                        if extra is None:
                            raise
                        inflight = None
                        done.extend(extra)
                        continue
                    if inflight is not None or len(done) > n_before:
                        # a recovered wedge must not eat the deadlock
                        # budget forever: the cap bounds CONSECUTIVE
                        # fruitless evictions, not a run's lifetime total
                        deadlock_evictions = 0
                        continue
                    if not self.queue:
                        break
                    # nothing dispatched, harvested, drained or admitted
                    # this turn, but requests still queued: overload always
                    # progresses (slots drain -> pages free -> admission),
                    # so something undrainable holds the pool
                    occupied = [s for s in range(self.num_slots)
                                if self.slot_req[s] is not None]
                    if occupied and deadlock_evictions < max_deadlock:
                        victim = min(occupied, key=lambda s: (
                            self.slot_req[s].priority,
                            -self.slot_req[s].t_admit))
                        deadlock_evictions += 1
                        self._evict_slot(victim, requeue=True,
                                         reason="deadlock")
                        continue
                    # pool exhausted with no evictable occupant (or the
                    # eviction budget burned without progress): a true
                    # leak/deadlock. Dump a flight-recorder bundle first:
                    # the ring's recent scheduler turns + pool state are
                    # the post-mortem
                    rec = _frec.get_recorder()
                    if rec is not None:
                        _frec.record_event(
                            "serving_stall", queued=len(self.queue),
                            free_pages=len(self._free_pages),
                            occupied=len(occupied))
                        try:
                            rec.dump("serving engine stalled: queued "
                                     "request cannot be admitted")
                        except OSError:
                            pass    # the diagnostic RuntimeError below
                                    # must not be replaced by a failed
                                    # bundle write
                    raise RuntimeError(
                        "serving engine stalled: queued request cannot "
                        "be admitted (page pool exhausted?)")
        finally:
            _frec.disarm(_wd_token)
            self._emit_gauges()
        return done

    # ---- step-level fault containment (ISSUE 10) -------------------------

    def _containable(self, exc):
        """Is this step failure containable? AssertionError is the
        audit invariant speaking — never swallow it; past the per-run
        containment budget the failure escapes to the
        EngineSupervisor (an engine failing every step must not loop
        forever)."""
        if isinstance(exc, AssertionError):
            return False
        return self._containments_run < self.max_containments

    def _contain_step_failure(self, exc, cohort=None):
        """Step-level fault isolation: one failed compiled step (a
        poisoned sampler, NaN materializing at the fetch, an injected
        fault) must not kill every in-flight stream. Every occupied
        slot gets a STRIKE — a poison request rides every step it is
        scheduled into, so repeat offenders cross ``max_strikes`` and
        are quarantined with a typed error, while co-scheduled
        innocents are requeued for recompute-style replay (suspects
        re-enter SOLO, so the next fault implicates exactly one
        request). Device state after a failed step is unreliable (the
        pools/hot-state chain ran through the failed program), so it
        is rebuilt from scratch and every survivor replays through the
        recompute path. Returns the requests completed (quarantined)
        by the containment.

        ``cohort`` is the failed program's DISPATCH-TIME request
        snapshot when the caller has one (a harvest record): only
        cohort members are struck — a request admitted during the
        overlap window must not be blamed for a program it never
        rode (it still resets and replays, unblamed)."""
        self._containments_run += 1
        self._stats.inc("containments")
        _frec.record_event(
            "containment", error=repr(exc)[:200],
            occupied=int(sum(r is not None for r in self.slot_req)))
        blame = None if cohort is None else \
            {id(r) for r in cohort if r is not None}
        requeue, quarantine = [], []
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None or req.finished:
                continue
            if blame is None or id(req) in blame:
                req.strikes += 1
            (quarantine if req.strikes >= self.max_strikes
             else requeue).append(req)
        self._reset_device_state()
        done = []
        for req in requeue:
            req.preemptions += 1
        # survivors replay in ARRIVAL order at the queue front
        # (appendleft in slot order would reverse it — later arrivals
        # must not replay first; slot order itself is shuffled by
        # drain/re-admit churn)
        requeue.sort(key=lambda r: (r.t_arrive, r.request_id))
        self.queue.extendleft(reversed(requeue))
        for req in quarantine:
            done.append(self._finish_error(
                req, RequestQuarantined(req.request_id, repr(exc))))
        self._audit_pages("containment")
        return done

    def _paged_arrays(self):
        """The pools that have a page axis, in pool order."""
        return [self.pools[i]._data for i in self._paged]

    def _set_paged(self, arrays):
        for i, a in zip(self._paged, arrays):
            self.pools[i].set_data(a)

    def _reset_device_state(self):
        """Rebuild the pools, the free list and all per-slot state from
        scratch — FRESH device buffers, so writes still racing out of
        an abandoned in-flight program land in orphaned arrays, never
        in state the engine will read again. Compiled programs are pure
        functions of their inputs and are kept (the pool Tensors too:
        their data is rebound)."""
        B = self.num_slots
        for p, s, dt in zip(self.pools, self._pool_shapes,
                            self._pool_dtypes):
            p.set_data(jnp.zeros(s, dt))
        self._free_pages = deque(range(1, self.num_pages))
        self._deferred_free = []
        self.tables[:] = 0
        self.ctx[:] = 0
        self.active[:] = False
        self.limits[:] = 0
        self.slot_eos[:] = -1
        self.slot_req = [None] * B
        self.slot_pages = [[] for _ in range(B)]
        # the rebuilt pools are zeroed, so every cached page's content
        # is gone with them: drop the whole radix index (its pages are
        # already back in the rebuilt free list)
        self.slot_shared = [[] for _ in range(B)]
        self._pc_root = _PrefixCacheNode(None, 0, None)
        self._pc_nodes = {}
        # exported-prefix pins die with the index they pointed into;
        # the parked migration payloads are host-side copies and
        # survive (the router still delivers them)
        self._exported_pins = {}
        self._slot_prompt = [None] * B
        self._prefilling[:] = False
        self._prefill_off[:] = 0
        self._act_target[:] = False
        self._pred_ctx[:] = 0
        self._act_since[:] = 0
        self._emits_inflight[:] = 0
        self._dev_tok = jnp.zeros((B,), jnp.int32)
        self._dev_ctx = jnp.zeros((B,), jnp.int32)
        self._dev_act = jnp.zeros((B,), bool)
        self._slot_reset[:] = -1
        self._staged_rows = 0
        # the RNG key chained through the failed program; rebuild from
        # the seed (greedy streams are unaffected; sampled streams
        # restart their key chain — documented in docs/serving.md)
        self._key = jax.random.PRNGKey(
            self._seed + self._containments_run)
        self._last_fetch_dispatch_seq = self._seq
        self._last_harvest_seq = self._seq

    # ---- unified batching step (ONE compiled program) --------------------

    def _worth_step(self):
        """Would a unified step advance anything? Prefilling slots
        always do; decode slots only while the host's ctx prediction
        leaves budget (an eos stop the host cannot see may still yield
        an empty step — counted in ``chunks_empty``)."""
        return bool(self._prefilling.any()
                    or np.any(self.active
                              & (self.limits > self._pred_ctx)))

    def _unified_static(self):
        """The ONE compiled batching-step program. It computes the
        positions whose result a turn uses, in two parts:

        - a loop over GROUPS of ``self._group`` prefilling slots, its
          trip count the turn's own (``lax.fori_loop`` with a traced
          bound; a turn without a prompt runs none): each trip gathers
          its rows' ids, positions, table rows and per-slot state, runs
          one ``[group, prefill_chunk]`` forward through
          ``ragged_paged_attention`` whose head reads ONE position a row
          (``logits_at``), samples the first token of the rows whose
          prompt ends in this chunk, and scatters results and state
          back. The paged pools go through whole. Padding rows of the
          last group name slot ``num_slots`` — out of range, so every
          scatter drops them — and carry length 0, so their K/V lands
          on the trash page;
        - ``decode_chunk`` decode micro-steps (``lax.scan`` over a
          ``[num_slots, 1]`` forward): the decoding slots ride all of
          them; a slot whose prompt ended in the loop joins after
          micro-step 0 with its first token — prefill→decode transition
          never leaves the device.

        Its arguments: the turn's ONE upload (``self._unified_up``:
        prompt chunks, row list, and the tables, limits, stop tokens and
        context resets the host decided), the chained tok/ctx/active
        state and the key. The pools are not arguments: the step reads
        ``self.pools`` and reassigns them (:func:`_keep_pools`), so
        ``to_static`` donates their buffers and the loops update them in
        place. The packed output carries every emitted token of the step
        (a first token in column 0, like a decoding slot's) plus the
        ctx/active mirrors in ONE int32 fetch. The forward is traced
        twice: the loop's body and the scan's."""
        if self._unified_fn is not None:
            return self._unified_fn
        from ..jit import to_static
        model = self.model
        greedy = self.greedy
        temperature = self.temperature
        n_steps = 1 + self._n_decode
        cpool = self._counter_pool
        kinds = tuple(self._pool_kinds)
        G = self._group
        R, MP = self._ring, self.pages_per_slot

        up = self._unified_up
        pools = self.pools

        def ustep(up_t, tok_t, ctx_t, act_t, key_t):
            fwd = model.forward

            def sample(lg, key):
                lg = lg.astype(jnp.float32)
                if greedy:
                    return jnp.argmax(lg, -1).astype(jnp.int32), key
                key, sub = jax.random.split(key)
                return jax.random.categorical(
                    sub, lg / temperature).astype(jnp.int32), key

            def fn(upload, tok, ctx, act, key, *pool_leaves):
                b = tok.shape[0]
                f = up.split(upload)
                ids, nq, rows, n_rows = (f["ids"], f["nq"], f["rows"],
                                         f["n_rows"])
                last, tgt = f["last"] != 0, f["tgt"] != 0
                tbl, lim, eos_arr = f["tbl"], f["lim"], f["eos"]
                ctx, act = _apply_slot_resets(f["reset"], ctx, act)
                if cpool is not None:
                    # the model's pass counters start every step at 0
                    # and leave with its packed fetch
                    pool_leaves = list(pool_leaves)
                    pool_leaves[cpool] = jnp.zeros_like(
                        pool_leaves[cpool])
                # stale instant-eos guard: a slot whose last token is
                # its stop token does not decode
                act = act & ((eos_arr < 0) | (tok != eos_arr))
                # the window layers' table (cache_spec.WindowKV): logical
                # page j of slot s is page 1 + s * R + j % R of its ring
                ring = 1 + R * jnp.arange(b, dtype=jnp.int32)[:, None] \
                    + jnp.arange(MP, dtype=jnp.int32)[None, :] % R \
                    if R else None

                def tables(rows_tbl, gate, rows_ring):
                    return (Tensor(rows_tbl), Tensor(gate)) \
                        + ((Tensor(rows_ring),) if R else ())

                def group(g, carry):
                    tok_c, ctx_c, fire_c, act_c, key_c, leaves = carry
                    rw = jax.lax.dynamic_slice(rows, (g * G,), (G,))
                    real = rw < b
                    at = jnp.minimum(rw, b - 1)      # gathers stay inside
                    nq_g = jnp.where(real, nq[at], 0)
                    ctx_g = ctx[at]
                    with no_grad():
                        logits, ncaches = fwd(
                            Tensor(ids[at]),
                            caches=[Tensor(a[at] if k == "state" else a)
                                    for k, a in zip(kinds, leaves)],
                            pos=Tensor(ctx_g[:, None]),
                            tables=tables(tbl[at], nq_g,
                                          ring[at] if R else None),
                            logits_at=Tensor(jnp.maximum(nq_g - 1, 0)))
                    sampled, key_c = sample(logits._data[:, 0], key_c)
                    fire_g = real & last[at]
                    nxt_g = jnp.where(fire_g, sampled, tok[at])
                    ctx1_g = ctx_g + nq_g
                    hit_eos = (eos_arr[at] >= 0) & (nxt_g == eos_arr[at])
                    act_g = fire_g & tgt[at] & (ctx1_g < lim[at]) \
                        & ~hit_eos

                    def put(whole, part):
                        # a padding row's index is out of range: dropped
                        return whole.at[rw].set(part, mode="drop")

                    return (put(tok_c, nxt_g), put(ctx_c, ctx1_g),
                            put(fire_c, fire_g), put(act_c, act_g), key_c,
                            tuple(put(a, n._data) if k == "state"
                                  else n._data for k, a, n in
                                  zip(kinds, leaves, ncaches)))

                none = jnp.zeros((b,), bool)
                tok_p, ctx_p, fire_pre, act_pre, key, leaves_p = \
                    jax.lax.fori_loop(
                        0, (n_rows + (G - 1)) // G, group,
                        (tok, ctx, none, none, key, tuple(pool_leaves)))

                def body(carry, first):
                    tok_c, ctx_c, act_c, key_c, leaves = carry
                    with no_grad():
                        lgs, ncaches = fwd(
                            Tensor(tok_c.reshape(b, 1)),
                            caches=[Tensor(a) for a in leaves],
                            pos=Tensor(ctx_c[:, None]),
                            tables=tables(tbl, act_c, ring))
                    nx, key_c = sample(lgs[:, -1]._data, key_c)
                    ctx_n = ctx_c + act_c.astype(jnp.int32)
                    nx = jnp.where(act_c, nx, tok_c)
                    still = act_c & (ctx_n < lim) & \
                        ((eos_arr < 0) | (nx != eos_arr))
                    # micro-step 0 is the decoding slots' alone; a slot
                    # whose prompt ended in the loop joins after it: its
                    # first token (the loop left it in the carry, with
                    # its ctx) takes column 0
                    emit = act_c | (first & fire_pre)
                    still = still | (first & act_pre)
                    new_leaves = tuple(t._data for t in ncaches)
                    return (nx, ctx_n, still, key_c, new_leaves), \
                        (jnp.where(emit, nx, -1), emit)

                carry0 = (tok_p, ctx_p, act & (nq == 0), key, leaves_p)
                carry, (toks, emitted) = jax.lax.scan(
                    body, carry0, jnp.arange(n_steps) == 0)
                tok_f, ctx_f, act_f, key_f, leaves_f = carry
                cols = [toks.T.astype(jnp.int32),
                        emitted.T.astype(jnp.int32),
                        ctx_f[:, None].astype(jnp.int32),
                        act_f[:, None].astype(jnp.int32)]
                if cpool is not None:
                    cols.append(jnp.broadcast_to(
                        leaves_f[cpool][None, :],
                        (b, leaves_f[cpool].shape[0])))
                packed_out = jnp.concatenate(cols, axis=1)
                return (packed_out, tok_f, ctx_f, act_f, key_f) \
                    + tuple(leaves_f)

            return _keep_pools(pools, _apply_multi(
                fn, [up_t, tok_t, ctx_t, act_t, key_t] + pools,
                n_out=5 + len(pools)))

        self._unified_fn = to_static(ustep)
        self._compiled.add(("unified", self.prefill_chunk, n_steps))
        return self._unified_fn

    def _stage_prompt_chunks(self, f):
        """Write the next ``prefill_chunk`` prompt tokens of every
        prefilling slot (at most ``admit_batch`` of them) into the
        upload's fields ``f``, as the step programs take them: ``ids
        [B, C]``, ``nq`` (tokens staged per slot), ``last`` (the prompt
        ends in this chunk), ``tgt`` (the slot decodes afterwards) and,
        where the layout has them (the unified step's), ``rows`` (the
        slots that carry a chunk, compacted; padded to whole groups with
        ``num_slots``, an index no scatter lands on) and ``n_rows``.
        Returns how many slots carry a chunk."""
        B, C = self.num_slots, self.prefill_chunk
        staged = []
        for slot in range(B):
            if not self._prefilling[slot] \
                    or len(staged) >= self.admit_batch:
                continue
            prm = self._slot_prompt[slot]
            off = int(self._prefill_off[slot])
            v = min(C, len(prm) - off)
            f["ids"][slot, :v] = prm[off:off + v]
            f["nq"][slot] = v
            f["last"][slot] = off + v == len(prm)
            f["tgt"][slot] = self._act_target[slot]
            staged.append(slot)
        if "rows" in f:
            f["rows"][:] = B
            f["rows"][:len(staged)] = staged
            f["n_rows"][()] = len(staged)
        return len(staged)

    # ---- the turn's ONE upload -------------------------------------------

    def _slot_fields(self):
        """The per-slot fields every upload carries: what admission and
        eviction decided on the host since the last launch."""
        B = self.num_slots
        return [("tbl", (B, self.pages_per_slot)), ("lim", (B,)),
                ("eos", (B,)), ("reset", (B,))]

    def _stage_reset(self, slot, ctx):
        """Stage "this slot's device context is now ``ctx``, and it is
        not decoding" for the next launch (the last value wins)."""
        self._slot_reset[slot] = ctx
        self._staged_rows += 1

    def _stage_upload(self, layout):
        """A fresh host buffer of ``layout`` and its field views, the
        slot state filled in from the host's arrays."""
        buf, f = layout.host()
        f["tbl"][:] = self.tables
        f["lim"][:] = self.limits
        f["eos"][:] = self.slot_eos
        f["reset"][:] = self._slot_reset
        return buf, f

    def _ship(self, buf):
        """The upload itself: one host array to the device."""
        self._stats.inc("step_uploads")
        return Tensor(jnp.asarray(buf))

    def _chained(self):
        """What a step program takes after its upload: the chained
        tok / ctx / active state and the key (the pools are state the
        step reassigns, not arguments)."""
        return [Tensor(self._dev_tok), Tensor(self._dev_ctx),
                Tensor(self._dev_act), Tensor(self._key)]

    def _chain(self, res):
        """Keep a launched step program's outputs as the next one's
        chained inputs and retire the staged resets its upload carried.
        Returns the packed output (not fetched)."""
        packed, tok_f, ctx_f, act_f, key_f = res
        self._dev_tok = tok_f._data
        self._dev_ctx = ctx_f._data
        self._dev_act = act_f._data
        self._key = key_f._data
        self._stats.inc("staged_slot_updates", self._staged_rows)
        self._staged_rows = 0
        self._slot_reset[:] = -1
        return packed

    def _count_dispatch(self, sp, mode, n_steps, n_active, n_pre,
                        n_tok, n_groups, group_rows):
        """The counters, the flight-recorder turn and the
        ``serving/dispatch`` span's args of one dispatched unified
        step (plain or speculative). The step computed ``n_groups``
        passes of ``group_rows x prefill_chunk`` prompt positions."""
        B = self.num_slots
        _t_obs = time.perf_counter()
        self._stats.inc("chunks")
        self._stats.inc("unified_steps")
        self._stats.inc("chunk_slot_steps", B * n_steps)
        if n_pre:
            self._stats.inc("prefill_waves")
        self._stats.inc("active_slot_steps", n_active * n_steps)
        # how full the prompt passes are: a pass computes every row's
        # prefill_chunk positions whether or not they hold a token
        self._stats.inc("prefill_tokens", n_tok)
        self._stats.inc("prefill_positions",
                        n_groups * group_rows * self.prefill_chunk)
        from ..profiler.trace import get_tracer
        _tr = get_tracer()
        if _tr.enabled:
            _tr.counter("serving/active_slots", n_active,
                        queued=len(self.queue), chunk_len=n_steps,
                        prefilling=n_pre)
        _frec.record_event("sched_turn", seq=self._seq, mode=mode,
                           active=n_active, queued=len(self.queue),
                           prefilling=n_pre, chunk_len=n_steps)
        sp.set_args(seq=self._seq, active=n_active, prefilling=n_pre,
                    prefill_tokens=n_tok, prefill_groups=n_groups,
                    chunk_len=n_steps)
        self._obs_s += time.perf_counter() - _t_obs

    def _dispatch_step(self):
        """Launch one unified step (async) and chain the device state.
        Returns an in-flight record for :meth:`_harvest_step` — the
        packed output is NOT fetched here, so a caller may overlap the
        fetch with the next step's on-device compute."""
        with _span("serving/dispatch") as sp:
            B = self.num_slots
            with _span("serving/dispatch.stage"):
                buf, f = self._stage_upload(self._unified_up)
                n_pre = self._stage_prompt_chunks(f)
                upload = self._ship(buf)
            nq, last, tgt = f["nq"], f["last"], f["tgt"]
            fn = self._unified_static()
            self._seq += 1
            self._last_fetch_dispatch_seq = self._seq
            n_steps = 1 + self._n_decode
            # a slot advances this step if it decodes with budget left OR
            # streams prompt tokens (a completing prompt decodes the
            # in-program tail too, so its tokens must be credited here)
            n_active = int(np.sum((self.active
                                   & (self.limits > self._pred_ctx))
                                  | (nq > 0)))
            self._count_dispatch(sp, "unified", n_steps, n_active, n_pre,
                                 int(nq.sum()),
                                 -(-n_pre // self._group), self._group)
            # the call stays in the dispatcher's own frame: a step traced
            # and lowered one Python frame deeper took 0.3-1.0 s longer
            # to build at every start (PERF.md section 6, PR 37)
            with _span("serving/dispatch.launch"):
                res = fn(upload, *self._chained())
            packed = self._chain(res)
            # host bookkeeping: prompt-stream progress is exact; decode
            # activity is a prediction refined by the harvested mirrors
            emits = np.zeros((B,), bool)
            for slot in range(B):
                if nq[slot] > 0:
                    self._prefill_off[slot] += nq[slot]
                    if last[slot]:
                        req = self.slot_req[slot]
                        tl = len(self._slot_prompt[slot])
                        req.t_prefill_done = time.perf_counter()
                        self._prefilling[slot] = False
                        self.ctx[slot] = tl
                        # the first token + in-program decode tail land in
                        # THIS step; mirrors from any EARLIER in-flight
                        # step must not clobber the activation
                        self.active[slot] = bool(tgt[slot])
                        self._act_since[slot] = self._seq
                        self._pred_ctx[slot] = min(
                            int(self.limits[slot]), tl + self._n_decode)
                        # the prompt's full pages are final now (decode
                        # writes land past tl): publish them for sharing
                        self._pc_insert(slot)
                        emits[slot] = True
                elif self.active[slot] \
                        and self.limits[slot] > self._pred_ctx[slot]:
                    self._pred_ctx[slot] = min(
                        int(self.limits[slot]),
                        int(self._pred_ctx[slot]) + n_steps)
                    emits[slot] = True
            self._emits_inflight += emits.astype(np.int32)
            return (packed, list(self.slot_req), emits, n_steps, self._seq)

    def _harvest_step(self, rec):
        """Fetch one in-flight unified step's packed output and apply
        it: append emitted tokens, refresh the ctx/active mirrors
        (unless the slot was re-admitted, or activated by a LATER
        dispatch, since this step went out)."""
        with _span("serving/harvest") as sp:
            packed, snap_req, emits, n_steps, seq = rec
            with _span("serving/harvest.fetch"):
                arr = np.asarray(packed._data)        # the ONE fetch
            self._last_harvest_seq = max(self._last_harvest_seq, seq)
            self._release_deferred()
            toks_np = arr[:, :n_steps]
            emitted_np = arr[:, n_steps:2 * n_steps].astype(bool)
            ctx_m = arr[:, 2 * n_steps].astype(np.int32)
            act_m = arr[:, 2 * n_steps + 1].astype(bool)
            t_now = time.perf_counter()
            appended = 0
            for slot in range(self.num_slots):
                req = snap_req[slot]
                if req is not self.slot_req[slot]:
                    continue      # slot re-admitted since this dispatch
                if emits[slot]:
                    self._emits_inflight[slot] -= 1
                if self._act_since[slot] <= seq:
                    self.ctx[slot] = ctx_m[slot]
                    self.active[slot] = act_m[slot]
                    self._pred_ctx[slot] = max(int(self._pred_ctx[slot]),
                                               int(ctx_m[slot]))
                if req is None or req.finished:
                    continue
                # a clean harvest exonerates its riders: one solo step
                # clears a suspect, so a containment cannot serialize the
                # whole batch into solo-to-completion replays
                req.strikes = 0
                for j in range(n_steps):
                    if emitted_np[slot, j]:
                        if not req.tokens:
                            req.t_first = t_now
                        req.tokens.append(int(toks_np[slot, j]))
                        appended += 1
            _t_obs = time.perf_counter()
            self._stats.inc("tokens_emitted", appended)
            if appended == 0:
                self._stats.inc("chunks_empty")
            # a SPEC step's packed output carries two extra accounting
            # columns (committed-draft and drafted counts per slot) past
            # the layout this method parses — fold them into the spec
            # economics counters
            if self._counter_names and not self._spec:
                # the model's pass counters ride the same fetch: every
                # row repeats them
                base = 2 * n_steps + 2
                for j, name in enumerate(self._counter_names):
                    self._c_model[name].inc(int(arr[0, base + j]))
            elif arr.shape[1] > 2 * n_steps + 2:
                nds = arr[:, 2 * n_steps + 3]
                accs = arr[:, 2 * n_steps + 2]
                drafted = int(nds.sum())
                if drafted:
                    committed = int(accs.sum())
                    self._c_spec_drafted.inc(drafted)
                    self._c_spec_accepted.inc(committed)
                    self._c_spec_rejected.inc(drafted - committed)
            sp.set_args(seq=seq, appended=appended)
            self._obs_s += time.perf_counter() - _t_obs

    # ---- speculative decoding (ISSUE 18) ---------------------------------

    def _unified_spec_static(self):
        """The speculative batching-step program: ONE ragged mixed pass
        over all slots (``[num_slots, prefill_chunk]``; it has not taken
        :meth:`_unified_static`'s group loop yet) — prefill slots stream
        prompt chunks — in which an active decode slot rides ``1 + n_d``
        tokens (its pending token in column 0, host-proposed draft
        tokens in columns ``1..n_d``) as a short prefill-shaped chunk,
        and the ``decode_chunk - 1`` scan tail is replaced by
        DISTRIBUTION-EXACT verification of the drafts against the
        target logits:

        - greedy: accept while the draft matches the argmax (so spec
          streams are token-identical to the plain engine);
        - sampling: accept draft ``d_j`` with prob ``min(1, p_j[d_j])``
          (point-mass draft), resample the first rejection from the
          renormalized residual, bonus-sample from ``p_K`` when every
          draft holds — each emitted position marginally exact.

        Accepted tokens COMMIT by advancing ctx over their already-
        written KV (``ops.paged_attention.paged_verify_write``
        semantics); rejected positions simply stay behind ctx, unread
        and overwritten by the next chunk. The packed output keeps the
        harvest layout with ``n_steps = K + 1`` plus two trailing
        accounting columns (committed drafts, drafted count)."""
        if self._spec_fn is not None:
            return self._spec_fn
        from ..jit import to_static
        model = self.model
        greedy = self.greedy
        temperature = self.temperature
        C = self.prefill_chunk
        K = self._spec_k

        up = self._spec_up
        pools = self.pools

        def sstep(up_t, tok_t, ctx_t, act_t, key_t):
            fwd = model.forward

            def fn(upload, tok, ctx, act, key, *pool_leaves):
                b = tok.shape[0]
                f = up.split(upload)
                ids, nq, nd = f["ids"], f["nq"], f["nd"]
                last, tgt = f["last"] != 0, f["tgt"] != 0
                tbl, lim, eos_arr = f["tbl"], f["lim"], f["eos"]
                ctx, act = _apply_slot_resets(f["reset"], ctx, act)
                # stale instant-eos guard (same as the plain step)
                act = act & ((eos_arr < 0) | (tok != eos_arr))
                is_pre = nq > 0
                dec = act & ~is_pre
                # drafts were clamped host-side against the host ctx;
                # re-gate on the device view (the eos guard above can
                # retire a slot the host still believed active)
                nd_eff = jnp.where(dec, nd, 0).astype(jnp.int32)
                lengths = jnp.where(
                    is_pre, nq,
                    jnp.where(dec, 1 + nd_eff, 0)).astype(jnp.int32)
                ids_eff = ids.at[:, 0].set(
                    jnp.where(is_pre, ids[:, 0], tok))
                with no_grad():
                    logits, npools = fwd(
                        Tensor(ids_eff),
                        caches=[Tensor(a) for a in pool_leaves],
                        pos=Tensor(ctx[:, None]),
                        tables=(Tensor(tbl), Tensor(lengths)))
                lg = logits._data                      # [B, C, V]
                # ---- decode slots: verify drafts on columns 0..K ----
                vlg = lg[:, :K + 1].astype(jnp.float32)
                d = ids[:, 1:K + 1].astype(jnp.int32)  # [B, K]
                jk = jnp.arange(K)[None, :]
                if greedy:
                    tgt_tok = jnp.argmax(vlg, -1).astype(jnp.int32)
                    acc = d == tgt_tok[:, :K]
                else:
                    p = jax.nn.softmax(vlg / temperature, axis=-1)
                    key, sub_u = jax.random.split(key)
                    u = jax.random.uniform(sub_u, (b, K))
                    pd = jnp.take_along_axis(
                        p[:, :K], d[:, :, None], axis=2)[:, :, 0]
                    acc = u < pd
                acc = acc & (jk < nd_eff[:, None])
                # leading-run length = accepted draft count
                n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32),
                                            axis=1), axis=1)
                # target token at the first unaccepted position:
                # rejection resample (draft zeroed, renormalized) or
                # the bonus sample when every draft held
                if greedy:
                    fin = jnp.take_along_axis(
                        tgt_tok, n_acc[:, None], axis=1)[:, 0]
                else:
                    row = jnp.take_along_axis(
                        p, n_acc[:, None, None], axis=1)[:, 0]
                    d_at = jnp.take_along_axis(
                        d, jnp.clip(n_acc, 0, K - 1)[:, None],
                        axis=1)[:, 0]
                    rej = n_acc < nd_eff
                    v_ax = jnp.arange(row.shape[-1])[None, :]
                    row = jnp.where(
                        rej[:, None] & (v_ax == d_at[:, None]),
                        0.0, row)
                    key, sub_f = jax.random.split(key)
                    fin_lg = jnp.where(row > 0, jnp.log(row), -1e30)
                    fin = jax.random.categorical(
                        sub_f, fin_lg).astype(jnp.int32)
                # emission ladder e_0..e_K: accepted drafts, then the
                # target sample; trimmed by per-position ctx budget
                # and a mid-chunk eos (the eos token itself emits,
                # nothing after it — the plain-engine contract)
                d_pad = jnp.concatenate(
                    [d, jnp.zeros((b, 1), jnp.int32)], axis=1)
                jk1 = jnp.arange(K + 1)[None, :]
                e = jnp.where(jk1 < n_acc[:, None], d_pad,
                              fin[:, None])
                eos_hit = (eos_arr[:, None] >= 0) & \
                    (e == eos_arr[:, None])
                eos_before = jnp.cumsum(
                    eos_hit.astype(jnp.int32), axis=1) - \
                    eos_hit.astype(jnp.int32)
                alive = (jk1 <= n_acc[:, None]) \
                    & ((ctx[:, None] + jk1) < lim[:, None]) \
                    & (eos_before == 0) & dec[:, None]
                n_emit = jnp.sum(alive.astype(jnp.int32), axis=1)
                ctx_dec = ctx + n_emit
                last_e = jnp.take_along_axis(
                    e, jnp.clip(n_emit - 1, 0, K)[:, None],
                    axis=1)[:, 0]
                tok_dec = jnp.where(n_emit > 0, last_e, tok)
                still_dec = dec & (n_emit > 0) & (ctx_dec < lim) \
                    & ((eos_arr < 0) | (last_e != eos_arr))
                # ---- prefill slots: plain-step single sample --------
                idx = jnp.clip(lengths - 1, 0, C - 1)
                last_lg = jnp.take_along_axis(
                    lg, idx[:, None, None],
                    axis=1)[:, 0].astype(jnp.float32)
                if greedy:
                    sampled = jnp.argmax(last_lg, -1).astype(jnp.int32)
                else:
                    key, sub_p = jax.random.split(key)
                    sampled = jax.random.categorical(
                        sub_p, last_lg / temperature).astype(jnp.int32)
                fire_pre = is_pre & last
                ctx1 = ctx + lengths
                hit_eos_pre = (eos_arr >= 0) & (sampled == eos_arr)
                act_pre = fire_pre & tgt & (ctx1 < lim) & ~hit_eos_pre
                # ---- merge + pack -----------------------------------
                toks_all = jnp.where(dec[:, None], e, -1)
                toks_all = toks_all.at[:, 0].set(
                    jnp.where(fire_pre, sampled, toks_all[:, 0]))
                emit_all = alive.at[:, 0].set(
                    fire_pre | alive[:, 0])
                tok_f = jnp.where(dec, tok_dec,
                                  jnp.where(fire_pre, sampled, tok))
                ctx_f = jnp.where(dec, ctx_dec, ctx + lengths)
                act_f = jnp.where(is_pre, act_pre,
                                  jnp.where(dec, still_dec, act))
                committed = jnp.where(
                    dec, jnp.minimum(n_acc,
                                     jnp.maximum(n_emit - 1, 0)), 0)
                packed_out = jnp.concatenate(
                    [toks_all.astype(jnp.int32),
                     emit_all.astype(jnp.int32),
                     ctx_f[:, None].astype(jnp.int32),
                     act_f[:, None].astype(jnp.int32),
                     committed[:, None].astype(jnp.int32),
                     nd_eff[:, None].astype(jnp.int32)], axis=1)
                return (packed_out, tok_f, ctx_f, act_f, key) \
                    + tuple(t._data for t in npools)

            return _keep_pools(pools, _apply_multi(
                fn, [up_t, tok_t, ctx_t, act_t, key_t] + pools,
                n_out=5 + len(pools)))

        self._spec_fn = to_static(sstep)
        self._compiled.add(("spec", C, 1 + K))
        return self._spec_fn

    def _dispatch_spec_step(self):
        """Launch one SPECULATIVE unified step: stream prefill chunks
        exactly like :meth:`_dispatch_step`, and for every active
        decode slot with budget propose up to K draft tokens from the
        configured :class:`~.spec_decode.DraftSource`, clamped to
        ``limits - ctx - 1`` so every verify write stays inside the
        slot's allocated table row. Runs serially (dispatch → harvest)
        — see :meth:`run`."""
        with _span("serving/dispatch") as sp:
            B, K = self.num_slots, self._spec_k
            with _span("serving/dispatch.stage"):
                buf, f = self._stage_upload(self._spec_up)
                n_pre = self._stage_prompt_chunks(f)
                ids, nq, last, tgt, nd = (f["ids"], f["nq"], f["last"],
                                          f["tgt"], f["nd"])
                drafting = [s for s in range(B)
                            if self.active[s] and not self._prefilling[s]
                            and self.slot_req[s] is not None
                            and int(self.limits[s]) - int(self.ctx[s]) > 1]
                if drafting:
                    drafts, counts = self._spec_source.propose(
                        self, drafting, K)
                    for s in drafting:
                        c = min(int(counts[s]), K,
                                int(self.limits[s]) - int(self.ctx[s]) - 1)
                        if c > 0:
                            ids[s, 1:1 + c] = drafts[s, :c]
                            nd[s] = c
                upload = self._ship(buf)
            fn = self._unified_spec_static()
            self._seq += 1
            self._last_fetch_dispatch_seq = self._seq
            n_steps = 1 + K
            n_active = int(np.sum((self.active
                                   & (self.limits > self._pred_ctx))
                                  | (nq > 0)))
            self._c_spec_steps.inc()
            # the speculative step keeps its one [B, C] mixed pass
            self._count_dispatch(sp, "spec", n_steps, n_active, n_pre,
                                 int(nq.sum()), 1, B)
            # called from this frame, as in _dispatch_step
            with _span("serving/dispatch.launch"):
                res = fn(upload, *self._chained())
            packed = self._chain(res)
            emits = np.zeros((B,), bool)
            for slot in range(B):
                if nq[slot] > 0:
                    self._prefill_off[slot] += nq[slot]
                    if last[slot]:
                        req = self.slot_req[slot]
                        tl = len(self._slot_prompt[slot])
                        req.t_prefill_done = time.perf_counter()
                        self._prefilling[slot] = False
                        self.ctx[slot] = tl
                        self.active[slot] = bool(tgt[slot])
                        self._act_since[slot] = self._seq
                        # the spec step has NO in-program decode tail:
                        # exactly the first token lands this turn
                        self._pred_ctx[slot] = tl
                        self._pc_insert(slot)
                        emits[slot] = True
                elif self.active[slot] \
                        and self.limits[slot] > self._pred_ctx[slot]:
                    # at least the target sample always lands; the exact
                    # accepted length arrives with the harvest mirrors
                    self._pred_ctx[slot] = min(
                        int(self.limits[slot]),
                        int(self._pred_ctx[slot]) + 1)
                    emits[slot] = True
            self._emits_inflight += emits.astype(np.int32)
            return (packed, list(self.slot_req), emits, n_steps, self._seq)

    def gauges(self) -> dict:
        """Serving observability surface (profiler subsystem):

        - ``slot_occupancy``: emitted tokens / dispatched slot-steps —
          the fraction of compiled slot-steps that produced a token.
        - ``active_occupancy``: slots active at dispatch / all slots —
          the drain/re-admit idle share specifically.
        - ``prefill_overlap_frac``: admissions made while a step was in
          flight (``run()``'s pipelined successor; 0 under ``step()``).
        - ``tokens_per_s``: emitted tokens / wall seconds inside
          scheduler turns (``serving/step`` spans), whoever pumps them:
          ``run()``, or ``step()`` from an ApiServer / fleet replica.
        - ``ttft_ms_p50/p99``: first-token-on-host percentiles of
          completed requests. The clock starts at ``add_request``, not
          when the request was due at the caller.
        - ``itl_ms_p50/p99``: percentiles of a PER-REQUEST MEAN —
          (t_done - t_first) / (tokens - 1) of each request with ≥2
          tokens — not of single token gaps: a request's longest gap
          is averaged away.
        - ``prefill_tokens`` / ``prefill_positions`` / ``prefill_fill``:
          prompt tokens carried by the dispatched programs, the prompt
          positions those programs computed, filled or not (the unified
          step: groups run x rows a group x ``prefill_chunk``; a
          speculative step's pass: ``num_slots x prefill_chunk``), and
          their ratio — how full the prompt passes are.
        - ``compiled_programs``: distinct compiled signatures this
          engine built — steady-state 1 (the single batching-step
          program). The compile-budget CI gate asserts on this.
        - ``chunks_empty``: harvested programs that delivered no
          tokens (eos stops the host could not predict).
        - ``prefill_waves``: steps that carried prompt tokens (≥1
          prefilling slot).
        - ``unified_steps``: batching-step programs dispatched.
        - ``step_uploads`` / ``staged_slot_updates`` /
          ``uploads_per_step``: host arrays the dispatched steps shipped
          to the device, the slot rows (admissions + device clears)
          whose state rode them, and uploads / steps — 1.0: a turn's
          host-decided state reaches the device in ONE transfer and
          nothing else runs there between a harvest and the launch.
        """
        s = self._stats.as_dict()
        steps = s["chunk_slot_steps"]
        return {
            "slot_occupancy": s["tokens_emitted"] / steps if steps
            else 0.0,
            "active_occupancy": s["active_slot_steps"] / steps if steps
            else 0.0,
            "prefill_overlap_frac": (s["prefills_overlapped"]
                                     / s["prefills"]) if s["prefills"]
            else 0.0,
            "tokens_per_s": (s["tokens_emitted"] / s["run_seconds"])
            if s["run_seconds"] else 0.0,
            "ttft_ms_p50": self._h_ttft.percentile(50),
            "ttft_ms_p99": self._h_ttft.percentile(99),
            "itl_ms_p50": self._h_itl.percentile(50),
            "itl_ms_p99": self._h_itl.percentile(99),
            "compiled_programs": len(self._compiled),
            "chunks_dispatched": s["chunks"],
            "chunks_empty": s["chunks_empty"],
            "prefill_waves": s["prefill_waves"],
            "unified_steps": s["unified_steps"],
            "step_uploads": s["step_uploads"],
            "staged_slot_updates": s["staged_slot_updates"],
            "uploads_per_step": (s["step_uploads"] / s["unified_steps"])
            if s["unified_steps"] else 0.0,
            "tokens_emitted": s["tokens_emitted"],
            "prefills": s["prefills"],
            "requests_completed": s["requests_completed"],
            "obs_overhead_frac": (self._obs_s / s["run_seconds"])
            if s["run_seconds"] else 0.0,
            "prefill_tokens": s["prefill_tokens"],
            "prefill_positions": s["prefill_positions"],
            "prefill_fill": (s["prefill_tokens"]
                             / s["prefill_positions"])
            if s["prefill_positions"] else 0.0,
            # reliability surface (ISSUE 10): overload economics
            "preempt_evictions": s["preempt_evictions"],
            "preempt_recompute_tokens": s["preempt_recompute_tokens"],
            "requests_cancelled": s["requests_cancelled"],
            "deadline_expired": (s["deadline_ttft_expired"]
                                 + s["deadline_total_expired"]),
            "shed_rejections": s["shed_rejections"],
            "queue_depth": len(self.queue),
            "quarantined": s["quarantined"],
            "containments": s["containments"],
            # prefix-cache economics (ISSUE 12): the shared-prefix
            # capacity story — hit rate, prefill tokens skipped, COW
            # forks and LRU evictions, plus current residency
            "prefix_cache_hits": s["prefix_cache_hits"],
            "prefix_cache_misses": s["prefix_cache_misses"],
            "prefix_cache_hit_rate": (
                s["prefix_cache_hits"]
                / (s["prefix_cache_hits"] + s["prefix_cache_misses"]))
            if s["prefix_cache_hits"] + s["prefix_cache_misses"]
            else 0.0,
            "prefix_cache_tokens_saved": s["prefix_cache_tokens_saved"],
            "prefix_cache_evictions": s["prefix_cache_evictions"],
            "prefix_cache_cow_forks": s["prefix_cache_cow_forks"],
            "prefix_cache_pages": len(self._pc_nodes),
            # speculative decoding economics (ISSUE 18)
            "spec_steps": int(self._c_spec_steps.value),
            "spec_tokens_drafted": int(self._c_spec_drafted.value),
            "spec_tokens_accepted": int(self._c_spec_accepted.value),
            "spec_tokens_rejected": int(self._c_spec_rejected.value),
            "spec_accept_rate": (
                self._c_spec_accepted.value
                / self._c_spec_drafted.value)
            if self._c_spec_drafted.value else 0.0,
            # quantized-KV pool geometry (ISSUE 20) — static per
            # engine, surfaced so capacity A/Bs read the byte budget
            # they actually ran at
            "kv_quant_bits": int(self._kv_quant_bits),
            "kv_quant_pool_bytes": int(self._kv_pool_bytes),
            "kv_quant_scale_pool_bytes": int(self._kv_scale_pool_bytes),
            # per-layer cache spec: paged K/V beside per-slot state,
            # and whatever the model counts per pass
            "kv_pool_bytes": int(self._kv_pool_bytes),
            "state_pool_bytes": int(self._state_pool_bytes),
            "window_pool_bytes": int(self._window_pool_bytes),
            **{n: int(c.value) for n, c in self._c_model.items()},
        }

    def reset_gauges(self):
        """Zero the gauge counters (e.g. after a warmup run whose lazy
        compiles would otherwise pollute tokens_per_s). The compiled-
        signature set is NOT cleared — compiled programs persist on the
        engine, so the compile-budget counter stays truthful."""
        for k in self._stats:
            self._stats[k] = 0.0 if k == "run_seconds" else 0
        for c in (self._c_spec_steps, self._c_spec_drafted,
                  self._c_spec_accepted, self._c_spec_rejected,
                  *self._c_model.values()):
            c.set(0)
        self._h_ttft.reset()
        self._h_itl.reset()
        self._obs_s = 0.0

    def _emit_gauges(self):
        _t_obs = time.perf_counter()
        s = self._stats.as_dict()
        self._g_overhead.set(
            (self._obs_s / s["run_seconds"]) if s["run_seconds"]
            else 0.0)
        self._g_pc_pages.set(len(self._pc_nodes))
        self._g_queue_depth.set(len(self.queue))
        self._g_kvq_bits.set(int(self._kv_quant_bits))
        self._g_kvq_pool_bytes.set(int(self._kv_pool_bytes))
        self._g_kvq_scale_bytes.set(int(self._kv_scale_pool_bytes))
        self._g_state_bytes.set(int(self._state_pool_bytes))
        self._g_window_bytes.set(int(self._window_pool_bytes))
        from ..profiler.trace import get_tracer
        tr = get_tracer()
        if tr.enabled:
            for name, val in self.gauges().items():
                tr.counter(f"serving/{name}",
                           round(val, 6) if isinstance(val, float)
                           else val)
        self._obs_s += time.perf_counter() - _t_obs

    # ---- admission / chunked batched prefill -----------------------------

    def _alloc_pages(self, n):
        if len(self._free_pages) < n and self._pc_nodes:
            # allocation pressure: reclaim unreferenced cache pages
            # (refcount-aware LRU) before declaring scarcity — a warm
            # cache must never deny admission the cold pool would
            # grant. The shortfall counts pages already deferred
            # behind the in-flight harvest (including this method's
            # own earlier evictions): they WILL arrive, so evicting
            # more cache for the same request would just destroy warm
            # entries a pipeline-depth wait is about to make moot.
            deferred = sum(len(p) for _, p in self._deferred_free)
            short = n - len(self._free_pages) - deferred
            if short > 0:
                self._pc_evict(short)
        if len(self._free_pages) < n:
            return None
        return [self._free_pages.popleft() for _ in range(n)]

    def _release_pages(self, pages, safe=False):
        """Return pages to the free pool. ``safe=True`` (the drain
        path) frees immediately — a drained slot is already inactive in
        every dispatched program, so its writes are trash-page-guarded.
        Pages from an EVICTED (still device-active) slot are deferred
        until every fetched program dispatched so far has been
        harvested (see ``_deferred_free``)."""
        if not pages:
            return
        if safe or self._last_harvest_seq >= \
                self._last_fetch_dispatch_seq:
            self._free_pages.extend(pages)
        else:
            self._deferred_free.append(
                (self._last_fetch_dispatch_seq, list(pages)))

    def _release_deferred(self):
        """Move deferred pages whose gating program has been harvested
        back into the free pool (called from every harvest)."""
        if not self._deferred_free:
            return
        keep = []
        for gate, pages in self._deferred_free:
            if gate <= self._last_harvest_seq:
                self._free_pages.extend(pages)
            else:
                keep.append((gate, pages))
        self._deferred_free = keep

    def _audit_pages(self, where):
        """PADDLE_TPU_SERVING_AUDIT invariant, extended to shared
        pages (ISSUE 12): every page lives in exactly one place — the
        free list, an occupied slot's PRIVATE list, the prefix-cache
        index (refcount-unique: one physical page per node, however
        many slots read it), the deferred-reclamation set, or the
        reserved trash page 0 — and every cache node's refcount equals
        its live slot attachments (>= 1 for every referenced page, 0
        exactly for evictable residents; free-list pages have no node
        at all)."""
        if not self._audit:
            return
        held = [p for pages in self.slot_pages for p in pages]
        cached = list(self._pc_nodes)
        deferred = [p for _, pages in self._deferred_free
                    for p in pages]
        allp = list(self._free_pages) + held + cached + deferred
        if len(allp) + 1 != self.num_pages \
                or len(set(allp)) != len(allp) or 0 in allp:
            raise AssertionError(
                f"serving page accounting broken at {where}: "
                f"free={len(self._free_pages)} held={len(held)} "
                f"cached={len(cached)} deferred={len(deferred)} "
                f"(+1 trash) != {self.num_pages} pages, "
                f"dupes={len(allp) - len(set(allp))}, "
                f"trash_leaked={0 in allp}")
        refs: dict[int, int] = {}
        for nodes in self.slot_shared:
            for node in nodes:
                refs[node.page] = refs.get(node.page, 0) + 1
        # a migrated-out request's exported prefix stays pinned until
        # the destination acks (ISSUE 17): each pin is a live
        # attachment exactly like a reading slot
        for nodes in self._exported_pins.values():
            for node in nodes:
                refs[node.page] = refs.get(node.page, 0) + 1
        for node in self._pc_nodes.values():
            expect = refs.get(node.page, 0)
            if node.ref != expect or node.ref < 0:
                raise AssertionError(
                    f"prefix-cache refcount broken at {where}: page "
                    f"{node.page} ref={node.ref} but {expect} live "
                    f"attachment(s)")
            if node.parent is not self._pc_root \
                    and node.parent.ref < node.ref:
                raise AssertionError(
                    f"prefix-cache chain broken at {where}: page "
                    f"{node.page} ref={node.ref} exceeds parent page "
                    f"{node.parent.page} ref={node.parent.ref}")
        for page in refs:
            if page not in self._pc_nodes:
                raise AssertionError(
                    f"prefix-cache attachment to unindexed page "
                    f"{page} at {where}")
        # structural invariant: the pools are the cache spec's, in its
        # order, shapes and dtypes. Under quantized KV (ISSUE 20) every
        # attention layer carries [k, v, k_scales, v_scales] and the
        # scales pools index the SAME page axis as their data pools — a
        # page id is valid in all four or in none, so the single
        # accounting above covers the scales pools too iff the geometry
        # agrees
        if len(self.pools) != self._n_pools:
            raise AssertionError(
                f"pool count broken at {where}: {len(self.pools)} pools, "
                f"the cache spec gives {self._n_pools}")
        for i, p in enumerate(self.pools):
            if tuple(p._data.shape) != tuple(self._pool_shapes[i]) \
                    or p._data.dtype != self._pool_dtypes[i]:
                raise AssertionError(
                    f"pool geometry broken at {where}: pool {i} "
                    f"({self._pool_kinds[i]}) is {tuple(p._data.shape)} "
                    f"{p._data.dtype}, the cache spec gives "
                    f"{tuple(self._pool_shapes[i])} {self._pool_dtypes[i]}")

    # ---- prefix cache: radix index + COW sharing (ISSUE 12) --------------

    def _pc_match(self, eff):
        """Longest cached full-page prefix of the admission prompt:
        walk the radix index block by block (``page_size`` tokens per
        level). Returns the matched node chain, root excluded."""
        if not self._prefix_cache:
            return []
        nodes, cur, ps = [], self._pc_root, self.page_size
        for i in range(len(eff) // ps):
            child = cur.children.get(eff[i * ps:(i + 1) * ps].tobytes())
            if child is None:
                break
            nodes.append(child)
            cur = child
        return nodes

    def _pc_pin(self, nodes):
        """Incref a matched chain (attach / pin against eviction)."""
        self._pc_clock += 1
        for node in nodes:
            node.ref += 1
            node.stamp = self._pc_clock

    def _pc_unpin(self, nodes):
        self._pc_clock += 1
        for node in nodes:
            node.ref -= 1
            node.stamp = self._pc_clock

    def _pc_detach(self, slot):
        """Drop a slot's shared-page attachments (drain/evict): decref
        only — the pages stay resident in the index, evictable once
        unreferenced (that residency IS the cache)."""
        if self.slot_shared[slot]:
            self._pc_unpin(self.slot_shared[slot])
            self.slot_shared[slot] = []

    def _pc_insert(self, slot):
        """Publish a slot's full prompt pages into the radix index at
        prefill completion: ownership moves page-by-page from the
        slot's private list to new cache nodes (the slot stays
        attached as a reader, so the refcount starts at 1). A level
        another slot published first keeps this slot's duplicate page
        private (it dies at drain) — re-pointing a live block table
        mid-flight is never worth the race. Safe against the async
        dispatch: a later attacher's program consumes this program's
        output pools, so the writes are ordered by data dependency."""
        if not self._prefix_cache:
            return
        eff = self._slot_prompt[slot]
        ps = self.page_size
        shared = self.slot_shared[slot]
        cur = shared[-1] if shared else self._pc_root
        self._pc_clock += 1
        for lvl in range(len(shared), len(eff) // ps):
            if not self.slot_pages[slot]:
                break
            key = eff[lvl * ps:(lvl + 1) * ps].tobytes()
            if key in cur.children:
                break
            page = self.slot_pages[slot].pop(0)
            node = _PrefixCacheNode(key, page, cur)
            node.ref = 1
            node.stamp = self._pc_clock
            cur.children[key] = node
            self._pc_nodes[page] = node
            shared.append(node)
            cur = node

    def _pc_evictable(self):
        """Pages the LRU could reclaim right now (ref-0 nodes; the
        monotone refcount chain makes every one reachable leaf-first)."""
        return sum(1 for n in self._pc_nodes.values() if n.ref == 0)

    def _pc_evict(self, n_pages):
        """Reclaim up to ``n_pages`` from unreferenced cache entries,
        LRU-first among childless ref-0 nodes (leaves first — an
        interior node never outlives its children, keeping every
        root-contiguous chain matchable). Freed pages ride the same
        deferred-release discipline as any reclaimed page: an
        in-flight program dispatched while a since-drained reader was
        attached may still READ them, so they only re-enter the free
        list once every fetched program has been harvested."""
        import heapq
        freed = []
        # one snapshot + a heap instead of a rescan per victim: no
        # admission runs inside this call, so nodes only change state
        # through our own evictions — a parent joins the heap exactly
        # when its last child is freed
        heap = [(n.stamp, n.page) for n in self._pc_nodes.values()
                if n.ref == 0 and not n.children]
        heapq.heapify(heap)
        while heap and len(freed) < n_pages:
            _, page = heapq.heappop(heap)
            victim = self._pc_nodes.get(page)
            if victim is None or victim.ref or victim.children:
                continue
            del victim.parent.children[victim.key]
            del self._pc_nodes[page]
            freed.append(page)
            parent = victim.parent
            if parent is not self._pc_root and parent.ref == 0 \
                    and not parent.children:
                heapq.heappush(heap, (parent.stamp, parent.page))
        if freed:
            self._stats.inc("prefix_cache_evictions", len(freed))
            self._release_pages(freed)
        return len(freed)

    def _pc_cow(self, src, dst):
        """Copy-on-write fork: duplicate one physical page across
        every layer's k/v pool so ``dst`` becomes a private writable
        copy of the shared ``src``. Functional pool update — the copy
        chains after every dispatched program in the device stream,
        exactly like admission's table/ctx updates, so it reads the
        prefix owner's completed writes and is visible to every later
        program."""
        s, d = jnp.int32(src), jnp.int32(dst)
        self._set_paged(_pc_copy_page(self._paged_arrays(), s, d))
        self._stats.inc("prefix_cache_cow_forks")

    @property
    def prefix_cache_pages(self):
        """Physical pages currently owned by the prefix-cache index
        (referenced + evictable) — the tests' page-accounting term."""
        return len(self._pc_nodes)

    def reset_prefix_cache(self):
        """Drop every UNREFERENCED cache entry (the bench cold/warm
        A/B resets without rebuilding the engine and recompiling its
        programs). Referenced entries stay — their readers are live.
        Returns the number of pages reclaimed."""
        n = self._pc_evict(len(self._pc_nodes))
        self._audit_pages("reset_prefix_cache")
        return n

    def _admission_key(self, req):
        # higher priority first; FIFO (arrival time, then id) within a
        # priority class — preempted requests keep their original
        # arrival slot, so recompute does not lose their queue position
        return (-req.priority, req.t_arrive, req.request_id)

    def _next_candidate(self):
        if not self.queue:
            return None
        if not self._has_priorities:
            return self.queue[0]       # the historical FIFO contract
        return min(self.queue, key=self._admission_key)

    def _already_complete(self, req):
        """A replayed request that already holds its full stream (it
        died between harvest and drain, or a wedged slot never drained
        it) — complete it instead of re-admitting."""
        if not req.tokens:
            return False
        eos = req.eos_token_id
        return (eos is not None and req.tokens[-1] == eos) \
            or len(req.tokens) >= req.max_new_tokens

    def _complete_ok(self, req):
        """Normal completion bookkeeping shared by the drain pass and
        the already-complete replay path."""
        req.finished = True
        req.t_done = time.perf_counter()
        eos = req.eos_token_id
        req.finish_reason = "eos" if (
            eos is not None and req.tokens
            and req.tokens[-1] == eos) else "length"
        req.strikes = 0        # innocence proven by completion
        self._record_latency(req)
        self.completed.append(req)
        _t_obs = time.perf_counter()
        self._stats.inc("requests_completed")
        _frec.record_event("finish", req=req.request_id,
                           reason=req.finish_reason,
                           tokens=len(req.tokens))
        self._obs_s += time.perf_counter() - _t_obs

    def _finish_error(self, req, err):
        """Complete a request EXCEPTIONALLY: typed error attached,
        tokens already emitted kept, latency booked when a first token
        existed."""
        req.finished = True
        req.error = err
        req.t_done = time.perf_counter()
        # completion instrumentation rides the obs_overhead_frac
        # window, exactly like _complete_ok (_record_latency books its
        # own slice internally)
        _t_obs = time.perf_counter()
        if isinstance(err, RequestCancelled):
            req.finish_reason = "cancelled"
            self._stats.inc("requests_cancelled")
        elif isinstance(err, DeadlineExceeded):
            req.finish_reason = "deadline"
            self._stats.inc("deadline_ttft_expired"
                            if err.kind == "ttft"
                            else "deadline_total_expired")
        else:
            req.finish_reason = "quarantined"
            self._stats.inc("quarantined")
        _frec.record_event("finish_error", req=req.request_id,
                           reason=req.finish_reason,
                           tokens=len(req.tokens))
        self._obs_s += time.perf_counter() - _t_obs
        self._record_latency(req)
        self.completed.append(req)
        return req

    def _clear_slot(self, slot, device=False):
        """The ONE per-slot teardown (drain and eviction share it —
        a field missed in a second copy is exactly the stale-state bug
        class the identity checks exist to catch). ``device=True``
        additionally stages the deactivation of the slot's chained
        DEVICE state (:meth:`_stage_reset`): needed on eviction, where
        the device still believes the slot is active; a drained slot
        already went inactive inside its program.
        Per-slot recurrent state (``cache_spec.SlotState``) is not
        touched: the model zeroes a slot's row in-program when its next
        occupant starts at position 0."""
        self._pc_detach(slot)        # shared pages: decref, stay cached
        self.slot_pages[slot] = []
        self.slot_req[slot] = None
        self._slot_prompt[slot] = None
        self.tables[slot] = 0
        self.ctx[slot] = 0
        self._pred_ctx[slot] = 0
        self.limits[slot] = 0
        self.slot_eos[slot] = -1
        self._prefill_off[slot] = 0
        self._act_target[slot] = False
        if device:
            self.active[slot] = False
            self._prefilling[slot] = False
            self._emits_inflight[slot] = 0
            self._stage_reset(slot, 0)

    def _evict_slot(self, slot, requeue, reason="preempt", error=None):
        """Tear one occupied slot out of the engine mid-flight:
        deactivate it on the host now and on the device with the next
        launch (:meth:`_stage_reset`; an in-flight program's stale
        view of the slot is discarded at harvest via the slot_req
        identity check), reclaim its pages (deferred past any fetched
        program that could still write them), and either requeue the
        request for recompute-style re-prefill or complete it with a
        typed error."""
        req = self.slot_req[slot]
        if requeue:
            self._stats.inc("preempt_evictions")
            self._stats.inc("preempt_pages_reclaimed",
                            len(self.slot_pages[slot]))
        self._release_pages(self.slot_pages[slot])
        self._clear_slot(slot, device=True)
        _frec.record_event("preempt", slot=slot, req=req.request_id,
                           tokens=len(req.tokens), reason=reason)
        record_hop(req, "preempt" if requeue else "evict",
                   replica=getattr(self, "_fleet_replica_id", None),
                   reason=reason, tokens=len(req.tokens))
        if requeue:
            req.preemptions += 1
            self.queue.appendleft(req)
        elif error is not None:
            self._finish_error(req, error)
        return req

    def _preempt_for(self, req, need, need_slot=False):
        """vLLM-style recompute preemption: evict strictly-LOWER-
        priority occupants — lowest priority, youngest (latest admit)
        first — until ``req`` has a slot (when ``need_slot``) and
        ``need`` pages are available or provably arriving (deferred
        behind the in-flight harvest). Equal-priority traffic never
        preempts: pure overload queues, it does not thrash."""
        victims = [s for s in range(self.num_slots)
                   if self.slot_req[s] is not None
                   and self.slot_req[s].priority < req.priority]
        victims.sort(key=lambda s: (self.slot_req[s].priority,
                                    -self.slot_req[s].t_admit))
        projected = len(self._free_pages) + sum(
            len(p) for _, p in self._deferred_free) \
            + self._pc_evictable()
        # feasibility first: if evicting EVERY victim still cannot
        # reach ``need``, evict none — destroying in-flight progress
        # with no admission to show for it is pure waste
        if projected + sum(len(self.slot_pages[s])
                           for s in victims) < need:
            return False
        evicted = 0
        for s in victims:
            if projected >= need and (evicted or not need_slot):
                break
            projected += len(self.slot_pages[s])
            self._evict_slot(s, requeue=True, reason="preempt")
            evicted += 1
        if need_slot and not evicted:
            return False
        return projected >= need

    def _lifecycle_error(self, req, now):
        if req.cancelled:
            return RequestCancelled(req.request_id)
        if req.deadline_s is not None \
                and now - req.t_arrive > req.deadline_s:
            return DeadlineExceeded(req.request_id, "total",
                                    req.deadline_s)
        if req.ttft_deadline_s is not None and not req.t_first \
                and now - req.t_arrive > req.ttft_deadline_s:
            return DeadlineExceeded(req.request_id, "ttft",
                                    req.ttft_deadline_s)
        return None

    def _reap(self):
        """The lifecycle control point, once per scheduler turn:
        cancelled or deadline-expired requests are shed from the queue,
        running ones are evicted (pages reclaimed mid-prefill or
        mid-decode) — each completes with its typed error instead of
        silently occupying a slot."""
        done = []
        now = time.perf_counter()
        # O(queue) sweep gated on lifecycle control being in play; the
        # periodic sweep bounds how long a direct handle-cancel() of a
        # queued request can go unobserved. Running slots (few) are
        # always swept below.
        self._reap_turn += 1
        if self.queue and (self._lifecycle_seen
                           or self._reap_turn % 32 == 0):
            drop = [(req, err) for req in self.queue
                    if (err := self._lifecycle_error(req, now))
                    is not None]
            if drop:
                self._lifecycle_seen = True
            for req, err in drop:
                self.queue.remove(req)
                done.append(self._finish_error(req, err))
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None or req.finished:
                continue
            err = self._lifecycle_error(req, now)
            if err is not None:
                self._evict_slot(slot, requeue=False,
                                 reason=type(err).__name__,
                                 error=err)
                done.append(req)
        return done

    def _admit(self):
        """One admission pass (:meth:`_admit_queued`) under the
        ``serving/admit`` span."""
        with _span("serving/admit") as sp:
            n0 = self._stats["prefills"]
            self._admit_queued()
            sp.set_args(admitted=self._stats["prefills"] - n0,
                        queued=len(self.queue))

    def _admit_queued(self):
        """Move queued requests into free slots: allocate pages, stage
        per-slot state, and mark the slot PREFILLING — the prompt itself
        streams through the step program's prompt groups
        (:meth:`_stage_prompt_chunks`). Admission order is
        priority-then-FIFO; when no slot or not enough pages are free,
        a strictly-higher-priority candidate preempts running
        lower-priority sequences (:meth:`_preempt_for`). Requests
        implicated by a step failure (``strikes > 0``) re-enter SOLO so
        the next fault implicates exactly one request."""
        while self.queue:
            req = self._next_candidate()
            if self._already_complete(req):
                # replayed request whose stream was already complete
                self.queue.remove(req)
                self._complete_ok(req)
                self._done_pending.append(req)
                continue
            if any(r is not None and r.strikes for r in self.slot_req):
                return         # a suspect runs alone, nothing joins it
            occupied = any(r is not None for r in self.slot_req)
            if req.strikes and occupied:
                return         # suspects wait for an empty engine
            gen = len(req.tokens)
            remaining = req.max_new_tokens - gen
            eff_len = req.prompt.size + gen
            need_total = -(-(eff_len + remaining) // self.page_size)
            slot = next((s for s in range(self.num_slots)
                         if self.slot_req[s] is None
                         and not self.active[s]), None)
            if slot is None and not self._has_priorities:
                return   # no slot and nobody to preempt: skip the
                         # O(prompt) replay-concat + radix-match work
                         # this turn would throw away
            if gen:
                # recompute re-admission: prompt + generated tokens
                # stream back through prefill (token-identical replay)
                eff = np.concatenate(
                    [req.prompt,
                     np.asarray(req.tokens, np.int32)])
            else:
                eff = req.prompt
            # cached-prefix fast path (ISSUE 12): match BEFORE the
            # page-need computation — shared pages are attached, not
            # allocated, so a warm cache admits deeper than the cold
            # pool would. The match is PINNED (incref) before any
            # allocation so the LRU cannot reclaim it mid-admission.
            shared = self._pc_match(eff)
            # copy-on-write case: the WHOLE admission prompt is
            # cached, but at least the last token must re-prefill to
            # produce logits — its write lands inside the last shared
            # page, so that page is forked to a private copy
            cow = bool(shared) \
                and len(shared) * self.page_size >= len(eff)
            start = len(eff) - 1 if cow \
                else len(shared) * self.page_size
            need = need_total - len(shared) + (1 if cow else 0)
            self._pc_pin(shared)
            if slot is None:
                if not self._preempt_for(req, need, need_slot=True):
                    self._pc_unpin(shared)
                    return
                slot = next((s for s in range(self.num_slots)
                             if self.slot_req[s] is None
                             and not self.active[s]), None)
                if slot is None:
                    self._pc_unpin(shared)
                    return
            pages = self._alloc_pages(need)
            if pages is None and self._has_priorities \
                    and self._preempt_for(req, need):
                pages = self._alloc_pages(need)
            if pages is None:
                self._pc_unpin(shared)
                return   # reclaimed pages still deferred behind the
                         # in-flight harvest (or pure overload): the
                         # candidate stays queued, admit next turn
            attach = shared
            if cow:
                fork = shared[-1]
                self._pc_cow(fork.page, pages[0])
                self._pc_unpin([fork])
                attach = shared[:-1]
            if self._prefix_cache:
                self._stats.inc("prefix_cache_hits" if start
                                else "prefix_cache_misses")
                if start:
                    self._stats.inc("prefix_cache_tokens_saved", start)
            self.queue.remove(req)
            if gen:
                self._stats.inc("preempt_recompute_tokens", gen)
            self._stage_slot(slot, req, pages, eff, remaining,
                             attach=attach, start=start)
        return

    def _stage_slot(self, slot, req, pages, eff, remaining,
                    attach=(), start=0):
        """Bind an admitted request to a slot, on the host alone:
        block-table row, limit, stop token, prefill progress, and the
        staged reset of the slot's device context to ``start`` — the
        next dispatch's upload carries them. ``eff`` is the admission
        prompt (original prompt + recompute replay tokens), ``remaining``
        the generation budget left. ``attach`` is the cached-prefix node
        chain (already pinned) whose pages head the block table;
        ``start`` is the cached prefix length in tokens — prefill
        resumes there, indistinguishable from a slot that already
        streamed ``start`` tokens (chunked prefill always supported
        arbitrary offsets; sharing only redirects the table)."""
        tl = len(eff)
        self.slot_pages[slot] = pages
        self.slot_shared[slot] = list(attach)
        self._slot_prompt[slot] = eff
        row = np.zeros((self.pages_per_slot,), np.int32)
        row[:len(attach)] = [n.page for n in attach]
        row[len(attach):len(attach) + len(pages)] = pages
        self.tables[slot] = row
        req.t_admit = time.perf_counter()
        _t_obs = req.t_admit
        if self._trace_every:
            req.traced = req.request_id % self._trace_every == 0
        record_hop(req, "admit",
                   replica=getattr(self, "_fleet_replica_id", None),
                   slot=slot, cached=int(start),
                   replayed=len(req.tokens))
        self._stats.inc("prefills")
        if self._overlap_admission:
            self._stats.inc("prefills_overlapped")
        from ..profiler.trace import get_tracer
        _tr = get_tracer()
        if _tr.enabled:
            _tr.instant("serving/prefill", slot=slot, prompt_len=tl,
                        chunk=self.prefill_chunk,
                        overlapped=self._overlap_admission)
        _frec.record_event("admit", slot=slot,
                           req=req.request_id, prompt_len=tl,
                           cached=int(start), queued=len(self.queue))
        self._obs_s += time.perf_counter() - _t_obs
        self.slot_req[slot] = req
        self._prefilling[slot] = True
        self._prefill_off[slot] = start
        self._emits_inflight[slot] = 0
        # a prefill-role engine never activates decode: the slot
        # finishes its prompt, samples the first token in-program, and
        # goes inactive — the drain pass exports it for migration. A
        # no_migrate request (the fleet found no decode capacity)
        # decodes here like any colocated stream
        self._act_target[slot] = remaining > 1 \
            and (self.role != "prefill"
                 or getattr(req, "no_migrate", False))
        self.ctx[slot] = start
        self._pred_ctx[slot] = start
        self._stage_reset(slot, start)
        self.slot_eos[slot] = -1 if req.eos_token_id is None \
            else int(req.eos_token_id)
        # ctx counts CACHE entries; one generated token is always
        # pending outside the cache, so the n-th token lands when
        # ctx hits tl + n - 1 (not tl + n)
        self.limits[slot] = tl + remaining - 1

    # ---- completion ------------------------------------------------------

    def _record_latency(self, req):
        """Book a finished request's latency into the bounded
        reservoirs and, for sampled requests, reconstruct its
        lifecycle spans into the chrome trace (queued → admitted →
        prefill → first-token → decode → finished) from the stamps
        taken on the hot path. Counted in the ``obs_overhead_frac``
        self-measurement window (the observes and the trace
        reconstruction ARE instrumentation cost)."""
        _t_obs = time.perf_counter()
        if req.t_first:
            self._h_ttft.observe((req.t_first - req.t_arrive) * 1e3)
            if len(req.tokens) > 1:
                self._h_itl.observe(
                    (req.t_done - req.t_first) * 1e3
                    / (len(req.tokens) - 1))
        record_hop(req, "finish",
                   replica=getattr(self, "_fleet_replica_id", None),
                   reason=req.finish_reason, tokens=len(req.tokens))
        if req.trace_id is None and req.request_id >= 0:
            # standalone engine use: THIS is the end of the request's
            # timeline, so feed the process trace log here. A
            # fleet-managed request (trace_id minted by the router) is
            # fed by the fleet at DELIVERY instead — a replica
            # completion may only be the losing hedge copy. Negative
            # ids are sacrificial warmup requests (fleet._warm): they
            # deliberately absorb the XLA compile, and their
            # multi-second "latency" would otherwise dominate the
            # /statusz slowest-traces render
            _get_trace_log().record(request_trace_summary(req))
        if req.traced:
            self._emit_request_trace(req)
        self._obs_s += time.perf_counter() - _t_obs

    def _emit_request_trace(self, req):
        from ..profiler.trace import get_tracer
        tr = get_tracer()
        if not tr.enabled:
            return
        rid = int(req.request_id)
        # each traced request gets its own track (tid) so Perfetto
        # shows the lifecycle as one stacked lane per request; a
        # fleet-minted trace id (ISSUE 13) keeps every attempt —
        # preemption replays, failover re-admissions, hedge copies —
        # on ONE track, reconstructing the cross-replica timeline
        tid = int(req.trace_id) if req.trace_id is not None else rid
        admit = req.t_admit or req.t_arrive
        tr.complete("req/queued", req.t_arrive, admit,
                    cat="serving_req", tid=tid, request_id=rid)
        pre_end = req.t_prefill_done or req.t_first or admit
        tr.complete("req/prefill", admit, pre_end, cat="serving_req",
                    tid=tid, prompt_len=int(len(req.prompt)))
        if req.t_first:
            tr.complete("req/first_token_wait", pre_end, req.t_first,
                        cat="serving_req", tid=tid)
            tr.complete("req/decode", req.t_first, req.t_done,
                        cat="serving_req", tid=tid,
                        tokens=len(req.tokens))
        if req.trace_id is None:
            # hop markers: zero-length retroactive spans AT the hop
            # timestamps, so the timeline places preemptions where
            # they happened. Fleet-owned traces (trace_id set) get
            # their hop markers from the fleet's delivery-time
            # reconstruction instead — emitting here too would
            # duplicate every marker on the same track
            for h in req.hops or ():
                tr.complete("req/hop", h["t"], h["t"],
                            cat="serving_req", tid=tid,
                            **{**h, "request_id": rid})
        tr.instant("req/finished", cat="serving_req",
                   request_id=rid, reason=req.finish_reason,
                   tokens=len(req.tokens))

    def _drain(self):
        """Finished slots out (:meth:`_drain_slots`) under the
        ``serving/drain`` span."""
        with _span("serving/drain") as sp:
            done = self._drain_slots()
            sp.set_args(finished=len(done))
        return done

    def _drain_slots(self):
        # lifecycle first: cancellations and deadline expiries free
        # their pages and complete with typed errors at this turn
        done = self._reap()
        if self._done_pending:
            done.extend(self._done_pending)
            self._done_pending = []
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            if self._prefilling[slot]:
                # prompt still streaming through the step's prompt
                # groups — the slot is inactive but very much occupied
                continue
            if self._emits_inflight[slot]:
                # tokens for this slot ride a dispatched-but-
                # unharvested program: finishing now would lose them
                # (defer one loop)
                continue
            if not self.active[slot]:
                if self._should_migrate(slot, req):
                    self._migrate_out(slot, req)
                    continue
                finished_now = not req.finished
                # drained slots are inactive in every dispatched
                # program (writes trash-page-guarded), so their pages
                # are immediately reusable
                self._release_pages(self.slot_pages[slot], safe=True)
                self._clear_slot(slot)
                if finished_now:
                    self._complete_ok(req)
                done.append(req)
        self._audit_pages("drain")
        return done


def _apply_multi(fn, tensors, n_out):
    """apply() with a tuple return of n_out arrays."""
    from ..framework.core import apply
    return apply(fn, *tensors, n_outputs=n_out, differentiable=False,
                 name="serving_engine")


def _keep_pools(pools, res):
    """A step program's pools are its persistable state: write the
    step's new pools (the outputs after the first five) back into the
    engine's pool Tensors, so ``to_static`` records them as written and
    donates their buffers to the compiled step. Returns the first five
    outputs (packed, tok, ctx, act, key)."""
    for p, r in zip(pools, res[5:]):
        p.set_data(r._data)
    return res[:5]


# -- tunable surface ---------------------------------------------------------
# The engine's chunk ladder is a tunable surface like the kernel tiles,
# but its trial needs a whole engine + workload, so there is no
# standalone builder: `bench.py --autotune`'s cb section is the sweep
# vehicle (it times candidate ladders on the real workload and commits
# the winner); a recorded winner then serves every ctor call that
# leaves the knobs as None. Candidate values are powers of two.

def _register_serving_surface():
    from ..tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        slots = int(shape.get("slots", 4))
        max_len = int(shape.get("max_len", 512))
        out = []
        for dc in (8, 16, 32, 64):
            if dc > max_len:
                continue
            for pc in (32, 64, 128, 256):
                if pc > max_len:
                    continue
                for ab in sorted({1, max(slots // 2, 1), slots}):
                    out.append({"decode_chunk": dc, "prefill_chunk": pc,
                                "admit_batch": ab})
        return out

    def _is_valid(config, shape):
        slots = int(shape.get("slots", 4))
        max_len = int(shape.get("max_len", 512))
        return (1 <= config["decode_chunk"] <= max_len
                and 1 <= config["prefill_chunk"] <= max_len
                and 1 <= config["admit_batch"] <= slots)

    register_surface(TunableSurface(
        name="serving_chunks",
        params=("decode_chunk", "prefill_chunk", "admit_batch"),
        default={"decode_chunk": 16, "prefill_chunk": 128,
                 "admit_batch": 4},
        candidates=_candidates,
        is_valid=_is_valid,
        describe="ContinuousBatchingEngine ladder: decode chunk length, "
                 "batched-prefill chunk, prompts admitted per step. "
                 "Shape key: slots/max_len/page."))


def _register_spec_surface():
    from ..tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        max_len = int(shape.get("max_len", 512))
        out = []
        for k in (2, 4, 6, 8):
            if k + 1 > max_len:
                continue
            for src in ("ngram", "self"):
                out.append({"k": k, "source": src})
        return out

    def _is_valid(config, shape):
        max_len = int(shape.get("max_len", 512))
        return (1 <= int(config["k"]) < max_len
                and config["source"] in ("ngram", "self"))

    register_surface(TunableSurface(
        name="spec_decode",
        params=("k", "source"),
        default={"k": 4, "source": "ngram"},
        candidates=_candidates,
        is_valid=_is_valid,
        describe="Speculative decoding: draft tokens per decode slot "
                 "(K, verified as a length-K+1 ragged chunk) x draft "
                 "source ('ngram' prompt-lookup / 'self' skip-layer). "
                 "Shape key: slots/max_len/page — the cb geometry; "
                 "bench.py --autotune's cb-spec section is the sweep "
                 "vehicle."))


_register_serving_surface()
_register_spec_surface()
