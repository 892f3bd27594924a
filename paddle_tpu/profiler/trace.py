"""Structured trace layer — nestable spans, gauges, chrome-trace export.

The round-5 verdict's blocking finding was *evidence*: MoE step time was
60% unattributed, serving ran at 74% of its occupancy ceiling with no
gauge saying so, and perf cliffs (scan declines, dropless downgrades)
were silent. This module is the measurement substrate every perf PR
cites: host-side spans with wall time + optional device-sync points +
FLOPs/bytes annotations, counter gauges, and export to both the chrome
trace-event schema (load in Perfetto / chrome://tracing) and raw JSON.

Deliberately stdlib-only at import time (no jax): it is imported from
hot paths (``nn/scan.py``, ``inference/serving.py``, ``hapi``) and must
never add import weight or create cycles. jax is imported lazily, when
the first span opens (:func:`_annotation`) or a span requests a device
sync (:func:`block_on`).

Design notes:

- ONE span primitive, two sinks. :func:`trace_span` always opens a
  ``jax.profiler.TraceAnnotation``: whenever a jax profiler session is
  live the span lands in the ``.xplane.pb`` host plane, on the clock the
  device trace uses, so a device gap can be laid over the program's own
  layers; with no session it is a sub-microsecond no-op, so
  instrumentation stays in production code paths. Only while the
  structured :class:`Tracer` is enabled does the same span ALSO record
  the chrome event (host clock, this module's export). "Tracing on"
  means "a profiler session is running" — there is no flag of its own.
- Spans are exception-safe: the event is recorded (with an ``error``
  arg) even when the body raises, so a trace of a crashed step still
  shows where the time went.
- Exports are ATOMIC (tmp file + ``os.replace``): a crash or ENOSPC
  mid-export can never leave a torn, half-JSON trace file (same
  invariant as the checkpoint layer, docs/checkpoint_fault_tolerance.md).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field

__all__ = ["TraceEvent", "Tracer", "get_tracer", "trace_span",
           "block_on", "log_perf_event", "perf_logger", "epoch_summary",
           "RequestTraceLog", "get_trace_log"]

perf_logger = logging.getLogger("paddle_tpu.perf")

_US = 1e6


@dataclass
class TraceEvent:
    """One trace record in chrome trace-event vocabulary: ``ph="X"`` is
    a complete span (ts + dur), ``"C"`` a counter sample (gauges),
    ``"i"`` an instant marker (e.g. a device-sync point)."""

    name: str
    ph: str = "X"
    cat: str = "user"
    ts: float = 0.0          # microseconds since tracer epoch
    dur: float = 0.0         # microseconds (X events)
    tid: int = 0
    depth: int = 0
    args: dict = field(default_factory=dict)

    def to_chrome(self, pid: int) -> dict:
        ev = {"name": self.name, "ph": self.ph, "cat": self.cat,
              "ts": self.ts, "pid": pid, "tid": self.tid}
        if self.ph == "X":
            ev["dur"] = self.dur
        if self.ph == "i":
            ev["s"] = "t"            # thread-scoped instant
        if self.args:
            ev["args"] = self.args
        return ev


def block_on(value):
    """Device-sync point: block until ``value`` (Tensor / jax array /
    pytree / callable returning one) is computed. Returns the seconds
    spent blocked."""
    t0 = time.perf_counter()
    if callable(value):
        value = value()
    import jax
    leaves = []

    def _collect(v):
        if v is None:
            return
        if isinstance(v, (list, tuple)):
            for x in v:
                _collect(x)
            return
        data = getattr(v, "_data", v)       # Tensor -> jax array
        leaves.append(data)

    _collect(value)
    if leaves:
        jax.block_until_ready(leaves)
    return time.perf_counter() - t0


_TraceAnnotation = None


def _annotation(name, args):
    """A ``jax.profiler.TraceAnnotation`` (jax imported on first use: this
    module stays importable without it)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **args)


class _Span:
    """The one span primitive: a ``TraceAnnotation`` on the profiler
    session's clock, always (a no-op with no session live). Parent by
    nesting; ``set_args`` maps to ``TraceMe.set_metadata``."""

    __slots__ = ("_ann",)

    def __init__(self, name, args):
        self._ann = _annotation(name, args)

    def set_args(self, **kw):
        """Attach metadata mid-span (e.g. flops discovered after shapes
        are known, a count known only once the body has run)."""
        self._ann.set_metadata(**kw)
        return self

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ann.__exit__(exc_type, exc, tb)
        return False                         # never swallow exceptions


class _RecordedSpan(_Span):
    """The same span while the structured tracer is enabled: it also
    records one chrome X event. Exception-safe: records even when the
    body raises (annotating ``args['error']``)."""

    __slots__ = ("_tracer", "name", "cat", "args", "sync", "_t0", "_depth")

    def __init__(self, tracer, name, cat, sync, args):
        super().__init__(name, args)
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.sync = sync
        self.args = args

    def set_args(self, **kw):
        self.args.update(kw)
        return super().set_args(**kw)

    def __enter__(self):
        super().__enter__()
        tl = self._tracer._tl
        self._depth = getattr(tl, "depth", 0)
        tl.depth = self._depth + 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        try:
            if self.sync is not None and exc_type is None:
                sync_s = block_on(self.sync)
                self.args.setdefault("sync_s", round(sync_s, 6))
            t1 = time.perf_counter()
            if exc_type is not None:
                self.args["error"] = f"{exc_type.__name__}: {exc}"
            self._tracer._record(TraceEvent(
                name=self.name, ph="X", cat=self.cat,
                ts=(self._t0 - self._tracer._epoch) * _US,
                dur=(t1 - self._t0) * _US,
                tid=threading.get_ident() & 0xFFFF, depth=self._depth,
                args=self.args))
        finally:
            self._tracer._tl.depth = self._depth
            super().__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Process-wide structured trace recorder (see module docstring)."""

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self.events: list[TraceEvent] = []
        self.options = None                 # ProfilerOptions when enabled
        self._lock = threading.Lock()
        self._tl = threading.local()
        self._epoch = time.perf_counter()

    # -- recording --------------------------------------------------------

    def _record(self, ev: TraceEvent):
        with self._lock:
            self.events.append(ev)

    def span(self, name, cat="user", sync=None, **args):
        """Nestable timed span: always a ``TraceAnnotation`` (see the
        module docstring), and a chrome event too while this tracer is
        enabled. ``sync`` (Tensor/array/pytree/callable) makes a
        RECORDED span block on the device before it closes, so its
        duration covers device work, not just dispatch. Extra kwargs
        become the span's args in both sinks (``flops=``/``bytes=`` feed
        the per-section MFU/roofline summary, profiler.cost)."""
        if not self.enabled:
            return _Span(name, args)
        return _RecordedSpan(self, name, cat, sync, args)

    def counter(self, name, value=None, cat="gauge", **values):
        """Record a gauge sample (chrome counter event)."""
        if not self.enabled:
            return
        args = dict(values)
        if value is not None:
            args.setdefault("value", value)
        self._record(TraceEvent(
            name=name, ph="C", cat=cat,
            ts=(time.perf_counter() - self._epoch) * _US,
            tid=threading.get_ident() & 0xFFFF, args=args))

    def instant(self, name, cat="marker", **args):
        if not self.enabled:
            return
        self._record(TraceEvent(
            name=name, ph="i", cat=cat,
            ts=(time.perf_counter() - self._epoch) * _US,
            tid=threading.get_ident() & 0xFFFF, args=dict(args)))

    def complete(self, name, t0, t1, cat="user", tid=None, **args):
        """Record a span RETROACTIVELY from ``perf_counter``
        timestamps: the serving request-lifecycle tracer reconstructs
        a request's queued/prefill/decode spans at completion time
        from stamps taken on the hot path (one float store each), so
        tracing a request costs nothing until it finishes. ``tid``
        gives the span its own track (e.g. the request id)."""
        if not self.enabled:
            return
        self._record(TraceEvent(
            name=name, ph="X", cat=cat,
            ts=(t0 - self._epoch) * _US,
            dur=max(0.0, t1 - t0) * _US,
            tid=(threading.get_ident() & 0xFFFF) if tid is None
            else int(tid),
            args=dict(args)))

    def device_sync(self, value, name="device_sync"):
        """Explicit sync point: blocks on ``value`` and records how long
        the host waited (the device-queue depth at this moment)."""
        if not self.enabled:
            return block_on(value)
        t0 = time.perf_counter()
        waited = block_on(value)
        self._record(TraceEvent(
            name=name, ph="X", cat="sync",
            ts=(t0 - self._epoch) * _US, dur=waited * _US,
            tid=threading.get_ident() & 0xFFFF,
            args={"waited_s": round(waited, 6)}))
        return waited

    def clear(self):
        with self._lock:
            self.events = []

    # -- summaries --------------------------------------------------------

    def section_summary(self, peak_flops=None, peaks=None):
        """Aggregate X events by name: count, total/mean ms, and — for
        spans annotated with ``flops``/``bytes`` — achieved FLOP/s, MFU
        against ``peak_flops`` and the roofline classification against
        ``peaks`` (default: the device's table entry; on a device the
        table does not know, no roofline is reported)."""
        from .cost import known_peaks, roofline
        peaks = peaks or known_peaks()
        if peak_flops is None and peaks is not None:
            peak_flops = peaks.flops
        agg: dict[str, dict] = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            if ev.ph != "X":
                continue
            a = agg.setdefault(ev.name, {
                "count": 0, "total_ms": 0.0, "flops": 0.0, "bytes": 0.0})
            a["count"] += 1
            a["total_ms"] += ev.dur / 1e3
            a["flops"] += float(ev.args.get("flops", 0.0))
            a["bytes"] += float(ev.args.get("bytes", 0.0))
        for name, a in agg.items():
            a["mean_ms"] = a["total_ms"] / max(a["count"], 1)
            if a["flops"] and a["total_ms"]:
                a["flops_per_s"] = a["flops"] / (a["total_ms"] / 1e3)
                if peak_flops:
                    a["mfu"] = a["flops_per_s"] / peak_flops
            if a["flops"] and a["bytes"] and peaks is not None:
                a["roofline"] = roofline(a["flops"], a["bytes"], peaks)
        return agg

    # -- export -----------------------------------------------------------

    def to_chrome_dict(self) -> dict:
        pid = os.getpid()
        with self._lock:
            events = list(self.events)
        return {"traceEvents": [ev.to_chrome(pid) for ev in events],
                "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path) -> str:
        """Write the chrome trace-event JSON atomically; returns path."""
        return _atomic_json_dump(self.to_chrome_dict(), path)

    def export_json(self, path) -> str:
        """Raw structured export (events + section summary), atomic."""
        with self._lock:
            events = [{"name": e.name, "ph": e.ph, "cat": e.cat,
                       "ts_us": e.ts, "dur_us": e.dur, "depth": e.depth,
                       "args": e.args} for e in self.events]
        return _atomic_json_dump(
            {"events": events, "sections": self.section_summary()}, path)


def _atomic_write(path, write_fn) -> str:
    """tmp + fsync + os.replace: the export either fully exists or not
    at all (fault-injection-tested; a torn half-written export is worse
    than none). ``write_fn(f)`` serializes onto the open tmp file —
    the one atomic-write skeleton every profiler export (chrome trace,
    metrics JSON, Prometheus text, flight bundles) shares."""
    path = os.fspath(path)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return path


def _atomic_json_dump(obj, path) -> str:
    return _atomic_write(path, lambda f: json.dump(obj, f))


# -- completed request-trace log (ISSUE 13) ---------------------------------

class RequestTraceLog:
    """Bounded store of COMPLETED end-to-end request traces — the
    ``/statusz`` "N slowest recent traces" source.

    The chrome tracer captures everything while enabled, but a serving
    fleet needs "what were the slowest requests lately?" answerable at
    any moment without chrome tracing on. Feeders (the fleet at
    delivery; a standalone engine at completion) call :meth:`record`
    with one small summary dict per finished request — ``trace_id``,
    latency, the condensed hop list the request accumulated across
    replicas. Memory is fixed (a deque of ``capacity``), recording is
    O(1), reads copy under the lock — a scrape never observes a
    half-appended entry."""

    def __init__(self, capacity=256):
        from collections import deque
        self.capacity = int(capacity)
        self._entries = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.recorded = 0

    def record(self, trace: dict):
        with self._lock:
            self._entries.append(dict(trace))
            self.recorded += 1

    def recent(self, n=None):
        """Newest-last; the whole resident window by default."""
        with self._lock:
            out = list(self._entries)
        return out if n is None else out[-int(n):]

    def slowest(self, n=10, key="latency_ms"):
        """The N slowest resident traces, slowest first (ties broken
        by trace id for a stable /statusz render)."""
        with self._lock:
            out = list(self._entries)
        out.sort(key=lambda e: (-float(e.get(key, 0.0)),
                                str(e.get("trace_id"))))
        return out[:int(n)]

    def clear(self):
        with self._lock:
            self._entries.clear()


_trace_log = RequestTraceLog()


def get_trace_log() -> RequestTraceLog:
    """The process-wide completed-request trace log (always on; the
    fleet and standalone engines feed it, /statusz reads it)."""
    return _trace_log


_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-wide tracer (disabled until ``profiler.enable()`` /
    ``PADDLE_PROFILER_TRACE=1`` / ``FLAGS_enable_host_trace``)."""
    return _tracer


def trace_span(name, cat="user", sync=None, **args):
    """THE way to open a span (``Tracer.span`` on the global tracer)."""
    return _tracer.span(name, cat=cat, sync=sync, **args)


# -- perf event log --------------------------------------------------------

_logged_once: set = set()
_logged_lock = threading.Lock()


def log_perf_event(event: str, message: str, *, level=logging.INFO,
                   once_key=None, **args) -> bool:
    """Log a performance-relevant event at INFO (logger
    ``paddle_tpu.perf``) and mirror it into the trace as an instant
    marker. This is how silent perf cliffs become observable: scan-path
    declines, remat-dose drops, dropless downgrades all route here.

    ``once_key`` dedupes process-wide (the cliff fires every forward;
    the log should not). Returns True iff the line was emitted."""
    if once_key is not None:
        with _logged_lock:
            if once_key in _logged_once:
                return False
            _logged_once.add(once_key)
    perf_logger.log(level, "[%s] %s", event, message)
    _tracer.instant(event, cat="perf_event", message=message, **args)
    return True


def epoch_summary(epoch, steps, seconds, **metrics) -> dict:
    """Per-epoch training summary (hapi.Model.fit hook): logs one INFO
    line, emits gauges, and returns the summary dict."""
    avg_ms = seconds / max(steps, 1) * 1e3
    summary = {"epoch": int(epoch), "steps": int(steps),
               "epoch_s": round(seconds, 4),
               "avg_step_ms": round(avg_ms, 3),
               "steps_per_s": round(steps / seconds, 3) if seconds else 0.0}
    summary.update(metrics)
    perf_logger.info("[hapi/epoch] %s", json.dumps(summary, sort_keys=True))
    # registry gauge (docs/observability.md); the default registry
    # mirrors into the tracer while tracing is on, preserving the old
    # chrome-trace counter stream
    from .metrics import get_registry
    get_registry().gauge("hapi/avg_step_ms").set(
        summary["avg_step_ms"], epoch=int(epoch))
    return summary
