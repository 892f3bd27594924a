"""``paddle.profiler`` (python/paddle/profiler/ parity, UNVERIFIED) —
grown into the perf observability subsystem.

Reference: host RecordEvent ranges + CUPTI device tracer → chrome trace
(SURVEY.md §5). TPU-native: ``jax.profiler`` captures host + device (TPU)
timelines into TensorBoard/Perfetto format; ``RecordEvent`` maps to
``jax.profiler.TraceAnnotation`` so user annotations appear in the same
trace. Summary tables come from jax's own profile session where available;
``profiler_result.save`` exports the trace dir.

On top of that capture surface, three structured layers (docs/
profiling.md):

- :mod:`.trace` — nestable ``trace_span()`` events with wall time,
  device-sync points, gauges, chrome-trace + JSON export;
- :mod:`.cost` — FLOPs/bytes accounting from static shapes, per-section
  MFU and roofline (compute- vs memory-bound) classification;
- :mod:`.breakdown` — the in-program section-ablation harness that
  attributes step time inside one compiled program (MoE gating / sort /
  a2a / expert-matmul; the evidence layer for every perf PR).

Enable via ``Profiler``/``enable()``, the ``PADDLE_PROFILER_TRACE=1``
env flag, or ``FLAGS_enable_host_trace``.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax

from ..framework.core import Tensor
from . import cost, trace  # noqa: F401 (public submodules)
from . import exposition, flight_recorder, goodput  # noqa: F401
from . import metrics, slo  # noqa: F401
from .breakdown import (StepBreakdown, ablation_breakdown,  # noqa: F401
                        moe_step_breakdown)
from .exposition import ObservabilityServer  # noqa: F401
from .flight_recorder import FlightRecorder, Watchdog  # noqa: F401
from .goodput import GoodputLedger  # noqa: F401
from .metrics import (Counter, FederatedRegistry, Gauge,  # noqa: F401
                      Histogram, MetricsRegistry, get_registry)
from .slo import SLORule, SLOTracker  # noqa: F401
from .trace import (Tracer, block_on, get_tracer,  # noqa: F401
                    log_perf_event, trace_span)

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SortedKeys", "SummaryView", "ProfilerOptions", "enable",
           "disable", "trace_span", "get_tracer", "Tracer", "block_on",
           "log_perf_event", "StepBreakdown", "ablation_breakdown",
           "moe_step_breakdown", "cost", "trace",
           "metrics", "flight_recorder", "goodput",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry", "FlightRecorder", "Watchdog", "GoodputLedger"]


def _env_bool(name, default=False):
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class ProfilerOptions:
    """Knob surface for the structured trace layer (the
    ``paddle.utils.profiler.ProfilerOptions`` shape, TPU-native fields).
    Every field has a ``PADDLE_PROFILER_*`` env twin so headless runs
    (bench.py, the elastic launcher) can flip tracing without code."""

    output_dir: str = "./profiler_log"          # PADDLE_PROFILER_LOG_DIR
    trace_enabled: bool = False                 # PADDLE_PROFILER_TRACE
    with_flops: bool = False                    # PADDLE_PROFILER_WITH_FLOPS
    export_on_disable: bool = True

    @classmethod
    def from_env(cls) -> "ProfilerOptions":
        return cls(
            output_dir=os.environ.get("PADDLE_PROFILER_LOG_DIR",
                                      "./profiler_log"),
            trace_enabled=_env_bool("PADDLE_PROFILER_TRACE"),
            with_flops=_env_bool("PADDLE_PROFILER_WITH_FLOPS"))


def enable(options: ProfilerOptions | None = None) -> Tracer:
    """Turn the structured trace layer on process-wide."""
    tr = get_tracer()
    tr.options = options or ProfilerOptions.from_env()
    tr.enabled = True
    return tr


def disable(export: bool | None = None) -> str | None:
    """Turn tracing off; by default exports the chrome trace into
    ``options.output_dir`` if any events were recorded. Returns the
    export path (or None)."""
    tr = get_tracer()
    opts = tr.options or ProfilerOptions()
    tr.enabled = False
    path = None
    if (opts.export_on_disable if export is None else export) \
            and tr.events:
        path = tr.export_chrome_trace(
            os.path.join(opts.output_dir, "paddle_trace.json"))
    return path


def _env_trace_requested() -> bool:
    if _env_bool("PADDLE_PROFILER_TRACE"):
        return True
    # FLAGS_enable_host_trace=1 in the environment: define_flag ingests
    # the value but on_change only fires through set_flags, so honor
    # the env form here (the flag's contract says it is the same switch)
    try:
        from ..framework.flags import flag
        return bool(flag("FLAGS_enable_host_trace"))
    except Exception:
        return False


if _env_trace_requested():
    enable()  # env-flag surface: tracing from process start


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "custom"
    TPU = "tpu"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SortedKeys:
    CPUTotal = 0
    CPUAvg = 1
    GPUTotal = 2


class SummaryView:
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Return a step→state callable (paddle.profiler.make_scheduler)."""
    cycle = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """Trace-ready handler directing the capture into ``dir_name``. The
    Profiler reads ``handler.log_dir`` at construction so the directory is
    set BEFORE recording starts (the jax trace is written at stop time)."""
    def handler(prof):
        prof._log_dir = dir_name
    handler.log_dir = dir_name
    return handler


def load_profiler_result(path):
    return path


class RecordEvent:
    """User range annotation: one ``trace_span`` held open between
    ``begin()`` and ``end()`` — in the jax/Perfetto trace whenever a
    profiler session is live, and in the chrome-trace/JSON export while
    the structured tracer is enabled."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None
        self.begin_ts = None

    def begin(self):
        self._span = trace_span(self.name, cat="record_event")
        self._span.__enter__()
        self.begin_ts = time.perf_counter()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False,
                 options: ProfilerOptions | None = None):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi
                else ProfilerState.CLOSED)
        self._on_trace_ready = on_trace_ready
        self._log_dir = os.environ.get("PADDLE_PROFILER_LOG_DIR",
                                       "./profiler_log")
        if on_trace_ready is not None and hasattr(on_trace_ready,
                                                  "log_dir"):
            self._log_dir = on_trace_ready.log_dir
        self._step = 0
        self._recording = False
        self._timer_only = timer_only
        self._step_times = []
        self._last = None
        self._with_flops = with_flops
        self._options = options
        if options is not None and getattr(options, "output_dir", None):
            self._log_dir = options.output_dir

    def start(self):
        if self._with_flops or self._options is not None:
            # structured trace layer rides along: spans/gauges recorded
            # while this Profiler is live land in the chrome export.
            # Save the global tracer's prior state — a sub-region
            # Profiler must not stomp a whole-process tracing session
            # (PADDLE_PROFILER_TRACE=1).
            tr = get_tracer()
            self._prev_trace_state = (tr.enabled, tr.options)
            opts = self._options or ProfilerOptions(
                output_dir=self._log_dir, with_flops=self._with_flops)
            enable(opts)
        self._last = time.perf_counter()
        self._maybe_transition()

    def stop(self):
        if self._recording:
            jax.profiler.stop_trace()
            self._recording = False
            if self._on_trace_ready:
                self._on_trace_ready(self)
        if self._with_flops or self._options is not None:
            prev_enabled, prev_options = getattr(
                self, "_prev_trace_state", (False, None))
            if prev_enabled:
                # outer tracing session continues: restore its options,
                # keep recording, export nothing early
                tr = get_tracer()
                tr.options = prev_options
            else:
                disable()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1
        self._maybe_transition()

    def _maybe_transition(self):
        if self._timer_only or self._scheduler is None:
            return
        state = self._scheduler(self._step)
        want = state in (ProfilerState.RECORD,
                         ProfilerState.RECORD_AND_RETURN)
        if want and not self._recording:
            os.makedirs(self._log_dir, exist_ok=True)
            jax.profiler.start_trace(self._log_dir)
            self._recording = True
        elif not want and self._recording:
            jax.profiler.stop_trace()
            self._recording = False
            if self._on_trace_ready:
                self._on_trace_ready(self)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        n = len(self._step_times)
        if not n:
            print("No steps recorded")
            return
        avg = sum(self._step_times) / n
        print(f"steps: {n}  avg step time: {avg * 1e3:.3f} ms  "
              f"throughput: {1.0 / avg:.2f} steps/s")
        # MFU/roofline against the device's table entry; on a device
        # the table does not know the columns are left out
        sections = get_tracer().section_summary()
        for name, a in sorted(sections.items(),
                              key=lambda kv: -kv[1]["total_ms"]):
            mfu_s = f"  MFU {a['mfu'] * 100:.1f}%" if "mfu" in a else ""
            bound = a.get("roofline", {}).get("bound", "")
            print(f"  {name}: {a['count']}x  total {a['total_ms']:.2f} ms"
                  f"  mean {a['mean_ms']:.3f} ms{mfu_s}"
                  f"{'  [' + bound + '-bound]' if bound else ''}")

    def export(self, path=None, format="json"):
        tr = get_tracer()
        if path is not None and tr.events:
            tr.export_chrome_trace(path)
            return path
        return self._log_dir

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
