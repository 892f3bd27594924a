"""Trial engine — times compiled candidate variants and picks winners.

The measurement discipline comes from ``profiler/trace.py``'s
device-sync rules: a trial's clock only stops after
:func:`profiler.trace.block_on` confirms the device finished (dispatch
time alone is meaningless on an async backend). Each candidate runs
``warmup`` discarded iterations (compilation + cold caches), then
``repeats`` timed iterations reduced by MEDIAN — robust to one GC
pause or dispatch hiccup, unlike mean or min.

Before anything is timed, candidates are pruned with the roofline
model from ``profiler/cost.py``: a candidate whose lower-bound time
(``max(flops/peak_flops, bytes/hbm_bw)``) exceeds ``prune_ratio`` ×
the best candidate's lower bound cannot win even if it runs at 100%
of the roofline, so the engine proves it worse and skips its compile
+ trial entirely (the cost model is a bound, not an estimate — the
default ratio is deliberately generous).

Non-representative backends: when the trial backend is not a TPU
(``JAX_PLATFORMS=cpu`` smoke runs, interpret-mode Pallas), the engine
warns ONCE per process, still records results (they are real orderings
of the interpreted kernels, useful for plumbing tests) but flags every
cache entry ``representative: false`` — and the cache key's backend
namespace (cache.py) already guarantees such entries can never serve a
TPU process.
"""

from __future__ import annotations

import time
import warnings

from .cache import TuningCache, backend_signature, get_cache, make_key
from .surface import TunableSurface, get_surface, sig_from_dict

__all__ = ["TrialEngine", "TrialResult", "measure_callable",
           "roofline_lower_bound_s"]

_non_tpu_warned = False


def _warn_non_tpu_once(backend: str) -> bool:
    """One-time non-representative-backend warning. Returns True iff
    the backend IS representative (a TPU)."""
    global _non_tpu_warned
    if backend.startswith("tpu:"):
        return True
    if not _non_tpu_warned:
        _non_tpu_warned = True
        msg = (f"trial engine running on non-TPU backend {backend!r}: "
               "timings are recorded but flagged non-representative, "
               "and cached under this backend's namespace (they can "
               "never be served to a TPU process)")
        warnings.warn("paddle_tpu.tuner: " + msg, stacklevel=3)
        from ..profiler.trace import log_perf_event
        log_perf_event("tuner/non_tpu_backend", msg,
                       once_key="tuner/non_tpu_backend")
    return False


def measure_callable(fn, warmup=1, repeats=3) -> float:
    """Median seconds over ``repeats`` device-synced calls of ``fn``
    (a zero-arg callable returning jax arrays / pytrees), after
    ``warmup`` discarded calls that absorb compilation."""
    from ..profiler.trace import block_on
    for _ in range(max(int(warmup), 0)):
        block_on(fn())
    times = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        block_on(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    n = len(times)
    mid = n // 2
    return times[mid] if n % 2 else 0.5 * (times[mid - 1] + times[mid])


def roofline_lower_bound_s(flops, bytes_, peaks) -> float:
    """The time no schedule can beat: compute-bound AND memory-bound
    floors, whichever binds."""
    lb = 0.0
    if flops and peaks.flops:
        lb = max(lb, float(flops) / peaks.flops)
    if bytes_ and peaks.hbm_bw:
        lb = max(lb, float(bytes_) / peaks.hbm_bw)
    return lb


class TrialResult:
    """Outcome of one surface search (winner + full trial table)."""

    def __init__(self, surface, shape_sig, dtype, backend, best_config,
                 best_ms, trials, pruned, representative, cached_hit=False,
                 truncated=0):
        self.surface = surface
        self.shape_sig = shape_sig
        self.dtype = str(dtype)
        self.backend = backend
        self.best_config = best_config
        self.best_ms = best_ms
        self.trials = trials            # [(config, median_ms)]
        self.pruned = pruned            # [(config, lower_bound_ms)]
        self.representative = representative
        self.cached_hit = cached_hit
        self.truncated = truncated      # candidates dropped by max_trials

    @property
    def key(self):
        return make_key(self.surface, self.shape_sig, self.dtype,
                        self.backend)

    def to_dict(self) -> dict:
        return {"surface": self.surface, "shape_sig": self.shape_sig,
                "dtype": self.dtype, "backend": self.backend,
                "config": self.best_config,
                "median_ms": self.best_ms,
                "representative": self.representative,
                "cached_hit": self.cached_hit,
                "truncated": self.truncated,
                "trials": [{"config": c, "median_ms": ms}
                           for c, ms in self.trials],
                "pruned": [{"config": c, "lower_bound_ms": ms}
                           for c, ms in self.pruned]}


class TrialEngine:
    """Search driver: prune → time → pick → persist (module docstring).

    measure_fn: ``fn(config, shape) -> seconds`` — injectable timing
      oracle. The default compiles and times ``builder(config, shape)``
      on the live backend; tests inject a synthetic cost table for a
      deterministic, TPU-free fast-tier check that the engine picks
      the known-best candidate.
    peaks: the chip's ``profiler.cost.Peaks`` for roofline pruning;
      default the device's table entry. On a device the table does not
      know (the CPU) nothing is pruned unless peaks are passed.
    """

    def __init__(self, cache: TuningCache | None = None, *, warmup=2,
                 repeats=5, prune_ratio=4.0, device=None, peaks=None):
        self.cache = cache if cache is not None else get_cache()
        self.warmup = int(warmup)
        self.repeats = int(repeats)
        self.prune_ratio = float(prune_ratio)
        self._device = device
        self._peaks = peaks
        self._backend = None

    @property
    def backend(self) -> str:
        if self._backend is None:
            self._backend = backend_signature(self._device)
        return self._backend

    # -- pruning -----------------------------------------------------------

    def _prune(self, surface: TunableSurface, shape, candidates):
        """Split candidates into (survivors, pruned): a candidate is
        pruned when the cost model PROVES it slower — its roofline
        lower bound exceeds ``prune_ratio`` × the grid's best lower
        bound (generous: the survivor would have to run below
        1/prune_ratio of roofline for the pruned one to have won)."""
        if surface.cost_fn is None or len(candidates) <= 1:
            return list(candidates), []
        from ..profiler.cost import known_peaks
        peaks = self._peaks or known_peaks(self._device)
        if peaks is None:
            return list(candidates), []
        bounds = []
        for c in candidates:
            try:
                flops, bytes_ = surface.cost_fn(c, shape)
                bounds.append(roofline_lower_bound_s(flops, bytes_, peaks))
            except Exception:
                bounds.append(None)     # unknown cost: THIS candidate
                #                         is never pruned, but it must
                #                         not poison the floor either
        known = [b for b in bounds if b]
        floor = min(known) if known else 0.0
        survivors, pruned = [], []
        for c, b in zip(candidates, bounds):
            if b and floor > 0.0 and b > self.prune_ratio * floor:
                pruned.append((c, b * 1e3))
            else:
                survivors.append(c)
        if not survivors:               # paranoia: never prune everything
            return list(candidates), []
        return survivors, pruned

    # -- search ------------------------------------------------------------

    def search(self, surface_name: str, shape: dict, builder=None, *,
               dtype="bfloat16", measure_fn=None, persist=True,
               force=False, max_trials=None) -> TrialResult:
        """Tune one surface at one shape.

        builder: ``fn(config, shape) -> zero-arg callable | None`` —
          produces the trial body for a candidate (None = candidate
          infeasible at runtime, dropped). Required unless
          ``measure_fn`` is given.
        force: re-tune even when the cache already holds this key
          (the CLI's --force; default is resume semantics — a crashed
          sweep restarts and skips every key that already committed).
        max_trials: cap on candidates actually timed (after pruning,
          default-first order). NOT a silent cap: the dropped count is
          reported in the result and the cache entry.
        """
        surface = get_surface(surface_name)
        shape = dict(shape)
        sig = sig_from_dict(shape)
        backend = self.backend
        representative = _warn_non_tpu_once(backend)
        key = make_key(surface_name, sig, dtype, backend)

        if not force:
            hit = self.cache.get(key)
            if hit is not None:
                return TrialResult(
                    surface_name, sig, dtype, backend,
                    dict(hit["config"]), hit.get("median_ms"),
                    trials=[], pruned=[],
                    representative=hit.get("representative", True),
                    cached_hit=True)

        candidates = surface.grid(shape)
        if not candidates:
            raise ValueError(
                f"surface {surface_name!r} produced no valid candidates "
                f"for shape {sig!r}")
        survivors, pruned = self._prune(surface, shape, candidates)
        truncated = 0
        if max_trials is not None and len(survivors) > max_trials:
            truncated = len(survivors) - int(max_trials)
            survivors = survivors[:int(max_trials)]

        if measure_fn is None and builder is None:
            raise ValueError("search() needs a builder when no "
                             "measure_fn is injected")
        trials, errored = [], []
        for config in survivors:
            # per-candidate isolation: one candidate that fails to
            # compile/run (VMEM overflow, Mosaic legalization, ...) is
            # dropped and reported — it must not abort the search and
            # discard every already-timed trial
            try:
                if measure_fn is not None:
                    seconds = measure_fn(dict(config), dict(shape))
                else:
                    fn = builder(dict(config), dict(shape))
                    if fn is None:
                        continue
                    seconds = measure_callable(fn, warmup=self.warmup,
                                               repeats=self.repeats)
            except Exception as e:  # noqa: BLE001 — candidate-scoped
                errored.append((dict(config), f"{type(e).__name__}: {e}"))
                continue
            if seconds is None:
                continue
            trials.append((dict(config), float(seconds) * 1e3))
        if errored:
            warnings.warn(
                f"paddle_tpu.tuner: {surface_name!r} @ {sig!r}: "
                f"{len(errored)} candidate(s) failed and were dropped "
                f"(first: {errored[0][0]} -> {errored[0][1]})",
                stacklevel=2)
        if not trials:
            raise RuntimeError(
                f"surface {surface_name!r}: no candidate produced a "
                f"timing at shape {sig!r}"
                + (f" ({len(errored)} errored; first: "
                   f"{errored[0][1]})" if errored else ""))
        best_config, best_ms = min(trials, key=lambda t: t[1])
        self.cache.put(key, best_config, median_ms=best_ms,
                       repeats=self.repeats, representative=representative,
                       source="search",
                       extra={"trials": len(trials),
                              "pruned": len(pruned),
                              "truncated": truncated,
                              "errored": len(errored)},
                       persist=False)
        if persist:
            self.cache.save_best_effort()
        return TrialResult(surface_name, sig, dtype, backend, best_config,
                           best_ms, trials, pruned, representative,
                           truncated=truncated)
