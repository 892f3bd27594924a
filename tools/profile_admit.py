#!/usr/bin/env python3
"""A traced benchmark run with the HOST side of a serving turn timed.

    chiprun -- python3 tools/profile_admit.py <cell> <seed>   (chip only)

Runs ``perfbench/run.py --workload <cell> --seed <seed> --seconds 40
--trace 1`` unchanged, like ``tools/count_trace_ops.py`` (whose per-op
event counts it prints too), with wall-clock timers around the engine's
phases of a turn (``_admit``, ``_dispatch_step``, ``_harvest_step``,
``_drain``), the host functions they call, and the two ways host code
reaches the device outside the step program: ``x.at[i].set(...)`` (an eager
one-op program) and ``jnp.asarray`` (a transfer). Counted from the end of
the warm-up (``reset_gauges``) to the end of the run; ``[profile_admit]``
lines give calls and milliseconds a turn, each inner function under the
phase it ran in. A function the tree does not have is left out. Not part of
the benchmark: it wraps from outside and edits nothing."""
import collections
import os
import runpy
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = ("_admit", "_dispatch_step", "_harvest_step", "_drain")
INNER = ("_stage_slot", "_clear_slot", "_pc_match", "_pc_pin",
         "_alloc_pages", "_next_candidate", "_already_complete",
         "_stage_prompt_chunks", "_count_dispatch", "_pc_insert",
         "_release_pages", "_complete_ok", "_reap")


class Clock:
    def __init__(self):
        self.calls = collections.Counter()
        self.secs = collections.Counter()
        self.phase = "outside"
        self.leaf_depth = 0

    def reset(self):
        self.calls.clear()
        self.secs.clear()

    def phase_of(self, name, fn):
        def timed(*a, **k):
            held, self.phase = self.phase, name
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                self.secs[name] += time.perf_counter() - t0
                self.calls[name] += 1
                self.phase = held
        return timed

    def inner(self, name, fn, leaf=False):
        def timed(*a, **k):
            if leaf and self.leaf_depth:
                return fn(*a, **k)      # jax's own nested use of a leaf
            self.leaf_depth += leaf
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                key = f"{self.phase}/{name}"
                self.secs[key] += time.perf_counter() - t0
                self.calls[key] += 1
                self.leaf_depth -= leaf
        return timed

    def report(self):
        turns = max(1, self.calls["step"])
        print(f"[profile_admit] turns={self.calls['step']} "
              f"admissions={self.calls['_admit/_stage_slot']} "
              f"step_ms_a_turn={1e3 * self.secs['step'] / turns:.3f}")
        for key in sorted(self.secs, key=lambda k: -self.secs[k]):
            if key == "step":
                continue
            print(f"[profile_admit] {self.calls[key] / turns:8.2f} calls "
                  f"{1e3 * self.secs[key] / turns:8.3f} ms a turn  {key}")


def install(clock):
    import jax.numpy as jnp
    from jax._src.numpy import array_methods

    from paddle_tpu.inference import serving
    from paddle_tpu.profiler import flight_recorder
    eng = serving.ContinuousBatchingEngine
    eng.step = clock.phase_of("step", eng.step)
    for name in PHASES:
        if hasattr(eng, name):
            setattr(eng, name, clock.phase_of(name, getattr(eng, name)))
    # an inner function is booked under the innermost phase it ran in
    for name in INNER:
        if hasattr(eng, name):
            setattr(eng, name, clock.inner(name, getattr(eng, name)))
    serving.record_hop = clock.inner("record_hop", serving.record_hop)
    flight_recorder.record_event = clock.inner(
        "record_event", flight_recorder.record_event)
    ref = array_methods._IndexUpdateRef
    ref.set = clock.inner("at[].set", ref.set, leaf=True)
    jnp.asarray = clock.inner("jnp.asarray", jnp.asarray, leaf=True)
    held = eng.reset_gauges

    def reset_gauges(self):
        clock.reset()                   # the warm-up ends here
        return held(self)
    eng.reset_gauges = reset_gauges


if __name__ == "__main__":
    cell, seed = sys.argv[1], sys.argv[2]
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from count_trace_ops import _counting
    from perfbench.harness import tracing, xplane
    clock = Clock()
    install(clock)
    counted = _counting(tracing.Tracer.discard, xplane)

    def discard(self):
        clock.report()
        counted(self)
    tracing.Tracer.discard = discard
    sys.argv = ["perfbench/run.py", "--workload", cell, "--seed", seed,
                "--seconds", "40", "--trace", "1"]
    runpy.run_path(os.path.join(ROOT, "perfbench", "run.py"),
                   run_name="__main__")
