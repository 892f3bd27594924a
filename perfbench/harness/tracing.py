"""Starting and stopping the profiler around the traced span."""

from __future__ import annotations

import contextlib
import os
import shutil
import time


class Tracer:
    """One traced span per run. The trace is written inside the checkout
    (``perfbench/.trace``, gitignored) and deleted once read."""

    def __init__(self, directory):
        self.dir = directory
        self.started = self.finished = False
        self.span = None
        self._t0 = None

    def start(self):
        import jax
        if self.started or self.finished:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans are the harness's
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True
        self._t0 = time.perf_counter()

    def stop(self):
        import jax
        if not self.started or self.finished:
            return
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.span = (self._t0, t1)
        self.finished = True
        self.started = False

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def annotator(on):
    """``with annotate("bench/x"):`` — a span on the profiler's clock in a
    traced run, nothing otherwise."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)
