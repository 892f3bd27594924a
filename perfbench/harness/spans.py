"""Readers of what the program says about itself: its spans, which it
writes into the profiler's host plane on the device trace's clock
(``paddle_tpu.profiler.trace.trace_span``), and its process-wide counters
(``paddle_tpu.profiler.metrics.get_registry``). Named from a metric's file
as ``perfbench.harness.spans:<function>``. Each returns None where the
program has no such span or counter — a parent commit from before they
existed — and the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import re

from . import xplane


def _overlap(a, b):
    """Seconds in which an interval of ``a`` and one of ``b`` both hold;
    each a list of disjoint (start, end) sorted by start."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read_idle_in_span(spec, ctx):
    """Of chip 0's idle time in the reduced window, the part that lies
    under the program's spans named by ``spec["span"]`` (a regex) — or,
    with ``"other": true``, under none of ``spec["spans"]`` — per step, in
    ms. By overlap, not by a gap's midpoint: a gap that straddles two
    spans is split between them, so classes of disjoint spans and their
    ``other`` add up to the window's whole idle time."""
    tr, r = ctx.get("trace"), ctx.get("reduced")
    if tr is None or not r or not r.get("steps") or not tr.device_ops:
        return None
    t0, t1 = r["window"]
    idle = xplane.gaps(tr.device_ops[min(tr.device_ops)], t0, t1)
    other = bool(spec.get("other"))
    rxs = [re.compile(p) for p in (spec["spans"] if other
                                   else [spec["span"]])]
    evs = sorted((e for e in tr.host
                  if any(rx.search(e.name) for rx in rxs)),
                 key=lambda e: (e.start, -e.end))
    if not evs:
        return None
    under = _overlap(idle, xplane.union(xplane.clip(evs, t0, t1)))
    if other:
        under = sum(b - a for a, b in idle) - under
    return 1e3 * under / r["steps"]


def read_registry_ratio(spec, ctx):
    """``num`` over ``den``, each a counter of the program's process-wide
    registry, or a list of two for the first less the second. The
    counters run from import and nothing here windows them: warm-up
    counts, and so does every function of the process that adds to them,
    so a per-call ratio is one function's only in a cell that runs one."""
    try:
        from paddle_tpu.profiler.metrics import get_registry
    except ImportError:
        return None
    reg = get_registry()

    def value(names):
        names = [names] if isinstance(names, str) else names
        ms = [reg.get(n) for n in names]
        if any(m is None for m in ms):
            return None
        return ms[0].value - sum(m.value for m in ms[1:])

    num, den = value(spec["num"]), value(spec["den"])
    if num is None or not den:
        return None
    return float(num) / float(den)
