#!/usr/bin/env python3
"""A traced benchmark run that also COUNTS each device op's events.

    chiprun -- python3 tools/count_trace_ops.py <cell> <seed>   (chip only)

Runs ``perfbench/run.py --workload <cell> --seed <seed> --seconds 40
--trace 1`` unchanged and, before the harness deletes its trace, prints per
device op the number of events and their summed seconds inside the traced
window (``[count_ops]`` lines; the result line stays the last line). The
benchmark's own breakdown prints seconds alone, and seconds without counts
misled once: PR 28 read "16 pool copies a turn" where 304 ran. Not part of
the benchmark: it wraps ``perfbench.harness.tracing.Tracer.discard`` from
outside and edits nothing."""
import collections
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counting(discard, xplane, top=45):
    def counted(self):
        tr = xplane.load(xplane.find_xplane(self.dir))
        red = xplane.reduce(tr)
        t0, t1 = red["window"]
        n, s = collections.Counter(), collections.Counter()
        for e in xplane.clip(tr.device_ops[min(tr.device_ops)], t0, t1):
            k = xplane.short_name(e.name)
            n[k] += 1
            s[k] += e.end - e.start
        print(f"[count_ops] window_s={t1 - t0:.4f} steps={red['steps']} "
              f"busy_s={red['busy_s']:.4f}")
        for k, v in sorted(s.items(), key=lambda kv: -kv[1])[:top]:
            print(f"[count_ops] {n[k]:6d} events {v:9.5f} s  {k}")
        discard(self)
    return counted


if __name__ == "__main__":
    cell, seed = sys.argv[1], sys.argv[2]
    sys.path.insert(0, ROOT)
    from perfbench.harness import tracing, xplane
    tracing.Tracer.discard = _counting(tracing.Tracer.discard, xplane)
    sys.argv = ["perfbench/run.py", "--workload", cell, "--seed", seed,
                "--seconds", "40", "--trace", "1"]
    runpy.run_path(os.path.join(ROOT, "perfbench", "run.py"),
                   run_name="__main__")
