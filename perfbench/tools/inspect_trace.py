#!/usr/bin/env python3
"""Print what a recorded trace holds: planes, lines, and the device ops by
self time. ``python perfbench/tools/inspect_trace.py <trace dir or .pb>``.
Look at one by hand before trusting a pattern in ``metrics/*.json``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.harness import xplane  # noqa: E402


def main(path, n=60):
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    tr = xplane.load(path)
    print(xplane.summary(tr, n))
    names = {}
    for e in tr.host:
        names[e.name] = names.get(e.name, 0.0) + (e.end - e.start)
    print("-- host events by total time")
    for k, v in sorted(names.items(), key=lambda kv: -kv[1])[:40]:
        print(f"   {v:10.6f} s  {k}")
    red = xplane.reduce(tr)
    print("-- reduce:", {k: v for k, v in red.items()})


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
