#!/usr/bin/env python3
"""From two sets of runs to the spread a bound is set from.

    python3 perfbench/tools/spread.py chiprun_out/sets.<cell>.txt

Each line of the file: ``SET <A|B> seed=<n> rc=<rc> <result line>``. Per
end-to-end metric: each set's median and spread ((Q3 - Q1) / median with
``statistics.quantiles(values, n=4)``), the wider of the two, five times
it, and how far the second set's median lies from the first's; then the
window a bound has to lie in by the driver's two refusals: over twice the
mean of the sets' spreads without each set's run farthest from its median
(too tight), under eight times the wider spread (too loose). ``setup_s``
leaves out each set's first run (it may compile)."""

import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.harness.stats import quartile_spread as spread  # noqa: E402


def trimmed(vals):
    """The spread without the run farthest from the median."""
    med = statistics.median(vals)
    rest = list(vals)
    rest.remove(max(rest, key=lambda v: abs(v - med)))
    return spread(rest) if len(rest) >= 2 else 0.0


def main(path):
    sets = {}
    for line in open(path):
        mt = re.match(r"SET (\w+) seed=(\d+) rc=(\d+) (\{.*\})\s*$", line)
        if not mt:
            print("unreadable:", line[:120])
            continue
        res = json.loads(mt.group(4))
        if not res["correct"]:
            print("NOT CORRECT:", line[:300])
        for k, v in res["metrics"].items():
            sets.setdefault(k, {}).setdefault(mt.group(1), []).append(
                v["value"])
        sets.setdefault("memory_peak_gb", {}).setdefault(
            mt.group(1), []).append(res["device"]["memory_peak_bytes"] / 1e9)
    for k, by in sets.items():
        row, meds, sp, tr = [], [], [], []
        for name, vals in sorted(by.items()):
            use = vals[1:] if k == "setup_s" else vals
            if len(use) < 2:
                continue
            meds.append(statistics.median(use))
            sp.append(spread(use))
            tr.append(trimmed(use))
            row.append(f"{name}: n={len(use)} median={meds[-1]:.6g} "
                       f"spread={100 * sp[-1]:.3f}% (without the farthest "
                       f"run {100 * tr[-1]:.3f}%) min={min(use):.6g} "
                       f"max={max(use):.6g}")
        print(k)
        for r in row:
            print("   ", r)
        if len(meds) == 2:
            print(f"    wider spread {100 * max(sp):.3f}% -> x5 = "
                  f"{100 * 5 * max(sp):.2f}% ; second median vs first "
                  f"{100 * (meds[1] / meds[0] - 1):+.3f}%")
            print(f"    a bound has to lie in ({100 * sum(tr):.3f}%, "
                  f"{100 * 8 * max(sp):.2f}%)")


if __name__ == "__main__":
    main(sys.argv[1])
