#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. The last line of standard output is the
result (see ``harness/main.py``); everything before it is an observation.
There is no CPU mode: without the chips the cell asks for the process
exits non-zero and prints no result.
"""

import os
import sys
import time

_T0 = time.perf_counter()          # set-up is counted from here

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

if __name__ == "__main__":
    from perfbench.harness.main import main
    sys.exit(main(sys.argv[1:], t0=_T0, root=_ROOT))
