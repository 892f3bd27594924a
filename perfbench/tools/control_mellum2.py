#!/usr/bin/env python3
"""The upper readings the ``mellum2-ep4-d4`` cell's limits are set from
(chip only; the benchmark's own runs never run this).

    python3 perfbench/tools/control_mellum2.py --seeds 1,2,3 [--faults fp8]

For every seed, in ONE process: the plain reference's first three steps on
the cell's batches, then the same steps with one thing wrong — (a) every
matmul in fp8 (``precision.mm_fp8``: the nearest precision below the
bfloat16 the configuration trains in), (b) the window layers attending every
earlier key, (c) the last of the 16 held experts left out, (d) the full
layer's YaRN replaced by the default rotary embedding — each judged against
the clean reference by the function that judges a run (``train.compare``):
each has to come out with ``correct`` false. The program's own (lower)
readings are the cell's benchmark runs. One JSON line per seed (the clean
reference) and one per seed and fault, each as soon as it is read, on stdout
and in ``chiprun_out/control.mellum2.jsonl``. A reading takes about three
minutes on one v5e (three steps of the float32 reference), so ``--faults``
picks which to read.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perfbench.harness import main as M  # noqa: E402
from perfbench.harness import precision, spec, train  # noqa: E402
from perfbench.harness import traffic as T  # noqa: E402

CELL = "mellum2-ep4-d4.code_pretrain_8k"
FAULTS = (("fp8", {"mm": precision.mm_fp8}),
          ("window_all_keys", {"fault": "window_all_keys"}),
          ("drop_expert", {"fault": "drop_expert"}),
          ("default_rope", {"fault": "default_rope"}))


def readings(cfg, w0, batches, **kw):
    """``train.reference_readings`` with the reference's ``fault``."""
    import jax.numpy as jnp
    ref = spec.reference_module(cfg)
    _, _, norms = train.pieces(cfg)
    losses, gn, w3 = ref.train_steps(cfg["sizes"], cfg["optimizer"], w0,
                                     [jnp.asarray(b) for b in batches],
                                     norms, **kw)
    gn = np.asarray(gn)
    return (np.asarray(losses), gn[0], gn[1], train.update_norms(cfg, w3, w0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(t for t, _ in FAULTS))
    a = ap.parse_args()
    wanted = a.faults.split(",")
    faults = [(t, kw) for t, kw in FAULTS if t in wanted]
    t0 = time.perf_counter()
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, CELL)
    cfg = spec.load_config(ROOT, bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    M.find_devices(int(cell["chips"]))
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "control.mellum2.jsonl")
    _, names, _ = train.pieces(cfg)

    def put(row):
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")

    for seed in (int(s) for s in a.seeds.split(",")):
        gen = T.train_batches(traffic, seed)
        batches = [next(gen) for _ in range(train.WARM_STEPS)]
        t = time.perf_counter()
        ref, w0 = train.reference_readings(cfg, traffic, seed, batches)
        put({"seed": seed, "reference_s": round(time.perf_counter() - t, 1),
             "ref_losses": ref[0].tolist()})
        for tag, kw in faults:
            t = time.perf_counter()
            got = readings(cfg, w0, batches, **kw)
            ok, out = train.compare(cfg, names, got, ref)
            put({"seed": seed, "fault": tag, "correct": ok, "checks": out,
                 "s": round(time.perf_counter() - t, 1)})
            gc.collect()
        del w0
        gc.collect()
    print(f"[done +{time.perf_counter() - t0:.1f}s]", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
