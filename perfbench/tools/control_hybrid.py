#!/usr/bin/env python3
"""The readings the hybrid serving cell's limit is set from (chip only; the
benchmark's own runs never run this).

    python3 perfbench/tools/control_hybrid.py --workload <cell> --seed <n> \
        --seconds 20

``control.py``'s serving flow for ONE seed a process (this configuration's
weights do not fit the device twice, so they cannot be re-seeded in place),
and beside the fp8 control the faults ``reference/nemotron_h.py`` can plant
in ONE part of the mathematics each: the reference with that part wrong is
put in the program's place and judged by ``serve.judge`` as the window's
tokens are. One JSON line on stdout and in
``chiprun_out/control.<cell>.jsonl``.
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import main as M  # noqa: E402
from perfbench.harness import model as model_mod  # noqa: E402
from perfbench.harness import precision, serve, spec, tracing  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args()
    t0 = time.perf_counter()
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, a.workload)
    cfg = spec.load_config(ROOT, bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    M.find_devices(int(cell["chips"]))
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    say = M.make_say(t0)
    ref = spec.reference_module(cfg)

    model, _ = model_mod.build(cfg, a.seed, say)
    model.eval()
    eng = serve.build_engine(cfg, model)
    serve.warm_up(eng, cfg, traffic, say)
    win = serve.run_window(eng, cfg, traffic, a.seed, a.seconds, None,
                           tracing.annotator(False))
    attempted, failed = serve.counts(win)
    k = int(cfg["check"]["sample_requests"])
    sample = serve.pick_sample(win, a.seed, k)
    ids, pos, tok, mask = serve.pack_sample(sample, traffic, k)
    del eng, model
    import gc
    gc.collect()
    ok, out, arg = serve.judge(cfg, a.seed, failed, ids, pos, tok, mask)
    row = {"seed": a.seed, "attempted": attempted, "failed": failed,
           "program": {"correct": ok, "checks": out},
           "argmax_agreement": float((arg == tok)[mask].mean())}
    say("program", **{k_: v for k_, v in row.items() if k_ != "program"},
        gap=out["served_gap_max"]["value"])

    def control(tag, cfg_c, mm=None):
        _, arg_c = serve.served_gaps(cfg_c, a.seed, ids, pos, tok, mm=mm)
        ok_c, out_c, _ = serve.judge(cfg, a.seed, 0, ids, pos, arg_c, mask)
        row[tag] = {"correct": ok_c,
                    "served_gap_max": out_c["served_gap_max"]["value"],
                    "argmax_agreement": float((arg_c == arg)[mask].mean())}
        say("control", tag=tag, **row[tag])

    control("fp8", cfg, mm=precision.mm_fp8)
    for fault in ref.FAULTS:
        cfg_f = copy.copy(cfg)
        cfg_f["sizes"] = dict(cfg["sizes"], fault=fault)
        control(fault, cfg_f)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    line = json.dumps(row)
    print(line, flush=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control.{a.workload}.jsonl"), "a") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
