#!/usr/bin/env python3
"""The readings a cell's limits are set from (chip only; the benchmark's
own runs never run this).

    python3 perfbench/tools/control.py --workload <cell> --seeds 1,2,3,... \
        --seconds 20 --control 3

In ONE process (set-up is long): for every seed the program's numbers
against the plain reference (the lower readings), and for the first
``--control`` seeds the control's: the reference put in the program's
place, computed in the nearest precision below the configuration's
(fp8 for bfloat16), plus — training — the planted faults, each judged by
the function that judges a run (``serve.judge``, ``train.compare``): it
has to come out with ``correct`` false. One JSON line per seed on stdout
and in ``chiprun_out/control.<cell>.jsonl``.
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
from perfbench.harness import model as model_mod  # noqa: E402
from perfbench.harness import precision, spec, tracing  # noqa: E402


def serving(cfg, traffic, seeds, seconds, n_control, mm, say, emit):
    from perfbench.harness import serve

    model, specs = model_mod.build(cfg, seeds[0], say)
    model.eval()
    named = list(model.named_parameters())
    eng = serve.build_engine(cfg, model)
    serve.warm_up(eng, cfg, traffic, say)
    annotate = tracing.annotator(False)
    k = int(cfg["check"]["sample_requests"])
    for n, seed in enumerate(seeds):
        if n:
            model_mod.set_seed_weights(cfg, named, specs, seed)
        win = serve.run_window(eng, cfg, traffic, seed, seconds, None,
                               annotate)
        attempted, failed = serve.counts(win)
        sample = serve.pick_sample(win, seed, k)
        ids, pos, tok, mask = serve.pack_sample(sample, traffic, k)
        ok, out, arg = serve.judge(cfg, seed, failed, ids, pos, tok, mask)
        secs = win.t_end - win.t_start
        row = {"seed": seed, "attempted": attempted, "failed": failed,
               "serve_tok_s": serve.tokens_in(win, win.t_start, win.t_end)
               / secs,
               "longest": max(len(r.prompt) + r.max_new for r in sample),
               "program": {"correct": ok, "checks": out},
               "argmax_agreement": float((arg == tok)[mask].mean())}
        if n < n_control:
            # the control need not decode: at each position of the same
            # prompts and tokens, the token the lower precision puts first,
            # judged as the window's tokens are
            _, arg_c = serve.served_gaps(cfg, seed, ids, pos, tok, mm=mm)
            ok_c, out_c, _ = serve.judge(cfg, seed, 0, ids, pos, arg_c, mask)
            row["control"] = {"correct": ok_c, "checks": out_c}
            row["control_argmax_agreement"] = float(
                (arg_c == arg)[mask].mean())
        emit(row)
        serve.clear_engine(eng, win)


def training(cfg, traffic, seeds, seconds, n_control, mm, say, emit):
    from perfbench.harness import train

    comp = M.Compiles()
    annotate = tracing.annotator(False)
    _, names, _ = train.pieces(cfg)
    for n, seed in enumerate(seeds):
        model, _ = model_mod.build(cfg, seed, say)
        run_ = train.run(cfg, model, traffic, seed, seconds, None, annotate,
                         comp, say)
        del model
        gc.collect()
        ref, w0 = train.reference_readings(cfg, traffic, seed,
                                           run_.first_batches)
        prog = (run_.first_losses, run_.g1_norms, run_.g2_norms,
                train.update_norms(cfg, run_.after3, w0))
        run_.after3 = None
        ok, out = train.compare(cfg, names, prog, ref)
        row = {"seed": seed, "steps": len(run_.losses),
               "program": {"correct": ok, "checks": out},
               "loss_rel_each": (np.abs(np.asarray(prog[0]) - ref[0])
                                 / np.abs(ref[0])).tolist(),
               "median_grad2_gap": float(np.median(train.norm_gap(
                   prog[2], ref[2]))),
               "median_update_gap": float(np.median(train.norm_gap(
                   prog[3], ref[3])))}
        if n < n_control:
            half = int(traffic["batch"]) // 2
            for tag, kw in (("control", {"mm": mm}),
                            ("fault_half_batch",
                             {"batch_fault": lambda ids: ids[:half]})):
                got, _ = train.reference_readings(
                    cfg, traffic, seed, run_.first_batches, w0=w0, **kw)
                ok_c, o = train.compare(cfg, names, got, ref)
                row[tag] = {"correct": ok_c, "checks": o}
        emit(row)
        del w0
        gc.collect()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=3)
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
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"control.{a.workload}.jsonl")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")

    seeds = [int(s) for s in a.seeds.split(",")]
    mm = precision.mm_fp8
    fn = training if traffic["kind"] == "train" else serving
    fn(cfg, traffic, seeds, a.seconds, a.control, mm, say, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
