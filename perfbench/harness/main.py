"""One run of one cell: set-up, the measured window, the check, the line."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from . import peaks as peaks_mod
from . import spec, stats, tracing


class Compiles:
    """Counts the programs jax builds (a compile, or a fetch from the
    persistent cache: both fire the backend-compile event)."""

    _PROGRAM = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring
        self.programs = self.hit = 0
        self.seconds = 0.0
        self._at = (0, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._PROGRAM:
            self.programs += 1
            self.seconds += duration
        elif event == self._HIT:
            self.hit += 1

    def mark(self):
        prev, self._at = self._at, (self.programs, self.seconds)
        return self.programs - prev[0], round(self.seconds - prev[1], 1)


def make_say(t0):
    def say(phase, **kv):
        body = " ".join(f"{k}={v}" for k, v in kv.items())
        print(f"[{phase} +{time.perf_counter() - t0:.1f}s] {body}", flush=True)
    return say


def find_devices(chips):
    """The chips the cell asks for, or exit: there is no CPU branch."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"perfbench: needs a TPU, jax found {devs[0].platform!r}; "
              f"there is no CPU mode", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"perfbench: the cell asks for {chips} chip(s), jax sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def memory_now(devs):
    s = devs[0].memory_stats() or {}
    return {"in_use_gb": round(s.get("bytes_in_use", 0) / 1e9, 2),
            "peak_gb": round(s.get("peak_bytes_in_use", 0) / 1e9, 2)}


def memory_peak(devs):
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(bench, cell, cfg, traffic, seed, seconds, trace, t0, root, devs,
             say):
    """Everything after the device has been found. Returns the result dict.
    The CPU rehearsal in the tests calls this with a tiny configuration."""
    import jax
    from paddle_tpu.framework.compile_cache import ensure_compile_cache

    from . import model as model_mod

    say("start", cell=cell["name"], seed=seed, seconds=seconds, trace=trace,
        compile_cache=ensure_compile_cache(), reduced=cfg.get("reduced"))
    pk = peaks_mod.peaks_for(devs[0].device_kind)
    comp = Compiles()
    tracer = tracing.Tracer(os.path.join(root, "perfbench", ".trace")) \
        if trace else None
    annotate = tracing.annotator(bool(trace))
    kind = "train" if traffic["kind"] == "train" else "serve"
    model, _ = model_mod.build(cfg, seed, say)
    say("memory", after="model", **memory_now(devs))
    ctx = {"kind": kind, "cfg": cfg, "traffic": traffic, "peaks": pk,
           "chips": len(devs)}
    e2e = {}

    if kind == "serve":
        from . import serve
        model.eval()
        eng = serve.build_engine(cfg, model)
        serve.warm_up(eng, cfg, traffic, say)
        say("setup", programs=comp.mark(), cache_hits=comp.hit,
            **memory_now(devs))
        setup_s = time.perf_counter() - t0
        win = serve.run_window(eng, cfg, traffic, seed, seconds, tracer,
                               annotate)
        in_window = comp.mark()[0]
        attempted, failed = serve.counts(win)
        secs = win.t_end - win.t_start
        n_tok = serve.tokens_in(win, win.t_start, win.t_end)
        gaps = serve.gaps(win)
        e2e = {"serve_tok_s": n_tok / secs,
               "ttft_p95_ms": 1e3 * stats.percentile(serve.ttfts(win), 95),
               "itl_p99_ms": (1e3 * stats.percentile(gaps, 99)
                              if gaps else None)}
        ctx.update(win=win, chunk=eng.prefill_chunk)
        g = win.gauges
        say("window", seconds=round(secs, 3), requests=attempted,
            failed=failed, tokens=n_tok, turns=len(win.turns),
            programs_built_in_window=in_window,
            generator_late_ms_max=round(1e3 * max(win.late, default=0.0), 2),
            prefix_cache_hits=g["prefix_cache_hits"],
            preempt_evictions=g["preempt_evictions"],
            compiled_programs=g["compiled_programs"])
        peak = memory_peak(devs)
        del eng, model
        gc.collect()
        ok, checks = serve.check(cfg, traffic, seed, win, say)
    else:
        from . import train
        # set-up ends inside train.run, after the first three steps
        run_ = train.run(cfg, model, traffic, seed, seconds, tracer, annotate,
                         comp, say)
        setup_s = run_.t_start - t0
        secs = run_.t_end - run_.t_start
        attempted = len(run_.losses)
        failed = sum(1 for x in run_.losses if x != x or abs(x) == float("inf"))
        toks = attempted * traffic["batch"] * traffic["seq"]
        e2e = {"train_tok_s": toks / secs}
        ctx.update(run=run_)
        # steps in each 5 s of the window: a disturbed host shows here as a
        # dip (the step is ~40% host work), a slower program as a level
        per5 = [0] * (int(secs // 5) + 1)
        for e in run_.step_ends:
            per5[int((e - run_.t_start) // 5)] += 1
        say("window", seconds=round(secs, 3), steps=attempted,
            steps_per_5s=per5,
            programs_built_in_window=run_.programs_in_window,
            loss_first=round(run_.losses[0], 4),
            loss_last=round(run_.losses[-1], 4))
        peak = memory_peak(devs)
        del model
        gc.collect()
        ok, checks = train.check(cfg, traffic, seed, run_, say)
        run_.after3 = None
    e2e["setup_s"] = setup_s

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": attempted, "failed": failed}
    metrics = {}
    if not trace:
        for mt in spec.cell_metrics(bench, cell, "end_to_end"):
            v = e2e.get(mt["name"])
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    else:
        from . import xplane
        t_r = time.perf_counter()
        tr = xplane.load(xplane.find_xplane(tracer.dir))
        red = xplane.reduce(tr)
        ctx.update(trace=tr, reduced=red, span=tracer.span)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        for mt in spec.cell_metrics(bench, cell, "per_layer"):
            rd = spec.load_metric_reader(mt["name"])
            v = spec.resolve_reader(rd)(rd, ctx)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        say("trace", read_s=round(time.perf_counter() - t_r, 1),
            busy_s=round(red["busy_s"], 4), window_s=round(red["window_s"], 4),
            steps=red["steps"], step_idle_pct=red["step_idle"],
            notes=json.dumps(ctx.get("notes", {})))
        tracer.discard()
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks           # last: each number beside its limit
    say("done", wall_s=round(time.perf_counter() - t0, 1),
        programs=comp.programs, cache_hits=comp.hit,
        compile_and_fetch_s=round(comp.seconds, 1))
    return result


def main(argv, t0, root):
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, args.workload)
    cfg = spec.load_config(root, bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    devs = find_devices(int(cell["chips"]))
    say = make_say(t0)
    say("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs))
    result = run_cell(bench, cell, cfg, traffic, args.seed, args.seconds,
                      args.trace, t0, root, devs, say)
    for name, c in result["checks"].items():
        print(f"perfbench check {name}: {json.dumps(c)}", file=sys.stderr)
    print(f"perfbench correct={result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0
