#!/usr/bin/env python3
"""Find the knee of an open-loop mix once, on the chip (the benchmark never
searches for a rate: the cell's rate is a number in its traffic file).

    python3 perfbench/tools/sweep.py --config qwen2-7b-d8 --traffic <mix> \
        --rates 4,5,6,7,8 --seconds 30 --seed 777

One process, one engine; for each rate one open-loop window of the traffic
file with ``rate_rps`` replaced. Per rate one JSON line: offered and
completed requests/s, tokens/s, TTFT p50/p95, gap p99, and the backlog when
arrivals stopped. The knee is the highest rate whose backlog does not grow:
completed/s keeps up with offered/s and the queue wait stays near a turn.
Writes ``chiprun_out/sweep.<traffic>.jsonl``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import main as M  # noqa: E402
from perfbench.harness import model as model_mod  # noqa: E402
from perfbench.harness import serve, spec, stats, tracing  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=777)
    a = ap.parse_args()
    t0 = time.perf_counter()
    bench = spec.load_benchmark(ROOT)
    cfg = spec.load_config(ROOT, bench, a.config)
    traffic = spec.load_traffic(a.traffic)
    M.find_devices(1)
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    say = M.make_say(t0)
    model, _ = model_mod.build(cfg, a.seed, say)
    model.eval()
    eng = serve.build_engine(cfg, model)
    serve.warm_up(eng, cfg, traffic, say)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"sweep.{a.traffic}.jsonl")
    for rate in [float(r) for r in a.rates.split(",")]:
        tr = dict(traffic, rate_rps=rate, drain_grace_s=0.0)
        win = serve.run_window(eng, cfg, tr, a.seed, a.seconds, None,
                               tracing.annotator(False))
        span = win.t_close - win.t_start
        done = [r for r in win.recs if r.done and r.error is None]
        first = [r for r in win.recs if r.times]
        row = {"rate_rps": rate, "seconds": round(span, 2),
               "offered": len(win.recs),
               "offered_rps": len(win.recs) / span,
               "completed_rps": len(done) / span,
               "tokens_per_s": serve.tokens_in(win, win.t_start, win.t_close)
               / span,
               "ttft_p50_ms": 1e3 * stats.percentile(serve.ttfts(win), 50),
               "ttft_p95_ms": 1e3 * stats.percentile(serve.ttfts(win), 95),
               "itl_p99_ms": 1e3 * stats.percentile(serve.gaps(win), 99),
               "queue_wait_p95_ms": 1e3 * stats.percentile(
                   serve.queue_waits(win), 95),
               "turn_ms_p50": 1e3 * stats.percentile(
                   [b - x for x, b in win.turns], 50),
               "no_first_token_at_close": len(win.recs) - len(first),
               "queued_at_close": win.gauges["queue_depth"],
               "late_ms_max": 1e3 * max(win.late, default=0.0)}
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
        serve.clear_engine(eng, win)
    return 0


if __name__ == "__main__":
    sys.exit(main())
