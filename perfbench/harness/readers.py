"""The general readers behind ``perfbench/metrics/<name>.json``. Each takes
the metric's own file (``spec``) and what the run left (``ctx``), and
returns a number, or None where it finds nothing to read — the harness then
leaves the metric out of the line. None is never replaced by 0.

ctx keys: kind ('serve'|'train'), cfg, traffic, peaks, chips, and for
serving ``win`` (serve.Window) and ``chunk``; for training ``run``
(train.TrainRun); in a traced run ``trace`` (xplane.Trace) and ``reduced``
(xplane.reduce's dict) and ``span`` (host-clock span of the trace).
"""

from __future__ import annotations

from . import flops, serve, stats, xplane


def read_gauge(spec, ctx):
    """A count the program keeps at a layer boundary (``engine.gauges()``)."""
    g = ctx["win"].gauges if ctx["kind"] == "serve" else None
    if not g or spec["gauge"] not in g:
        return None
    return float(g[spec["gauge"]]) * float(spec.get("scale", 1.0))


def read_harness_stat(spec, ctx):
    """A percentile of something the harness stamped itself."""
    win = ctx["win"]
    vals = {"queue_wait_ms": lambda: [w * 1e3 for w in serve.queue_waits(win)],
            "turn_ms": lambda: [(b - a) * 1e3 for a, b in win.turns],
            }[spec["stat"]]()
    return stats.percentile(vals, float(spec["percentile"]))


def _cache_served(win):
    """True where the prefix cache served prompt tokens: ``work_items``
    would count them as computed, so no FLOP reader reads such a window."""
    return bool(win.gauges.get("prefix_cache_hits"))


def read_serve_mfu(spec, ctx):
    """Model FLOPs of every prompt and output token the window processed,
    over the window's seconds and the chip's published peak."""
    win = ctx["win"]
    if _cache_served(win):
        return None
    spans, sampled, _ = serve.work_items(win, ctx["chunk"], win.t_start,
                                         win.t_end)
    f = flops.serve_flops(ctx["cfg"]["sizes"], spans, sampled)
    secs = win.t_end - win.t_start
    if secs <= 0 or not sampled:
        return None
    return 100.0 * f / secs / (ctx["peaks"].flops * ctx["chips"])


def read_train_mfu(spec, ctx):
    run, tr = ctx["run"], ctx["traffic"]
    secs = run.t_end - run.t_start
    if secs <= 0 or not run.step_ends:
        return None
    toks = len(run.step_ends) * tr["batch"] * tr["seq"]
    per = flops.train_flops_per_token(ctx["cfg"]["sizes"], tr["seq"])
    return 100.0 * per * toks / secs / (ctx["peaks"].flops * ctx["chips"])


def read_idle_share(spec, ctx):
    r = ctx.get("reduced")
    if not r or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def read_device_ms_per_step(spec, ctx):
    """Device busy time per step span of the traced window: the part of a
    step the host's speed does not touch."""
    r = ctx.get("reduced")
    if not r or not r.get("steps") or r["busy_s"] <= 0:
        return None
    return 1e3 * r["busy_s"] / r["steps"]


def _kernel_seconds(spec, ctx):
    tr, r = ctx.get("trace"), ctx.get("reduced")
    if tr is None or r is None:
        return None, 0
    t0, t1 = r["window"]
    total = n = 0
    for evs in tr.device_ops.values():
        s, k = xplane.matching_seconds(xplane.clip(evs, t0, t1),
                                       spec["pattern"])
        total, n = total + s, n + k
    return total / max(len(tr.device_ops), 1), n


def read_kernel_roofline(spec, ctx):
    """max(FLOPs / peak, bytes / peak bandwidth) of the work the traced
    steps needed from this kernel, over the kernel's summed device time."""
    secs, n = _kernel_seconds(spec, ctx)
    if not secs or not n:
        return None
    m, r = ctx["cfg"]["sizes"], ctx["reduced"]
    if spec["work"] == "ragged_attention":
        win = ctx["win"]
        if _cache_served(win):
            return None
        t0, t1 = ctx["span"]
        # whole steps inside the traced span, on the host's clock
        inside = [(a, b) for a, b in win.turns if a >= t0 and b <= t1]
        # the turns the device window counts: the reducer leaves the
        # settling ones out (xplane.window_of)
        inside = inside[len(inside) - r["steps"]:] if r["steps"] else []
        if not inside:
            return None
        _, _, calls = serve.work_items(win, ctx["chunk"], inside[0][0],
                                       inside[-1][1])
        f, b = flops.ragged_attention_work(
            m, calls, kv_bytes=ctx["cfg"]["kv_bytes"])
    elif spec["work"] == "flash_attention":
        tr = ctx["traffic"]
        f, b = flops.flash_attention_work(m, tr["batch"], tr["seq"])
        f, b = f * r["steps"], b * r["steps"]
    else:
        raise ValueError(f"unknown work function {spec['work']!r}")
    least, bound = flops.roofline_seconds(f, b, ctx["peaks"])
    ctx.setdefault("notes", {})[spec.get("note", spec["pattern"])] = {
        "bound": bound, "kernel_s": secs, "events": n,
        "flops": f, "bytes": b}
    return 100.0 * least / secs
