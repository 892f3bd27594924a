#!/usr/bin/env python3
"""The ragged paged-attention kernel ALONE, at a benchmark cell's shapes.

    chiprun -- python3 tools/ragged_kernel_bench.py \\
        --config perfbench/configs/qwen2-7b-d8.json \\
        --traffic perfbench/traffic/batch_closed.json      (chip only)

A cell's configuration file gives the heads, the pools (slots, ``max_len``,
page size, KV dtype) and the layers' windows; its traffic file gives the
lengths, so contexts are drawn as the cell's requests have them. For every
kind of attention layer the file has (no window; each window, over a slot's
ring of pages) and the two shapes the serving step calls the kernel at —
a decode micro-step ``[slots, 1, H, D]`` with a few idle slots, a prefill
group ``[8, 128, H, D]`` — it prints the blocks the shape resolved to
(``_resolve_blocks``), milliseconds a call (mean device time of the events
named ``ragged_paged_attention`` in a profiler trace: what the benchmark's
``kernel.ragged_attn_roofline.*`` divides by), the share of
``ragged_attention_cost``'s roofline that is, and with ``--check`` the
error against the jnp oracle over the same pools in f32.

``--force name=q_block,kv_pages[,kv_heads]`` times the same call with the
blocks pinned (``force_ragged_blocks``), beside the default: the sweep a
change to the kernel's defaults is read from. ``--root DIR`` imports
``paddle_tpu`` from another checkout (the parent's, unpacked beside), so
two trees are timed by one tool on one chip, one process each.

``--lower`` needs no chip: it lowers the same calls for a DESCRIBED v5e
(shapes only, nothing runs) and prints what a call site costs the HOST —
seconds to trace + lower a program of one call, of the same call again
(the kernel's trace is kept by its jitted function, so this is the
lowering alone) and of ``--sites`` calls with one signature, beside the
number of kernel bodies (``tpu_custom_call``) the lowered module holds.
A serving step's set-up pays this per kind of layer, twice (the eager
discovery turn and the compiled call; PERF.md section 6, PR 35). Host
seconds of THIS machine: compare trees, not machines.
"""
import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUP_ROWS, CHUNK = 8, 128      # the serving step's prefill group
IDLE_SLOTS = 4                  # of a decode micro-step (occupancy 94 %)


def layer_kinds(cfg):
    """The distinct windows of the file's attention layers (None: a layer
    that sees its whole history)."""
    wins = cfg.get("sliding_windows")
    if not wins:
        return [None]
    return sorted({int(w) or None for w in wins}, key=lambda w: w or 0)


def contexts(traffic, n, rng, chunk=None):
    """(ctx, lengths) of ``n`` slots in flight. Decode: a request drawn
    from the traffic's lengths, somewhere in its output. Group: a prompt
    drawn likewise, at one of its chunks."""
    from perfbench.harness import traffic as T
    pool = int(traffic["pool"])
    prompts = rng.permutation(T.lengths(traffic["prompt"], pool))[:n]
    outputs = rng.permutation(T.lengths(traffic["output"], pool))[:n]
    if chunk is None:
        ctx = prompts + (rng.random(n) * outputs).astype(int)
        return ctx, (rng.permutation(n) >= IDLE_SLOTS).astype(int)
    at = (rng.random(n) * -(-prompts // chunk)).astype(int) * chunk
    return at, (prompts - at).clip(1, chunk)


def case_dims(cfg, window, group):
    """The static sizes of one call: a prefill group or a decode
    micro-step of a layer with ``window`` (None: the long table over the
    global pool; else the same table over a slot's ring of pages)."""
    from paddle_tpu.inference.cache_spec import ring_pages
    eng = cfg["engine"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    slots, page = eng["num_slots"], eng["page_size"]
    pps = eng["max_len"] // page
    b, c = (GROUP_ROWS, CHUNK) if group else (slots, 1)
    ring = None if window is None \
        else min(pps, ring_pages(window, CHUNK, page))
    return dict(b=b, c=c, h=h, kvh=kvh, page=page, pps=pps, ring=ring,
                d=cfg.get("head_dim") or cfg["hidden_size"] // h,
                n_pages=slots * (ring or pps) + 1)


def build_case(cfg, traffic, window, group, rng, quant):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import paged_attention as PA
    z = case_dims(cfg, window, group)
    b, c, h, kvh, d, page, pps, ring, n_pages = (
        z[k] for k in ("b", "c", "h", "kvh", "d", "page", "pps", "ring",
                       "n_pages"))
    slots = cfg["engine"]["num_slots"]
    ctx, lens = contexts(traffic, b, rng, CHUNK if group else None)
    ctx = np.minimum(ctx, pps * page - c)
    if ring is None:
        tables = 1 + rng.permutation(slots * pps)[:b * pps].reshape(b, pps)
    else:                         # serving.py: page j is page j % R of a ring
        tables = 1 + ring * np.arange(b)[:, None] \
            + np.arange(pps)[None, :] % ring
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv, ks = jax.random.split(key, 4)
    shape = PA.kv_pool_shape(kvh, n_pages, page, d)
    kw = {}
    if quant:
        kp = jax.random.randint(kk, shape, -127, 128, jnp.int8)
        vp = jax.random.randint(kv, shape, -127, 128, jnp.int8)
        sc = jax.random.uniform(ks, PA.kv_scales_shape(kvh, n_pages, page),
                                jnp.float32, 0.002, 0.02)
        kw = {"k_scales": sc, "v_scales": sc * 1.5}
    else:
        kp = jax.random.normal(kk, shape, jnp.bfloat16)
        vp = jax.random.normal(kv, shape, jnp.bfloat16)
    q = jax.random.normal(kq, (b, c, h, d), jnp.bfloat16)
    args = (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(ctx, jnp.int32), jnp.asarray(lens, jnp.int32))
    # the keys a token sees, and the cached tokens a call has to read
    # (each sequence's visible span, once)
    seen = tokens = span = 0
    for cx, ln in zip(ctx.tolist(), lens.tolist()):
        for j in range(ln):
            seen += min(cx + j + 1, window or 1 << 30)
        if ln:
            span += min(cx + ln, (window or 1 << 30) + ln - 1)
        tokens += ln
    return args, kw, dict(tokens=tokens, avg_ctx=seen / max(tokens, 1),
                          kv_tokens=span)


def kernel_ms(fn, args, calls, trace_dir):
    """Mean device milliseconds of the kernel's events over ``calls``
    calls, from a profiler trace (None where the trace holds none)."""
    import jax
    from perfbench.harness import xplane
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / calls * 1e3
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(trace_dir))
    durs = [(e.end - e.start) * 1e3
            for e in tr.device_ops.get(min(tr.device_ops, default=0), [])
            if "ragged_paged_attention" in e.name]
    shutil.rmtree(trace_dir, ignore_errors=True)
    return (sum(durs) / len(durs) if durs else None), len(durs), wall


def oracle_error(got, args, kw, window, step=8):
    """max |kernel - oracle| / max |oracle|, the oracle on the same values
    in f32, ``step`` sequences at a time (it gathers whole tables)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import paged_attention as PA
    q, kp, vp, tables, ctx, lens = args
    if not kw:                      # a quantized pool is dequantized there
        kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
    ref = jax.jit(lambda q_, k, v, t, c, n, kw_:
                  PA.ragged_paged_attention_reference(
                      q_.astype(jnp.float32), k, v, t, c, n, window=window,
                      **kw_))
    worst = top = 0.0
    for i in range(0, q.shape[0], step):
        sl = slice(i, i + step)
        want = np.asarray(ref(q[sl], kp, vp, tables[sl], ctx[sl], lens[sl],
                              kw), np.float32)
        have = np.asarray(got[sl], np.float32)
        assert np.isfinite(have).all(), "the kernel's output is not finite"
        worst = max(worst, float(np.abs(have - want).max()))
        top = max(top, float(np.abs(want).max()))
    return worst / max(top, 1e-30)


def lower_costs(cfg, quant, shapes, sites, root):
    """``--lower``: trace + lower seconds of a call site, for a described
    v5e. Yields one line a layer kind and shape."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    rpa._interpret = lambda: False      # lower the kernel, not its interpreter
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def lowered(fn, args):
        t0 = time.perf_counter()
        text = jax.jit(fn).lower(*args).as_text()
        return time.perf_counter() - t0, text.count("@tpu_custom_call")

    for window in layer_kinds(cfg):
        for shape in shapes:
            z = case_dims(cfg, window, shape == "group")
            pool = sds(PA.kv_pool_shape(z["kvh"], z["n_pages"], z["page"],
                                        z["d"]),
                       jnp.int8 if quant else jnp.bfloat16)
            sc = sds(PA.kv_scales_shape(z["kvh"], z["n_pages"], z["page"]),
                     jnp.float32)
            vec = sds((z["b"],), jnp.int32)
            args = (sds((z["b"], z["c"], z["h"], z["d"]), jnp.bfloat16),
                    pool, pool, sds((z["b"], z["pps"]), jnp.int32), vec, vec)
            kw = {"k_scales": sc, "v_scales": sc} if quant else {}

            def call(n):
                # a fresh function each time: jax keeps a trace by function
                def fn(q, k, v, t, cx, ln, *scales):
                    for _ in range(n):
                        q = rpa.ragged_paged_attention(
                            q, k, v, t, cx, ln, window=window,
                            **dict(zip(kw, scales)))
                    return q
                return lowered(fn, args + tuple(kw.values()))

            first, again, many = call(1), call(1), call(sites)
            yield dict(config=cfg["name"], shape=shape, window=window,
                       quant=quant, root=os.path.relpath(root, ROOT),
                       first_s=round(first[0], 3), again_s=round(again[0], 3),
                       sites=sites, sites_s=round(many[0], 3),
                       kernel_bodies=many[1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", help="needed unless --lower")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--root", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--force", action="append", default=[],
                    metavar="NAME=QB,G[,HP]")
    ap.add_argument("--shapes", default="decode,group")
    ap.add_argument("--quant", action="store_true",
                    help="int8 pools with f32 scales pools")
    ap.add_argument("--check", action="store_true",
                    help="also the error against the jnp oracle")
    ap.add_argument("--out", help="also write the lines as JSON here")
    ap.add_argument("--lower", action="store_true",
                    help="no chip: trace + lower seconds a call site, "
                         "for a described v5e")
    ap.add_argument("--sites", type=int, default=4,
                    help="--lower: calls of one signature in a program")
    a = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(a.root), ROOT]
    with open(a.config) as f:
        cfg = json.load(f)
    if a.lower:
        for line in lower_costs(cfg, a.quant, a.shapes.split(","), a.sites,
                                a.root):
            print("[ragged_lower]", json.dumps(line), flush=True)
        return 0

    import jax
    import numpy as np
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    from paddle_tpu.profiler.cost import device_peaks
    dev = jax.devices()[0]
    peaks = device_peaks(dev)       # an unknown device (the CPU) raises
    if not a.traffic:
        ap.error("--traffic is needed to draw contexts")
    with open(a.traffic) as f:
        traffic = json.load(f)
    variants = [("default", None)] + [
        (n, tuple(int(x) for x in v.split(",")))
        for n, v in (s.split("=") for s in a.force)]
    trace_dir = os.path.join(ROOT, "chiprun_out", ".ragged_bench_trace")
    lines = []
    for window in layer_kinds(cfg):
        for shape in a.shapes.split(","):
            rng = np.random.default_rng([a.seed, window or 0,
                                         shape == "group"])
            args, kw, work = build_case(cfg, traffic, window,
                                        shape == "group", rng, a.quant)
            q, kp = args[0], args[1]
            cost = rpa.ragged_attention_cost(
                q.shape, kp.shape, work["avg_ctx"], work["tokens"],
                pool_dtype=kp.dtype, kv_tokens=work["kv_tokens"])
            floor_ms = max(cost.flops / peaks.flops,
                           cost.bytes / peaks.hbm_bw) * 1e3
            for name, forced in variants:
                fn = jax.jit(lambda *x: rpa.ragged_paged_attention(
                    *x, window=window, **kw))
                line = dict(
                    config=cfg["name"], shape=shape, window=window,
                    quant=a.quant, variant=name, q=list(q.shape),
                    pool=list(kp.shape), tokens=work["tokens"],
                    avg_ctx=round(work["avg_ctx"], 1),
                    floor_ms=round(floor_ms, 5),
                    bound="flops" if cost.flops / peaks.flops
                    > cost.bytes / peaks.hbm_bw else "bytes")
                try:
                    if forced is None:
                        pinned = contextlib.nullcontext()
                        line["blocks"] = list(rpa._resolve_blocks(
                            q.shape[1], args[3].shape[1], kp.shape[1],
                            q.shape[3], q.dtype, bool(kw),
                            kv_heads=kp.shape[2] // q.shape[3],
                            rep=q.shape[2] * q.shape[3] // kp.shape[2],
                            window=window, pool_dtype=kp.dtype))
                    else:
                        pinned = rpa.force_ragged_blocks(*forced)
                        line["blocks"] = list(forced)
                    with pinned:    # the first call traces: inside it
                        ms, n, wall = kernel_ms(fn, args, a.calls, trace_dir)
                        if a.check:
                            line["rel_err"] = oracle_error(
                                fn(*args), args, kw, window)
                    line.update(ms=ms and round(ms, 5), events=n,
                                wall_ms=round(wall, 4),
                                roofline_pct=ms and round(
                                    100 * floor_ms / ms, 2))
                except Exception as e:  # noqa: BLE001 — a variant the
                    # compiler refuses is a finding of the sweep, not its end
                    line["error"] = f"{type(e).__name__}: {e}"[:300]
                lines.append(line)
                print("[ragged_bench]", json.dumps(line), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "lines": lines}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
