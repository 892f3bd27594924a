#!/usr/bin/env python3
"""The flash kernel ALONE, at a training cell's attention shapes.

    python3 tools/flash_bench.py \\
        --config perfbench/configs/mellum2-ep4-d4.json \\
        --traffic perfbench/traffic/code_pretrain_8k.json   (chip only)

For each kind of attention layer the configuration has (each window, and
the layer without one) at the traffic's ``batch x seq``, it times one
forward and one backward of ``flash_attention`` (query heads over the
file's K/V heads, head size and bf16 as the cell runs them) and prints,
per kernel (``flash_attention_fwd``, ``_dq``, ``_dkv``): milliseconds a
call (mean device time of the kernel's events in a profiler trace), the
blocks the shape resolved to, the (q block, K/V block) pairs one head
visits by kind — counted here from the explicit mask, not from the
kernel's schedule — microseconds per visible pair over all heads, and
the time the pair's matmuls take at the chip's peak (forward 2, dq 3,
dkv 4). ``--gpt2`` adds GPT-2's training shape (4 x 1024, 12 heads of 64,
causal) through the resident programs, which it takes, and through the
blocked ones, steered here alone.

``--blocks Q,K`` (repeatable) times the same calls with the blocks pinned
(``force_blocks``) beside the default: the sweep a change of the blocked
programs' blocks is read from. ``--root DIR`` imports ``paddle_tpu`` from
another checkout (the parent's, unpacked beside), and ``--against FILE``
prints the ``--out`` of such a run beside each line, so two trees are
timed by one tool on one chip, one process each.
"""
import argparse
import contextlib
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ("fwd", "dq", "dkv")
MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}     # per visible pair


def layer_kinds(cfg):
    """The distinct windows of the file's attention layers (None: the
    layer that sees every earlier key)."""
    kinds = set()
    for t in cfg.get("layer_types") or ["full_attention"]:
        kinds.add(int(cfg["sliding_window"]) if t == "sliding_attention"
                  else None)
    return sorted(kinds, key=lambda w: w or 1 << 62)


def pair_counts(sq, sk, block_q, block_k, window):
    """(full, cut) pairs of one head, from the explicit bottom-right
    causal mask over the padded blocks (a padded row or key is masked)."""
    import numpy as np
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    k = np.arange(nk * block_k)
    full = cut = 0
    for i in range(nq):
        q = np.arange(i * block_q, (i + 1) * block_q)[:, None]
        m = (q < sq) & (k < sk) & (k <= q + sk - sq)
        if window is not None:
            m &= k > q + sk - sq - window
        m = m.reshape(block_q, nk, block_k)
        seen, every = m.any((0, 2)), m.all((0, 2))
        full += int(every.sum())
        cut += int((seen & ~every).sum())
    return full, cut


def kernel_ms(fn, args, calls, trace_dir):
    """Mean device milliseconds of each flash kernel's events over
    ``calls`` calls, from a profiler trace; and the events counted."""
    import jax
    from perfbench.harness import xplane
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    tr = xplane.load(xplane.find_xplane(trace_dir))
    durs = {k: [] for k in KERNELS}
    pat = re.compile(r"^%?(\w*_)?flash_attention_(fwd|dq|dkv)")
    for e in tr.device_ops.get(min(tr.device_ops, default=0), []):
        m = pat.search(e.name)
        if m:
            durs[m.group(2)].append((e.end - e.start) * 1e3)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {k: (sum(v) / len(v) if v else None, len(v))
            for k, v in durs.items()}


def fwd_bwd(window):
    """One forward and backward of ``flash_attention``, a new function at
    each call: ``jax.jit`` keeps its traces by function, and the blocks a
    variant pins are read while tracing."""
    import jax
    from paddle_tpu.ops.pallas import flash_attention as fa

    def run(q, k, v, g):
        out, vjp = jax.vjp(lambda *x: fa.flash_attention(
            *x, True, None, window), q, k, v)
        return out, vjp(g)
    return run


def cases(cfg, traffic, gpt2):
    """(name, b, s, h, kvh, d, window, blocked) of every shape timed."""
    b, s = int(traffic["batch"]), int(traffic["seq"])
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // h
    out = [(f"{cfg['name']}.{'w%d' % w if w else 'full'}", b, s, h, kvh, d,
            w, None) for w in layer_kinds(cfg)]
    if gpt2:
        out += [("gpt2.resident", 4, 1024, 12, 12, 64, None, False),
                ("gpt2.blocked", 4, 1024, 12, 12, 64, None, True)]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config",
                    default="perfbench/configs/mellum2-ep4-d4.json")
    ap.add_argument("--traffic",
                    default="perfbench/traffic/code_pretrain_8k.json")
    ap.add_argument("--root", default=ROOT,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--blocks", action="append", default=[],
                    metavar="Q,K", help="also time with these blocks pinned")
    ap.add_argument("--gpt2", action="store_true",
                    help="also GPT-2's shape, resident and blocked")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--against", help="an --out of another tree, printed "
                                      "beside each line")
    ap.add_argument("--out", help="also write the lines as JSON here")
    a = ap.parse_args(argv)
    sys.path[:0] = [os.path.abspath(a.root), ROOT]
    with open(a.config) as f:
        cfg = json.load(f)
    with open(a.traffic) as f:
        traffic = json.load(f)
    before = {}
    if a.against:
        with open(a.against) as f:
            for line in json.load(f)["lines"]:
                before[(line["case"], line["variant"], line["kernel"])] = line

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.profiler.cost import device_peaks
    dev = jax.devices()[0]
    peaks = device_peaks(dev)       # an unknown device (the CPU) raises
    variants = [("default", None)] + [
        (v, tuple(int(x) for x in v.split(","))) for v in a.blocks]
    trace_dir = os.path.join(ROOT, "profiler_log", ".flash_bench_trace")
    lines = []
    for name, b, s, h, kvh, d, window, blocked in cases(cfg, traffic,
                                                         a.gpt2):
        key = jax.random.split(jax.random.PRNGKey(a.seed), 4)
        q = jax.random.normal(key[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(key[1], (b, s, kvh, d), jnp.bfloat16)
        v = jax.random.normal(key[2], (b, s, kvh, d), jnp.bfloat16)
        g = jax.random.normal(key[3], (b, s, h, d), jnp.bfloat16)

        takes = fa._takes_blocked
        for variant, forced in variants:
            if blocked is False and forced is not None:
                continue                # the resident programs: no walk
            pinned = contextlib.nullcontext() if forced is None \
                else fa.force_blocks(*forced)
            try:
                if blocked:
                    fa._takes_blocked = lambda *x: True
                with pinned:            # the first call traces: inside it
                    bq, bk = fa._blocked_blocks(s, s, d, q.dtype)
                    t0 = time.perf_counter()
                    ms = kernel_ms(jax.jit(fwd_bwd(window)), (q, k, v, g),
                                   a.calls, trace_dir)
                    wall = time.perf_counter() - t0
                err = None
            except Exception as e:  # noqa: BLE001 — blocks the compiler
                # refuses are a finding of the sweep, not its end
                ms, wall, err = {}, None, f"{type(e).__name__}: {e}"[:300]
            finally:
                fa._takes_blocked = takes
            walks = blocked is not False and (
                blocked or fa._takes_blocked(s, s, d, 2, window))
            full, cut = pair_counts(s, s, bq, bk, window) if walks \
                else (None, None)
            for kern in KERNELS:
                t, n = ms.get(kern, (None, 0))
                line = dict(case=name, variant=variant, kernel=kern,
                            shape=[b, s, h, kvh, d], window=window,
                            root=os.path.relpath(a.root, ROOT),
                            blocks=[bq, bk] if walks else None,
                            pairs_full=full, pairs_cut=cut,
                            ms=t and round(t, 5), events=n,
                            wall_s=wall and round(wall, 2))
                if walks and t:
                    pairs = (full + cut) * b * h
                    line["us_per_pair"] = round(1e3 * t / pairs, 4)
                    line["mxu_us_per_pair"] = round(
                        1e6 * MATMULS[kern] * 2 * bq * bk * d / peaks.flops,
                        4)
                if err:
                    line["error"] = err
                was = before.get((name, variant, kern))
                if was:
                    line["before"] = {x: was.get(x) for x in (
                        "ms", "us_per_pair", "blocks", "root")}
                lines.append(line)
                print("[flash_bench]", json.dumps(line), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "lines": lines}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
