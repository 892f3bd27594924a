#!/usr/bin/env python3
"""The LM loss tail ALONE: head matmul + cross-entropy, forward and backward.

    python3 tools/ce_tail_bench.py [--batch 4 --seq 1024 \\
        --hidden 768 --vocab 50257 --layout vd] [--tiles 1024x1024,...] \\
        [--check]                                            (TPU only)

Times one step of the tail at a training cell's shape — the loss and the
gradients of the hidden state and of the head — three ways, each one jitted
function of (hidden, weight, labels):

- ``plain``: the tail over materialized logits, as a labeled forward ran
  it before the fused tail: bf16 logits ``[B, S, V]`` from AMP's casts,
  sliced ``[:, :-1]``, an f32 ``log_softmax`` cross-entropy; ``jax.grad``
  for the backward.
- ``fused`` (per tile): the fused forward kernel
  (``ops/pallas/linear_ce.py``), then the backward kernel that writes
  ``dlogits`` once in bf16 and XLA's two matmuls for ``dh`` and ``dW``.
- ``library``: ``ops.fused_ce.lm_head_loss``'s jax core under ``jax.grad``,
  at the tiles ``_resolve_tiles`` gives the shape: what the model runs.

For each it prints milliseconds a step by the host's clock over ``--calls``
back-to-back calls and the device's busy milliseconds a step from a
profiler trace of them (the union of device-op intervals), the forward
alone the same way, and the five ops with the most device time. ``--check``
adds each fused variant's error against the jnp scan of ``ops/fused_ce.py``
on the same bf16 operands (f32 accumulation): loss, ``dh`` and ``dW``, as
max |got - want| / max |want|. The weight is f32 (a master weight, as the
training cell holds it) in ``--layout vd`` (``[V, D]``, a tied embedding)
or ``dv``. One JSON line a variant goes to ``chiprun_out/ce_tail_bench.jsonl``.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IGNORE = -100


def device_ms(fn, args, calls, trace_dir):
    """(host ms a call, device busy ms a call, top ops) over ``calls``
    back-to-back calls, after three warm ones."""
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
    evs = tr.device_ops.get(min(tr.device_ops, default=0), [])
    shutil.rmtree(trace_dir, ignore_errors=True)
    busy = xplane.busy_seconds(evs) * 1e3 / calls
    by = {}
    for name, s in xplane.self_times(evs).items():
        k = xplane.short_name(name)
        by[k] = by.get(k, 0.0) + s * 1e3 / calls
    top = sorted(by.items(), key=lambda kv: -kv[1])[:5]
    return wall, busy, [(k, round(v, 3)) for k, v in top]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--vocab", type=int, default=50257)
    ap.add_argument("--layout", choices=("vd", "dv"), default="vd")
    ap.add_argument("--tiles", default="1024x1024",
                    help="row x vocab tiles of the fused variants, "
                         "comma-separated")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.default_backend() != "tpu":
        print("ce_tail_bench: times the TPU kernels; no TPU here",
              file=sys.stderr)
        return 2
    from paddle_tpu.ops import fused_ce
    from paddle_tpu.ops.pallas import linear_ce as K

    vf = a.layout == "vd"
    b, s, d, v = a.batch, a.seq, a.hidden, a.vocab
    n = b * s
    k1, k2, k3 = jax.random.split(jax.random.key(a.seed), 3)
    hid = jax.random.normal(k1, (b, s, d), jnp.float32)
    w = 0.02 * jax.random.normal(k2, (v, d) if vf else (d, v), jnp.float32)
    ids = jax.random.randint(k3, (b, s), 0, v, jnp.int32)

    def rows(hid, ids):
        """The fused tail's rows: every position, the last of each
        sequence ignored (ops.fused_ce.lm_head_loss)."""
        nxt = jnp.concatenate([ids[:, 1:], jnp.full((b, 1), IGNORE,
                                                    ids.dtype)], 1)
        return hid.reshape(n, d).astype(jnp.bfloat16), nxt.reshape(-1)

    def plain(hid, w, ids):
        def loss(hid, w):
            wb = w.astype(jnp.bfloat16)
            logits = jnp.einsum("bsd,vd->bsv" if vf else "bsd,dv->bsv",
                                hid.astype(jnp.bfloat16), wb)
            lp = jax.nn.log_softmax(
                logits[:, :-1].reshape(-1, v).astype(jnp.float32), -1)
            lab = ids[:, 1:].reshape(-1)
            return -jnp.mean(jnp.take_along_axis(lp, lab[:, None], -1))
        return jax.value_and_grad(loss, argnums=(0, 1))(hid, w)

    def fused(tm, tv, with_bwd=True):
        def step(hid, w, ids):
            h, lab = rows(hid, ids)
            valid = lab != IGNORE
            target = jnp.where(valid, lab, -1)
            lse, tgt = K._fwd_call(h, w, target, vocab_first=vf, tm=tm,
                                   tv=tv)
            count = jnp.sum(valid.astype(jnp.float32))
            loss = jnp.sum(jnp.where(valid, lse - tgt, 0.0)) / count
            if not with_bwd:
                return loss
            scale = jnp.where(valid, 1.0 / count, 0.0)
            dh, dw = K._dlogits_call(h, w, target, lse, scale,
                                     vocab_first=vf, tm=tm, tv=tv)
            return loss, (dh.astype(jnp.float32).reshape(b, s, d), dw)
        return step

    def library(hid, w, ids):
        def loss(hid, w):
            h, lab = rows(hid, ids)
            return fused_ce.fused_linear_cross_entropy(h, w, lab, IGNORE, vf)
        return jax.value_and_grad(loss, argnums=(0, 1))(hid, w)

    def oracle():
        h, lab = rows(hid, ids)
        target = jnp.where(lab != IGNORE, lab, -1)
        lse, tgt = fused_ce._scan_fwd(h, w, target, vf)
        valid = lab != IGNORE
        count = jnp.sum(valid.astype(jnp.float32))
        loss = jnp.sum(jnp.where(valid, lse - tgt, 0.0)) / count
        dh, dw = fused_ce._scan_bwd(h, w, target, lse,
                                    jnp.where(valid, 1.0 / count, 0.0), vf)
        return loss, dh.astype(jnp.float32).reshape(b, s, d), dw

    want = jax.jit(oracle)() if a.check else None

    def rel(got, ref):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))

    cases = [("plain", None, jax.jit(plain), None)]
    for t in a.tiles.split(","):
        tm, tv = (int(x) for x in t.split("x"))
        cases.append(("fused", (tm, tv), jax.jit(fused(tm, tv)),
                      jax.jit(fused(tm, tv, with_bwd=False))))
    cases.append(("library", K._resolve_tiles(n, v), jax.jit(library), None))

    trace_dir = os.path.join(ROOT, "chiprun_out", ".ce_tail_trace")
    out_path = os.path.join(ROOT, "chiprun_out", "ce_tail_bench.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    print(f"[shape] N={n} (batch {b} x seq {s}) D={d} V={v} layout={a.layout}"
          f" device={jax.devices()[0].device_kind}", flush=True)
    for name, tiles, fn, fwd in cases:
        rec = {"variant": name, "tiles": tiles, "n": n, "d": d, "v": v,
               "layout": a.layout}
        try:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(hid, w, ids))
            rec["first_call_s"] = round(time.perf_counter() - t0, 2)
            wall, busy, top = device_ms(fn, (hid, w, ids), a.calls,
                                        trace_dir)
            rec.update(step_ms=round(wall, 3), step_busy_ms=round(busy, 3),
                       top=top)
            if fwd is not None:
                wall_f, busy_f, _ = device_ms(fwd, (hid, w, ids), a.calls,
                                              trace_dir)
                rec.update(fwd_ms=round(wall_f, 3),
                           fwd_busy_ms=round(busy_f, 3))
            if want is not None and name != "plain":
                loss, (dh, dw) = fn(hid, w, ids)
                rec.update(err_loss=rel(loss, want[0]),
                           err_dh=rel(dh, want[1]), err_dw=rel(dw, want[2]))
        except Exception as e:  # noqa: BLE001 — one variant, reported
            rec["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(rec), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
