#!/usr/bin/env python3
"""Prove on the chip that serving and training still start.

One process, one chip (``python chip_smoke.py``), through the entry
points a user calls — ``ContinuousBatchingEngine`` and a ``to_static``
training step — at the published widths of models the repo supports,
with seeded random weights. Every phase checks what comes out; the
first failed phase ends the run with a non-zero exit code (no phase is
caught and skipped). There is NO CPU mode: without a TPU the script
exits non-zero before any phase and prints no result line.

    python chip_smoke.py             one chip: device, kernels, serve, train
    python chip_smoke.py --chips 4   four chips: ONLY the mesh phase

Last stdout line (the only one the driver reads):
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Everything on earlier lines is a smoke OBSERVATION (compile seconds,
step times, peak bytes), not a benchmark number.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time

import numpy as np

_T0 = time.perf_counter()
SEED = 0          # weights, prompts and batches are made from it


def say(phase, **kv):
    """One observation line: ``[phase +seconds] key=value ...``."""
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase} +{time.perf_counter() - _T0:.0f}s] {body}", flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


# ---- compile accounting ---------------------------------------------------

class Compiles:
    """Counts the programs jax builds. Every program fires jax's
    backend-compile event, whether XLA compiled it (a persistent-cache
    MISS) or it was fetched from the cache (a HIT, which also fires the
    retrieval event). ``mark()`` returns (programs, seconds) since the
    previous mark."""

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

    @property
    def miss(self):
        return self.programs - self.hit

    def mark(self):
        prev, self._at = self._at, (self.programs, self.seconds)
        return self.programs - prev[0], round(self.seconds - prev[1], 1)


def memory(dev):
    s = dev.memory_stats() or {}
    return {"bytes_in_use": s.get("bytes_in_use"),
            "peak_bytes_in_use": s.get("peak_bytes_in_use")}


def ready(x):
    import jax
    return jax.block_until_ready(x)


def rel_err(got, want):
    """max|got - want| / max|want| in f32 — scale-relative, so one
    tolerance serves outputs and gradients of any magnitude."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
    check(np.isfinite(g).all(), "non-finite kernel output")
    return float(np.max(np.abs(g - w)) / max(float(np.max(np.abs(w))),
                                             1e-30))


# ---- phase: device + S8 ---------------------------------------------------

def phase_device(dev, n_chips):
    """Nothing asked onto the TPU can land elsewhere; and ROADMAP S8:
    does ``block_until_ready`` wait for the device, is anything
    replayed?"""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.device import TPUPlace
    from paddle_tpu.profiler.cost import device_peaks
    from paddle_tpu.tuner.cache import backend_signature

    peaks = device_peaks(dev)           # unknown kind raises: no default
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=n_chips, jax=jax.__version__,
        peak_table=f"{peaks.kind}:{peaks.flops / 1e12:.0f}TFLOP/s",
        tuner_backend=backend_signature())
    check(TPUPlace(0).jax_device().platform == "tpu",
          "TPUPlace(0) resolved off the TPU")
    t = paddle.to_tensor(np.ones((8, 128), np.float32))
    check(next(iter(t._data.devices())).platform == "tpu",
          "paddle.to_tensor landed off the TPU")

    # S8. A chain of n x n bf16 matmuls cannot finish faster than its
    # FLOPs at the published peak. If block_until_ready returned before
    # that bound it would not be waiting for the device; if a repeat on
    # IDENTICAL inputs beat the bound, something would be replaying.
    n, depth = 4096, 48
    bound_ms = 2.0 * n ** 3 * depth / peaks.flops * 1e3

    @jax.jit
    def chain(a, b):
        def body(x, _):
            return jnp.tanh(x @ b).astype(x.dtype), None
        return jax.lax.scan(body, a, None, length=depth)[0]

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.random.normal(k1, (n, n), jnp.bfloat16)
    a2 = jax.random.normal(k2, (n, n), jnp.bfloat16)
    b = jax.random.normal(k3, (n, n), jnp.bfloat16) * (1.0 / 64)
    ready(chain(a, b))                                  # compile + warm

    def timed(x):
        t0 = time.perf_counter()
        y = chain(x, b)
        t_dispatch = time.perf_counter() - t0
        ready(y)
        t_ready = time.perf_counter() - t0
        float(y[0, 0])                                  # scalar fetch
        return (t_dispatch * 1e3, t_ready * 1e3,
                (time.perf_counter() - t0) * 1e3)

    same = [timed(a) for _ in range(3)]                 # identical inputs
    fresh = timed(a2)                                   # new inputs
    ready_same = min(r for _, r, _ in same)
    honest = ready_same >= bound_ms and fresh[1] >= bound_ms
    fetch_extra = min(f - r for _, r, f in same + [fresh])
    say("device", S8_bound_ms=round(bound_ms, 2),
        dispatch_ms=round(min(d for d, _, _ in same), 2),
        ready_ms_identical_inputs=round(ready_same, 2),
        ready_ms_fresh_inputs=round(fresh[1], 2),
        scalar_fetch_after_ready_ms=round(fetch_extra, 2))
    say("device", S8_answer=(
        "block_until_ready returns only when the device is done "
        "(never under the FLOP bound; a scalar fetch after it adds "
        f"{fetch_extra:.2f} ms) and identical inputs are NOT replayed "
        "(repeat >= bound)" if honest else
        "block_until_ready or a repeat on identical inputs returned "
        "UNDER the FLOP bound — timings here cannot be trusted"))
    check(honest, "S8: a timing came in under the physical bound")


# ---- phase: kernel-vs-oracle parity on the chip ---------------------------

def _ragged_case(rng, b, c, h, kvh, d, page, pps, quant, ring=0):
    """Seeded pools + a mixed batch (full prefill chunk, continuing
    prefill, decode steps, an idle slot) for the ragged kernel. ``ring``:
    a window layer's pools — a slot's table cycles through its own
    ``ring`` pages, as the serving step builds it."""
    import jax.numpy as jnp
    n_pages = b * (ring or pps) + 1
    q = jnp.asarray(rng.randn(b, c, h, d), jnp.bfloat16)
    tables = jnp.asarray(
        1 + ring * np.arange(b)[:, None] + np.arange(pps)[None, :] % ring
        if ring else 1 + rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    max_ctx = pps * page - c
    plan = [(0, c), (min(c, max_ctx), max(c // 2, 1)), (max_ctx, 1),
            (0, 0), (17, 1), (min(2 * page, max_ctx), max(c // 3, 1)),
            (max_ctx // 2, 1), (5, min(c, 7))]
    plan = (plan * (b // len(plan) + 1))[:b]
    ctx = jnp.asarray([p[0] for p in plan], jnp.int32)
    lens = jnp.asarray([p[1] for p in plan], jnp.int32)
    from paddle_tpu.ops.paged_attention import kv_pool_shape, kv_scales_shape
    shape = kv_pool_shape(kvh, n_pages, page, d)
    if quant:
        sshape = kv_scales_shape(kvh, n_pages, page)
        kp = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.002, 0.02, sshape), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.002, 0.02, sshape), jnp.float32)
        return (q, kp, vp, tables, ctx, lens), {"k_scales": ks,
                                                "v_scales": vs}
    kp = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    vp = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    return (q, kp, vp, tables, ctx, lens), {}


def phase_kernels(serve, train, seed, tol=2.0 ** -6):
    """Every kernel the smoke's programs contain, against its jnp
    oracle, at the smoke's shapes, seeded data. Tolerance: max error
    <= 2^-6 of the reference's max magnitude (four bf16 ulps) for
    outputs AND gradients."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import sdpa_reference
    from paddle_tpu.ops import fused_ce
    from paddle_tpu.ops.pallas import linear_ce, rms_norm as RN
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    from paddle_tpu.ops.pallas.swiglu import swiglu_fused, swiglu_reference
    rng = np.random.RandomState(seed)
    errs = {}

    def f32(a):
        return a.astype(jnp.float32) if jnp.issubdtype(
            a.dtype, jnp.floating) else a

    def grads(fn, *args):
        def loss(*a):
            out = fn(*a)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            # a fixed non-uniform cotangent (a uniform one is blind to
            # most of a softmax's jacobian)
            return sum(jnp.sum(o.astype(jnp.float32) * jnp.cos(
                0.37 * jnp.arange(o.size, dtype=jnp.float32)
            ).reshape(o.shape)) for o in outs)
        return jax.jit(jax.grad(
            loss, argnums=tuple(range(len(args)))))(*args)

    def both(name, kern, oracle, *args):
        """kernel on the bf16 operands vs the jnp oracle on the same
        values in f32: outputs, then gradients."""
        wide = [f32(a) for a in args]
        out_k, out_o = jax.jit(kern)(*args), jax.jit(oracle)(*wide)
        outs_k = out_k if isinstance(out_k, (tuple, list)) else (out_k,)
        outs_o = out_o if isinstance(out_o, (tuple, list)) else (out_o,)
        e = [rel_err(a, b) for a, b in zip(outs_k, outs_o)]
        e += [rel_err(a, b) for a, b in zip(grads(kern, *args),
                                            grads(oracle, *wide))]
        errs[name] = max(e)

    # serving: the engine's mixed pass (C = prefill chunk) and its
    # in-program decode micro-steps (C = 1), plain and int8 pools — at
    # the smoke's own shape, then at the benchmark cells': a prefill
    # group of 8 rows and a decode step of 64 slots of every kind of
    # attention layer they run (``window``: over a slot's ring of pages)
    s = serve
    shapes = [("", s["slots"], s["chunk"], s["heads"], s["kv_heads"],
               s["pages_per_slot"], None)]
    shapes += [(f"_{name}_{tag}", b, c, h, kvh, pps, window)
               for name, (h, kvh, pps, window) in s["cells"].items()
               for tag, b, c in (("group", 8, s["chunk"]), ("decode", 64, 1))]
    from paddle_tpu.inference.cache_spec import ring_pages
    from tools.ragged_kernel_bench import oracle_error
    for tag, b, c0, h, kvh, pps, window in shapes:
        ring = window and ring_pages(window, s["chunk"], s["page"])
        for quant in (False, True):
            for c in (c0, 1) if not tag else (c0,):
                args, kw = _ragged_case(rng, b, c, h, kvh, s["head_dim"],
                                        s["page"], pps, quant, ring or 0)
                got = jax.jit(lambda *a, _kw=kw: ragged_paged_attention(
                    *a, window=window, **_kw))(*args)
                # against the oracle on the same values in f32 (it
                # gathers whole tables: 8 sequences at a time)
                errs[f"ragged{tag}{'_int8' if quant else ''}_c{c}"] = \
                    oracle_error(got, args, kw, window)

    # training: flash fwd+bwd at the GPT-2 and Qwen2 head shapes,
    # rms_norm(+residual), swiglu at the Qwen2 widths, the CE pair
    for name, (bsz, seq, h, kvh, d) in train["flash"].items():
        q = jnp.asarray(rng.randn(bsz, seq, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(bsz, seq, kvh, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(bsz, seq, kvh, d), jnp.bfloat16)
        both(f"flash_{name}",
             lambda a, b_, c_: flash_attention(a, b_, c_, True, None),
             lambda a, b_, c_: sdpa_reference(a, b_, c_, is_causal=True),
             q, k, v)
    rows, hid, inter = train["rows"], train["hidden"], train["inter"]
    x = jnp.asarray(rng.randn(rows, hid), jnp.bfloat16)
    r = jnp.asarray(rng.randn(rows, hid), jnp.bfloat16)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(hid), jnp.bfloat16)
    both("rms_norm", lambda a, ww: RN.rms_norm(a, ww, 1e-6),
         lambda a, ww: RN.rms_norm_reference(a, ww, 1e-6), x, w)
    both("rms_norm_residual",
         lambda a, b_, ww: RN.rms_norm_residual(a, b_, ww, 1e-6),
         lambda a, b_, ww: RN.rms_norm_residual_reference(a, b_, ww, 1e-6),
         x, r, w)
    g = jnp.asarray(rng.randn(rows, inter), jnp.bfloat16)
    u = jnp.asarray(rng.randn(rows, inter), jnp.bfloat16)
    both("swiglu", swiglu_fused, swiglu_reference, g, u)

    # the fused head + CE kernels against the jnp scan, on the same bf16
    # operands (f32 accumulation in both), the f32 weight in both layouts
    n, d, v = train["ce"]
    h = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    lab = jnp.asarray(rng.randint(-1, v, n), jnp.int32)
    scale = jnp.where(lab >= 0, 1.0 / n, 0.0).astype(jnp.float32)
    for vf in (True, False):
        w = jnp.asarray(0.02 * rng.randn(*((v, d) if vf else (d, v))),
                        jnp.float32)
        lse, tgt = linear_ce.lse_and_target(h, w, lab, vf)
        lse_r, tgt_r = fused_ce._scan_fwd(h, w, lab, vf)
        dh, dw = linear_ce.head_grads(h, w, lab, lse_r, scale, vf)
        dh_r, dw_r = fused_ce._scan_bwd(h, w, lab, lse_r, scale, vf)
        errs["linear_ce" + ("_vd" if vf else "_dv")] = max(
            rel_err(lse, lse_r), rel_err(tgt, tgt_r), rel_err(dh, dh_r),
            rel_err(dw, dw_r))

    say("kernels", tolerance=f"{tol:.4f}(rel-to-max)",
        **{k: f"{v:.2e}" for k, v in errs.items()})
    bad = {k: v for k, v in errs.items() if not v <= tol}
    check(not bad, f"kernel-vs-oracle parity over tolerance: {bad}")


# ---- phase: serve ----------------------------------------------------------

def _requests(rng, vocab, lengths, per_length, shared):
    """``per_length`` prompts of each length; within a length the LAST
    prompt shares its first ``shared[length]`` tokens with the first
    (a page-aligned common prefix for the prefix cache)."""
    out = []
    for n in lengths:
        group = [rng.randint(0, vocab, n).astype(np.int32)
                 for _ in range(per_length)]
        group[-1][:shared[n]] = group[0][:shared[n]]
        out += group
    return out


def _drive(eng, prompts, n_new, comp):
    """Submit everything, pump ``step()`` to completion; per-turn wall
    time ends in block_until_ready on the KV pools."""
    ids = [eng.add_request(p, n_new) for p in prompts]
    done, turns = [], []
    while eng.has_work():
        t0 = time.perf_counter()
        done += eng.step()
        ready([p._data for p in eng.pools])
        turns.append((time.perf_counter() - t0, comp.mark()[0]))
    by_id = {r.request_id: r for r in done}
    check(sorted(by_id) == sorted(ids), "a request never completed")
    for r in done:
        check(r.error is None and len(r.tokens) == n_new,
              f"request {r.request_id}: error={r.error!r} "
              f"tokens={len(r.tokens)}")
    return [by_id[i].tokens for i in ids], turns


def phase_serve(model_cfg, sizes, seed, dev, comp):
    """Serve a mixed batch through ContinuousBatchingEngine (defaults:
    unified step), check the tokens against the dense path on the same
    weights, then once more over int8 KV pools."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import Qwen2ForCausalLM

    paddle.seed(seed)
    t0 = time.perf_counter()
    model = Qwen2ForCausalLM(model_cfg)
    model.to(dtype="bfloat16")
    model.eval()
    ready([p._data for p in model.parameters()])
    n_params = sum(p.size for p in model.parameters())
    say("serve", params=f"{n_params / 1e9:.2f}B",
        weights_gb=f"{n_params * 2 / 1e9:.1f}",
        build_s=round(time.perf_counter() - t0, 1), **memory(dev))

    rng = np.random.RandomState(seed)
    n_new, lengths = sizes["n_new"], sizes["prompt_lens"]
    prompts = _requests(rng, model_cfg.vocab_size, lengths,
                        sizes["per_length"], sizes["shared"])
    eng_kw = dict(num_slots=sizes["slots"], max_len=sizes["max_len"],
                  greedy=True, audit=True)

    def serve(tag, **kw):
        comp.mark()
        eng = ContinuousBatchingEngine(model, **eng_kw, **kw)
        toks, turns = _drive(eng, prompts, n_new, comp)
        g = eng.gauges()
        warm = [t for t, built in turns if not built]
        check(warm, "no turn ran without building a program")
        check(not any(b for _, b in turns[2:]),
              f"{tag}: a program was built after turn 2: "
              f"{[b for _, b in turns]}")
        texts = eng._unified_static().program_texts()
        calls = sum(t.count("tpu_custom_call") for t in texts)
        check(texts and calls >= sizes["pallas_calls"],
              f"{tag}: no Pallas call (tpu_custom_call) in the engine's "
              f"step program")
        say(tag, requests=len(prompts), prompt_lens=lengths,
            prefill_chunk=eng.prefill_chunk, new_tokens=n_new,
            turns=len(turns),
            compile_s=round(sum(t for t, b in turns if b), 1),
            steady_turn_ms=round(float(np.median(warm)) * 1e3, 2),
            step_programs=g["compiled_programs"],
            pallas_calls_in_step=calls,
            prefix_hit_rate=round(g.get("prefix_cache_hit_rate", 0.0), 3),
            kv_bits=g.get("kv_quant_bits"), page_audit="clean",
            **memory(dev))
        check(g["compiled_programs"] == 1, "more than one step program")
        eng._audit_pages("chip_smoke end")       # raises on a leak
        del eng
        gc.collect()
        return toks

    got = serve("serve")

    # dense reference 1: free-running model.generate, one batch per
    # prompt length (same weights, dense KV cache, no paging)
    ref, at = [], 0
    comp.mark()
    for n in lengths:
        batch = np.stack(prompts[at:at + sizes["per_length"]])
        at += sizes["per_length"]
        out, _ = model.generate(
            paddle.to_tensor(batch.astype(np.int64)),
            max_new_tokens=n_new, decode_strategy="greedy_search",
            eos_token_id=None, pad_token_id=0)
        ref += np.asarray(out.numpy()).tolist()
    same = [next((i for i, (a, b) in enumerate(zip(g_, r_)) if a != b),
                 n_new) for g_, r_ in zip(got, ref)]
    agreement = sum(same) / (n_new * len(got))

    # dense reference 2: teacher-forced logits on the ENGINE's streams —
    # one right-padded causal forward. Random weights give near-flat
    # logits over a 152k vocabulary (top-2 gap ~0.25 at sigma ~1.2), so
    # two bf16 paths legitimately part at near-ties and never rejoin;
    # what must hold is that EVERY engine token is the dense argmax or
    # within `margin` of it (4 bf16 ulps at the logits' magnitude; the
    # first chip run measured 2). A wrong token sits ~5 below the max.
    def forced_rows(streams):
        """f32 dense logits [len(stream), V] per request, at the
        positions that predict the stream's tokens."""
        full = [np.concatenate([p, np.asarray(t, np.int32)])
                for p, t in zip(prompts, streams)]
        width = max(len(f) for f in full)
        ids = np.zeros((len(full), width), np.int64)
        for i, f in enumerate(full):
            ids[i, :len(f)] = f
        with paddle.no_grad():
            logits = model(paddle.to_tensor(ids))._data
        return [logits[i, len(p) - 1:len(p) - 1 + len(t)].astype(
            jnp.float32) for i, (p, t) in enumerate(zip(prompts, streams))]

    def gap(rows, toks):
        """max logit minus the chosen token's logit, per position."""
        chosen = jnp.take_along_axis(
            rows, jnp.asarray(toks, jnp.int32)[:, None], axis=1)[:, 0]
        return np.asarray(jnp.max(rows, -1) - chosen)

    margin = sizes["margin"]
    rows = forced_rows(got)
    gaps = np.concatenate([gap(r, t) for r, t in zip(rows, got)])
    # where a stream parts from dense generate, the prefix is common,
    # so the same logits judge generate's token too: both must be
    # near-ties of the dense max, else one of the two paths is wrong
    part = [float(gap(rows[i][t:t + 1], ref[i][t:t + 1])[0])
            for i, t in enumerate(same) if t < n_new]
    say("serve", vs_dense_generate_token_agreement=round(agreement, 3),
        matched_prefix_lens=same, teacher_forced_argmax_rate=round(
            float(np.mean(gaps == 0.0)), 3),
        max_gap_to_dense_max=round(float(gaps.max()), 4),
        max_gap_of_generate_token_where_streams_part=round(
            max(part, default=0.0), 4), margin=margin,
        reference_programs=comp.mark()[0])
    check(gaps.max() <= margin,
          f"an engine token is {gaps.max():.3f} below the dense max "
          f"(margin {margin}): not a bf16 near-tie")
    check(max(part, default=0.0) <= margin,
          "a stream parts from dense generate at a token that is not a "
          "near-tie")
    check(np.mean(gaps == 0.0) >= sizes["argmax_rate"],
          "too few engine tokens are the dense argmax")
    del rows

    got_q = serve("serve_int8kv", kv_quant="int8")
    gaps_q = np.concatenate(
        [gap(r, t) for r, t in zip(forced_rows(got_q), got_q)])
    say("serve_int8kv", vs_bf16_engine_token_agreement=round(
        float(np.mean([a == b for g_, q_ in zip(got, got_q)
                       for a, b in zip(g_, q_)])), 3),
        teacher_forced_argmax_rate=round(float(np.mean(gaps_q == 0.0)), 3),
        max_gap_to_dense_max=round(float(gaps_q.max()), 4),
        margin=sizes["margin_int8"])
    check(gaps_q.max() <= sizes["margin_int8"],
          "an int8-KV token is not near the dense max")
    del model
    gc.collect()


# ---- phase: train ----------------------------------------------------------

def _steps(step, batches, comp, tag, want_calls, dev, **note):
    import paddle_tpu as paddle
    losses, times, built = [], [], []
    comp.mark()
    for ids in batches:
        t0 = time.perf_counter()
        loss = step(paddle.to_tensor(ids))
        losses.append(float(loss.item()))
        ready(loss._data)
        times.append(time.perf_counter() - t0)
        built.append(comp.mark()[0])
    check(all(math.isfinite(x) for x in losses), f"{tag}: loss not finite")
    check(not any(built[2:]),
          f"{tag}: recompiled after step 2 (programs per step: {built})")
    check(step.n_eager_runs == 1 and
          step.n_compiled_runs == len(batches) - 1,
          f"{tag}: {step.n_eager_runs} eager runs (want the 1 discovery)")
    calls = sum(t.count("tpu_custom_call") for t in step.program_texts())
    check(calls >= want_calls,
          f"{tag}: {calls} Pallas calls in the step program, expected "
          f">= {want_calls}")
    say(tag, losses=[round(x, 4) for x in losses],
        compile_s=round(sum(times[:2]), 1),
        steady_step_ms=round(float(np.median(times[2:])) * 1e3, 2),
        programs_per_step=built, pallas_calls_in_step=calls,
        **note, **memory(dev))
    return losses


def phase_train(gpt_cfg, qwen_cfg, sizes, seed, dev, comp):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models import GPT2ForCausalLM, Qwen2ForCausalLM

    # (a) GPT-2 small, full size: AdamW + global-norm clip, bf16 AMP,
    # the whole step (fwd + loss + bwd + update) as ONE compiled program
    paddle.seed(seed)
    model = GPT2ForCausalLM(gpt_cfg)
    model.train()
    opt = paddle.optimizer.AdamW(
        learning_rate=sizes["gpt_lr"], parameters=model.parameters(),
        weight_decay=0.01, grad_clip=nn.ClipGradByGlobalNorm(1.0))

    @paddle.jit.to_static
    def gpt_step(ids):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(seed)
    b, s = sizes["gpt_batch"], sizes["gpt_seq"]
    # tokens drawn from 512 ids of the vocabulary: the loss must fall
    # from ~ln(vocab) as the model learns which ids occur at all
    used = min(gpt_cfg.vocab_size, 512)
    batches = [rng.randint(0, used, (b, s)).astype(np.int64)
               for _ in range(sizes["gpt_steps"])]
    n_params = sum(p.size for p in model.parameters())
    losses = _steps(gpt_step, batches, comp,
                    "train_gpt2", want_calls=sizes["gpt_calls"], dev=dev,
                    params=f"{n_params / 1e6:.0f}M", batch=b, seq=s)
    check(losses[-1] < losses[0] - sizes["gpt_drop"],
          f"train_gpt2: loss did not fall: {losses}")
    del model, opt, gpt_step
    gc.collect()

    # (b) Qwen2-7B widths, 2 layers, bf16, forward + backward only (no
    # optimizer state: it would not fit) — rms_norm, rms_norm_residual,
    # swiglu and the flash fwd/bwd kernels at h3584 / 18944 / d128
    paddle.seed(seed)
    qmodel = Qwen2ForCausalLM(qwen_cfg)
    qmodel.to(dtype="bfloat16")
    qmodel.train()

    @paddle.jit.to_static
    def qwen_step(ids):
        _, loss = qmodel(ids, labels=ids)
        loss.backward()
        gsum = None
        for p in qmodel.parameters():
            if p.grad is not None:
                g = p.grad.astype("float32").abs().mean()
                gsum = g if gsum is None else gsum + g
        for p in qmodel.parameters():
            p.clear_grad()
        return loss + 0.0 * gsum       # grads are live in the program

    b, s = sizes["qwen_batch"], sizes["qwen_seq"]
    batches = [rng.randint(0, qwen_cfg.vocab_size, (b, s)).astype(np.int64)
               for _ in range(sizes["qwen_steps"])]
    n_params = sum(p.size for p in qmodel.parameters())
    # fwd flash + rms (2 per layer + final) + swiglu, and their bwds
    _steps(qwen_step, batches, comp, "train_qwen2_fwd_bwd",
           want_calls=sizes["qwen_calls"], dev=dev,
           params=f"{n_params / 1e9:.2f}B", batch=b, seq=s)
    del qmodel, qwen_step
    gc.collect()


# ---- phase: four chips (--chips 4) ----------------------------------------

def phase_mesh(build, sizes, seed, comp):
    """``fleet.init(sharding_degree=2, mp_degree=2)`` over the four
    chips of one host, 3 optimizer steps, loss parity against the same
    model (same seed, same batch) on ONE device in this process."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.ops.pallas._mesh import kernel_placement

    b, s, steps = sizes["batch"], sizes["seq"], sizes["steps"]

    def run(parallel, place_ids):
        paddle.seed(seed)
        model = build(parallel)     # TP layers when parallel, same seed
        model.train()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        if parallel:
            opt = fleet.distributed_optimizer(opt)   # ZeRO over 'sharding'

        @paddle.jit.to_static
        def step(ids):
            _, loss = model(ids, labels=ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids_np = np.random.RandomState(seed).randint(
            0, model.config.vocab_size, (b, s)).astype(np.int64)
        ids = paddle.Tensor(place_ids(jnp.asarray(ids_np)))
        losses, times, built = [], [], []
        comp.mark()
        for _ in range(steps):
            t0 = time.perf_counter()
            loss = step(ids)
            losses.append(float(loss.item()))
            times.append(round(time.perf_counter() - t0, 1))
            built.append(comp.mark()[0])
        calls = sum(t.count("tpu_custom_call")
                    for t in step.program_texts())
        return model, opt, losses, (times, built), calls

    model, opt, ref, (times, built), calls = run(False, lambda a: a)
    say("mesh", reference="one device", losses=[round(x, 5) for x in ref],
        step_s=times, programs_per_step=built, pallas_calls_in_step=calls)
    del model, opt
    gc.collect()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 2,
                               "sep_degree": 1, "ep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().global_mesh
    check(mesh.size == 4, f"fleet mesh spans {mesh.size} devices, not 4")
    use_kernel, shard_mesh = kernel_placement()
    sharded = use_kernel and shard_mesh is mesh
    model, opt, got, (times, built), calls = run(True, lambda a: jax.device_put(
        a, NamedSharding(mesh, PartitionSpec(("data", "sharding"), None))))
    say("mesh", fleet="sharding2 x mp2", losses=[round(x, 5) for x in got],
        step_s=times, programs_per_step=built, pallas_calls_in_step=calls,
        attention_path=("Pallas flash per shard (shard_map: batch over "
                        "sharding, heads over model)"
                        if sharded and calls else "jnp"))
    check(sharded and calls >= sizes["pallas_calls"],
          "kernels under the fleet mesh did not take the shard_map path")
    for name, p in model.named_parameters():
        sh = p._data.sharding
        say("mesh", param=name, shape=tuple(p.shape),
            spec=getattr(sh, "spec", sh),
            devices=len(p._data.sharding.device_set))
    for d in jax.devices():
        say("mesh", device=d.id, **memory(d))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()]
    if sizes["memory_check"]:
        check(min(in_use) > 0.2 * max(in_use),
              f"state sits on one device: bytes_in_use {in_use}")
    diff = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))
                        / np.abs(np.asarray(ref))))
    say("mesh", loss_parity_max_rel_diff=f"{diff:.2e}", rtol=sizes["rtol"])
    check(diff <= sizes["rtol"], f"mesh losses {got} != one-device {ref}")
    check(got[-1] < got[0], "mesh: loss did not fall")


# ---- the real sizes -------------------------------------------------------

def real_sizes():
    """Published widths; every cut is printed by main()."""
    from paddle_tpu.models import GPT2Config, Qwen2Config

    serve_cfg = Qwen2Config.qwen2_7b()
    serve_cfg.num_hidden_layers = 8            # depth cut: 8 of 28
    serve_cfg.scan_layers = False
    serve = dict(slots=8, max_len=512, n_new=32, prompt_lens=[40, 96, 200],
                 per_length=3, shared={40: 32, 96: 64, 200: 160},
                 margin=0.125, margin_int8=0.25, argmax_rate=0.8,
                 pallas_calls=1)

    gpt_cfg = GPT2Config.small()
    # the flash kernel has no dropout path; with dropout live the model
    # would take jnp attention and the unrolled stack
    gpt_cfg.attention_dropout_prob = 0.0
    gpt_cfg.hidden_dropout_prob = 0.0
    qwen_cfg = Qwen2Config.qwen2_7b()
    qwen_cfg.num_hidden_layers = 2             # depth cut: 2 of 28
    # batch sizes are what 16 GB holds next to the eager discovery
    # step (GPT-2's f32 [B, 1024, 50257] logits: 14.7 GB of temporaries
    # at batch 8 when compiled for a described v5e)
    train = dict(gpt_batch=4, gpt_seq=1024, gpt_steps=6, gpt_lr=1e-3,
                 gpt_drop=0.2, gpt_calls=3, qwen_batch=1, qwen_seq=1024,
                 qwen_steps=4, qwen_calls=8)

    # cells: (heads, kv heads, pages a slot, window) of the attention
    # layers of perfbench/configs — qwen2-7b-d8, nemotron3-super-ep4-d11,
    # k-exaone-ep8-d5's global and window layers
    kernels_serve = dict(slots=8, chunk=128, heads=28, kv_heads=4,
                         head_dim=128, page=16, pages_per_slot=32,
                         cells={"qwen": (28, 4, 128, None),
                                "nemotron": (32, 2, 128, None),
                                "kexa_global": (64, 8, 320, None),
                                "kexa_window": (64, 8, 320, 128)})
    kernels_train = dict(
        flash={"gpt2": (4, 1024, 12, 12, 64), "qwen2": (1, 1024, 28, 4, 128)},
        rows=1024, hidden=3584, inter=18944, ce=(1024, 768, 50257))

    def mesh_model(parallel):
        # TinyLlama/TinyLlama-1.1B-Chat-v1.0 config.json widths, depth
        # cut to 2 of 22. (A 152k-vocabulary model does not fit here:
        # the ONE-device reference's eager discovery step holds f32
        # params + grads + Adam state + the update's temporaries, and a
        # 1.2 GB embedding ran the first four-chip call out of HBM.)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=32000, hidden_size=2048, num_hidden_layers=2,
            num_attention_heads=32, num_key_value_heads=4,
            intermediate_size=5632, max_position_embeddings=2048,
            rope_theta=10000.0, rms_norm_eps=1e-5,
            tensor_parallel=parallel))
    mesh = dict(batch=4, seq=512, steps=3, rtol=1e-2, pallas_calls=1,
                memory_check=True)
    return dict(serve_cfg=serve_cfg, serve=serve, gpt_cfg=gpt_cfg,
                qwen_cfg=qwen_cfg, train=train,
                kernels_serve=kernels_serve, kernels_train=kernels_train,
                mesh_model=mesh_model, mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the four-chip mesh phase")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r} — "
              f"there is no CPU mode", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    say("start", compile_cache=ensure_compile_cache(), seed=SEED)
    comp = Compiles()
    z = real_sizes()

    if args.chips == 4:
        say("mesh", model="Llama decoder at TinyLlama/TinyLlama-1.1B-Chat-"
            "v1.0 config.json widths (h2048, 32 heads / 4 KV, d64, MLP "
            "5632, vocab 32000), f32, AdamW", cut="depth 2 of 22 layers",
            **z["mesh"])
        phase_device(dev, len(devs))
        phase_mesh(z["mesh_model"], z["mesh"], SEED, comp)
    else:
        phase_device(dev, len(devs))
        phase_kernels(z["kernels_serve"], z["kernels_train"], SEED)
        say("kernels", compiled=comp.mark())
        say("serve", model="Qwen2-7B widths (Qwen/Qwen2-7B config.json: "
            "h3584, 28 heads / 4 KV, d128, MLP 18944, vocab 152064->"
            f"{z['serve_cfg'].vocab_size} as the preset has it), bf16",
            cut="depth 8 of 28 layers; nothing else")
        phase_serve(z["serve_cfg"], z["serve"], SEED, dev, comp)
        say("train", model_a="GPT-2 small, full published size (12L, "
            "h768, vocab 50257), seq 1024, bf16 AMP O1, AdamW",
            cut_a="none (dropout 0: the flash path has no dropout)",
            model_b="Qwen2-7B widths, bf16, forward+backward only",
            cut_b="depth 2 of 28 layers; no optimizer state")
        phase_train(z["gpt_cfg"], z["qwen_cfg"], z["train"], SEED,
                    dev, comp)
    say("done", programs_compiled=comp.miss, cache_hits=comp.hit,
        compile_and_fetch_s=round(comp.seconds, 1),
        wall_s=round(time.perf_counter() - _T0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
