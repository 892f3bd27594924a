"""Driver benchmark (BASELINE configs 2 & 5, chip-sized):

1. TRAIN (headline metric): Llama fwd/bwd bf16 on one chip at the
   LARGEST config that fits its HBM — ~2.4B with rematerialization on a
   16GB v5e (the 8B config needs 16GB for bf16 params+grads alone; see
   BASELINE.md for the arithmetic). MFU is reported against the chip's
   bf16 peak; vs_baseline = MFU / 0.40 (the north-star target).
2. DECODE (secondary, extra JSON keys): KV-cache greedy decode
   throughput on the 1B config — tokens/s across a batch of streams.

Prints the JSON record line INCREMENTALLY: once after the core
(train/decode/cb) sections, then re-printed enriched after each MoE
section. Every printed line is a complete, parseable record — whichever
line is last when the driver's time limit hits carries everything
measured so far:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "decode_*": ..., "cb_*": ..., "moe_*": ..., "moe_decode_*": ...}
"""

from __future__ import annotations

import json
import os
import sys
import time


def _provenance(dev) -> dict:
    """Attribution metadata stamped into EVERY record line: when a
    round goes sideways, the artifact alone
    must say which jax, which chip/backend, which restart round and
    which commit produced it — no cross-referencing driver logs."""
    import platform
    import subprocess

    import jax
    git_rev = None
    try:
        p = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        git_rev = p.stdout.strip() or None
    except Exception:
        pass
    return {
        "jax_version": jax.__version__,
        "backend": dev.platform,
        "chip": getattr(dev, "device_kind", None) or "?",
        "device_count": jax.device_count(),
        "restart_round": int(os.environ.get("PADDLE_RESTART_ROUND",
                                            "0")),
        "git_rev": git_rev,
        "python": platform.python_version(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def _mfu(flops_per_step, dt, device):
    """Model-FLOPs utilization against the profiler's peak table
    (profiler/cost.py — single source of truth), or None on a device
    the table does not know (a CPU smoke): no peak is invented."""
    from paddle_tpu.profiler.cost import known_peaks
    peaks = known_peaks(device)
    return flops_per_step / dt / peaks.flops if peaks else None


def _pct(x):
    return "n/a" if x is None else f"{x * 100:.1f}%"


def _train_bench(on_tpu, dev):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        try:
            hbm = dev.memory_stats().get("bytes_limit", 16e9)
        except Exception:
            hbm = 16e9
        if hbm > 64e9:
            cfg = LlamaConfig.llama3_8b()
            batch, seq = 4, 2048
            cfg.use_recompute = True
            cfg.recompute_granularity = "core_attn"
        else:
            # v5e 16GB: largest-fit ~2.4B with remat (dots_saveable);
            # shows the deep-config MFU, not just the 1B sweet spot
            cfg = LlamaConfig.llama_2_4b()
            batch, seq = 2, 2048
        cfg.scan_layers = False  # unrolled beats lax.scan on-chip today
        # (scan also OOMs at full depth: stacking weights into [L, ...]
        # transiently doubles parameter memory). Flash block sizes come
        # from the FLAGS defaults (256/512, tuned for this config).
        steps, warmup = 10, 3
    else:
        cfg = LlamaConfig.tiny()
        batch, seq = 2, 128
        steps, warmup = 5, 2
    cfg.tensor_parallel = False

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)).astype(np.int64))

    @paddle.jit.to_static
    def fwd_bwd(ids):
        _, loss = model(ids, labels=ids)
        loss.backward()
        # keep backward alive in the compiled program: fold one element
        # of every grad into the returned scalar, then drop them. (A
        # no-compute optimization_barrier was tried instead — it pins
        # every grad buffer live until the end of step and HBM-thrashes:
        # 930 vs 182 ms. Full-grad sums were the round-3 choice; the
        # one-element read keeps every grad's producing ops alive while
        # skipping a 4.7GB reduce of the stacked grads — worth ~0.2 MFU
        # at 2.37B, round-4 A/B.)
        gsum = None
        for p in model.parameters():
            if p.grad is not None:
                s = p.grad.flatten()[0].astype("float32")
                gsum = s if gsum is None else gsum + s
        for p in model.parameters():
            p.clear_grad()
        return loss, gsum

    # distinct inputs per step (chip_smoke.py's S8 check found no
    # execution replay on the current chip; the roll is harmless)
    step_ids = [paddle.to_tensor(np.roll(np.asarray(ids.numpy()), i,
                                         axis=1))
                for i in range(steps)]

    # warmup / compile (a scalar fetch is a true sync)
    for _ in range(warmup):
        loss, gsum = fwd_bwd(ids)
    float(loss.item())

    t0 = time.perf_counter()
    acc = None
    for i in range(steps):
        loss, gsum = fwd_bwd(step_ids[i])
        acc = loss if acc is None else acc + loss
    float(acc.item())  # device-chained; one final scalar sync
    dt = (time.perf_counter() - t0) / steps

    import os
    if os.environ.get("BENCH_AB_GUARD"):
        # A/B the keep-backward-alive trick: the one-element grad read
        # relies on XLA NOT sinking the slice into the backward dots; if
        # a future XLA applies slice-of-dot simplification it could DCE
        # weight-grad compute and silently inflate MFU. Time the
        # full-grad-sum variant and flag a divergence.
        @paddle.jit.to_static
        def fwd_bwd_full(ids):
            _, loss = model(ids, labels=ids)
            loss.backward()
            gsum = None
            for p in model.parameters():
                if p.grad is not None:
                    s = p.grad.astype("float32").sum()
                    gsum = s if gsum is None else gsum + s
                p.clear_grad()
            return loss, gsum

        for _ in range(2):
            loss_f, gsum_f = fwd_bwd_full(ids)
        float(loss_f.item())
        t0 = time.perf_counter()
        accf = None
        for i in range(4):
            loss_f, _ = fwd_bwd_full(step_ids[i])
            accf = loss_f if accf is None else accf + loss_f
        float(accf.item())
        dt_full = (time.perf_counter() - t0) / 4
        drift = (dt_full - dt) / dt_full
        print(f"# A/B guard: one-elem {dt*1000:.1f} ms vs full-grad-sum "
              f"{dt_full*1000:.1f} ms ({drift*100:+.1f}% incl. the "
              f"full 4.7GB reduce)", file=sys.stderr)
        if drift > 0.10:
            print("# A/B GUARD FAILED: one-element variant >10% faster "
                  "than full-grad-sum — XLA may be DCE'ing backward "
                  "compute; headline MFU suspect", file=sys.stderr)

    tokens = batch * seq
    n_params = sum(p.size for p in model.parameters())
    L, d = cfg.num_hidden_layers, cfg.hidden_size
    # MFU counts model FLOPs only (6*N*tokens + attention); recompute's
    # re-forward work is real hardware time but not model FLOPs, so it is
    # deliberately NOT added (that would report HFU and inflate the metric)
    flops_per_step = 6.0 * n_params * tokens \
        + 12.0 * L * batch * seq * seq * d
    mfu = _mfu(flops_per_step, dt, dev)
    tok_per_s = tokens / dt
    print(f"# train: step {dt*1000:.1f} ms, params {n_params/1e9:.3f}B, "
          f"MFU {_pct(mfu)} "
          f"({getattr(dev, 'device_kind', dev.platform)}), "
          f"loss {float(loss.item()):.3f}", file=sys.stderr)
    return n_params, tok_per_s, mfu


def _fit_e2e_bench(on_tpu, dev, autotune=False):
    """End-to-end fit-loop efficiency (ISSUE-5 tentpole): hapi
    ``Model.fit`` running the compiled step with device prefetch and
    non-blocking loss, measured against (a) the raw compiled
    fwd_bwd+update step over a pre-placed batch — the floor the fit
    loop should approach — and (b) the eager tape loop (CPU smoke
    only; eager per-op dispatch of the chip config would dwarf the
    section budget). Emits ``train_e2e_*`` keys plus
    ``input_*`` keys from the prefetch stage.

    autotune=True additionally sweeps the ``fit_pipeline`` surface
    (prefetch_depth × steps_in_flight) over short fits, committing the
    winner to the tuning cache (the serving_chunks pattern: the
    surface needs a live model + workload, so it cannot ride the
    standalone CLI builders)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.hapi import Model
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        batch, seq, n_batches = 8, 1024, 12
    else:
        cfg = LlamaConfig.tiny()
        batch, seq, n_batches = 2, 64, 10
    cfg.tensor_parallel = False
    cfg.scan_layers = False

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    # SGD keeps optimizer-state HBM flat (the 1B + Adam moments would
    # crowd a 16GB chip next to activations); the fit-loop overhead
    # being measured is optimizer-agnostic
    m = Model(model)
    m.prepare(paddle.optimizer.SGD(1e-4, parameters=model.parameters()),
              LlamaPretrainingCriterion(cfg))

    ids_np = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch * n_batches, seq + 1)).astype(np.int64)
    ids_t = paddle.to_tensor(ids_np)
    ds = paddle.io.TensorDataset([ids_t, ids_t])

    # (a) raw compiled step over one resident batch — no loader, no
    # prefetch, no loss bookkeeping; scalar fetch only at the end.
    # Runs under the SAME fused-linear-CE default as fit (fit flips it
    # via flags.scoped_default) so the raw/fit comparison times one
    # program, and the StaticFunction cache discovered here matches
    # what fit reuses.
    from paddle_tpu.framework import flags as _flags
    x0 = paddle.to_tensor(ids_np[:batch])
    step_fn = m._static_train_step()
    with _flags.scoped_default("FLAGS_fused_linear_cross_entropy", True):
        loss = step_fn(x0, x0)            # discovery
        loss = step_fn(x0, x0)            # compile+run
        float(np.asarray(loss._data))
        raw_steps = 2 * n_batches
        t0 = time.perf_counter()
        for _ in range(raw_steps):
            loss = step_fn(x0, x0)
        float(np.asarray(loss._data))
        raw_ms = (time.perf_counter() - t0) / raw_steps * 1e3

    tuned_fit = {}
    if autotune:
        from paddle_tpu import tuner
        from paddle_tpu.tuner.surface import sig_from_dict
        shape = {"bs": batch}
        key = tuner.make_key("fit_pipeline", sig_from_dict(shape), "-",
                             tuner.backend_signature())
        cache = tuner.get_cache()
        hit = cache.get(key)
        if hit is not None:
            tuned_fit = {"config": hit["config"], "cached_hit": True,
                         "shape_sig": sig_from_dict(shape)}
        else:
            surface = tuner.get_surface("fit_pipeline")
            # small DIVERSE slice (each candidate = one timed epoch):
            # default first, then an even stride across the rest so
            # both depth extremes get tried; candidates_tried reports
            # the truncation — no silent cap
            grid = surface.grid(shape)
            rest = [c for c in grid if c != surface.default]
            # default + both grid extremes + the middle: the corners
            # are the configs a sweep exists for, so pick them
            # literally instead of striding past them
            picks = ([rest[0], rest[len(rest) // 2], rest[-1]]
                     if rest else [])
            cands = grid[:1] + [c for i, c in enumerate(picks)
                                if c not in picks[:i]]
            trials = []
            for c in cands:
                m.fit(ds, batch_size=batch, epochs=1, verbose=0,
                      shuffle=False, log_freq=1_000_000,
                      prefetch_depth=c["prefetch_depth"],
                      steps_in_flight=c["steps_in_flight"])
                trials.append(
                    (dict(c), m._last_epoch_summary["avg_step_ms"]))
            win_cfg, win_ms = min(trials, key=lambda t: t[1])
            cache.put(key, win_cfg, median_ms=win_ms,
                      representative=on_tpu, source="search",
                      extra={"trials": len(trials)})
            tuned_fit = {"config": win_cfg, "cached_hit": False,
                         "shape_sig": sig_from_dict(shape),
                         "step_ms": round(win_ms, 3),
                         "candidates_tried": len(trials)}
            print(f"# fit autotune: {win_cfg} {win_ms:.2f} ms/step "
                  f"({len(trials)} candidates)", file=sys.stderr)

    # (b) the compiled fit loop: epoch 0 warms (compile + prefetch
    # spin-up), epoch 1 is the measurement — per-epoch stats ride the
    # profiler's epoch summary
    m.fit(ds, batch_size=batch, epochs=2, verbose=0, shuffle=False,
          log_freq=1_000_000)
    s = m._last_epoch_summary
    fit_ms = s["avg_step_ms"]
    tokens = batch * seq
    # goodput ledger projection (obs_* keys, docs/observability.md):
    # the compiled fit's wall-time partition — captured HERE, before
    # the eager oracle fit below replaces the model's ledger
    gp_keys = m._goodput.bench_keys() if m._goodput is not None else {}

    # (c) eager oracle loop (CPU smoke only — see docstring)
    eager_ms = None
    if not on_tpu:
        m.fit(ds, batch_size=batch, epochs=1, verbose=0, shuffle=False,
              log_freq=1_000_000, compiled=False)
        eager_ms = m._last_epoch_summary["avg_step_ms"]

    out = {
        "train_e2e_step_ms": round(fit_ms, 3),
        "train_e2e_raw_step_ms": round(raw_ms, 3),
        "train_e2e_overhead_ms": round(fit_ms - raw_ms, 3),
        "train_e2e_tokens_per_sec": round(tokens / (fit_ms / 1e3), 2),
        "input_wait_ms": s.get("input_wait_ms"),
        "input_h2d_mb": s.get("h2d_mb"),
        "input_prefetch_depth": m._fit_pipeline["prefetch_depth"],
        "input_steps_in_flight": m._fit_pipeline["steps_in_flight"],
    }
    out.update(gp_keys)
    if eager_ms is not None:
        out["train_e2e_eager_step_ms"] = round(eager_ms, 3)
        out["train_e2e_vs_eager"] = round(eager_ms / fit_ms, 4)
    if tuned_fit:
        out["tuned_fit_pipeline"] = tuned_fit
    print(f"# fit e2e: {fit_ms:.2f} ms/step (raw step {raw_ms:.2f} ms, "
          f"overhead {fit_ms - raw_ms:+.2f} ms"
          + (f", eager {eager_ms:.2f} ms" if eager_ms is not None else "")
          + f"), input wait {s.get('input_wait_ms')} ms/epoch",
          file=sys.stderr)
    return out


def _train_mem_bench(on_tpu, dev):
    """Peak-HBM accounting for the training hot path (ISSUE-8): turns
    the fused linear+CE memory claim into TRACKED bench records.

    Measures the lm_head+CE tail (fwd + dh/dW backward, the exact
    sub-program the fused op replaces) at the train bench geometry via
    XLA's compile-time memory analysis — ``lower().compile()`` only,
    nothing executes, so the probe is cheap and deterministic on CPU
    and TPU alike. Emits:

    - ``train_peak_hbm_gb`` / ``train_peak_hbm_unfused_gb``: peak
      temp-buffer bytes of the fused vs materialized-[N, V] tail;
      ``train_peak_hbm_ratio`` is the headline (>= 4x expected — the
      acceptance bar).
    - ``train_max_fit``: the largest ``(batch, seq)`` whose fused tail
      fits the activation budget (real ``bytes_limit`` on TPU minus
      the weight-resident floor; a nominal v5e 16GB elsewhere), found
      by doubling batch; ``train_max_fit_unfused`` for contrast — the
      bigger-batch headroom the fused path buys, as a record."""
    import numpy as np  # noqa: F401  (symmetry with sibling sections)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    if on_tpu:
        batch, seq, d, v = 2, 2048, 2560, 32000   # llama_2_4b train bench
        try:
            budget = float(dev.memory_stats().get("bytes_limit", 16e9))
        except Exception:
            budget = 16e9
    else:
        # the CPU-smoke fit geometry's head (llama_1b: d 2048, v 32000)
        # against the nominal v5e budget — same accounting, no chip
        batch, seq, d, v = 8, 1024, 2048, 32000
        budget = 16e9
    # activations may use roughly what is left after bf16 params+grads
    # of the 2.4B bench config (~9.6GB); the probe budget is the rest
    act_budget = budget * 0.4
    dt = jnp.bfloat16

    def tail_fused(h, w, labels):
        return fused_linear_cross_entropy(h, w, labels)

    def tail_unfused(h, w, labels):
        logits = (h @ w).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(lp, labels[:, None], -1)[:, 0]
        return per.mean()

    def tail_peak_bytes(fn, n):
        """Peak temp bytes of jit(grad(tail)) at N=n rows — compile
        only, never executed."""
        h = jax.ShapeDtypeStruct((n, d), dt)
        w = jax.ShapeDtypeStruct((d, v), dt)
        lab = jax.ShapeDtypeStruct((n,), jnp.int32)
        step = jax.jit(jax.grad(fn, argnums=(0, 1)))
        mem = step.lower(h, w, lab).compile().memory_analysis()
        if mem is None:
            return None
        return float(mem.temp_size_in_bytes)

    n0 = batch * seq
    fused_b = tail_peak_bytes(tail_fused, n0)
    unfused_b = tail_peak_bytes(tail_unfused, n0)
    if fused_b is None or unfused_b is None:
        print("# train mem: memory_analysis unavailable on this "
              "backend; skipping", file=sys.stderr)
        return None

    def max_fit(fn, base_peak, cap_doublings=7):
        """Largest batch (power-of-2 ladder from the bench batch) whose
        tail fits act_budget; ``base_peak`` reuses the bench-geometry
        measurement above so the ladder's first rung never recompiles."""
        best, b, peak = None, batch, base_peak
        for _ in range(cap_doublings + 1):
            if peak is None or peak > act_budget:
                break
            best, b = b, b * 2
            peak = tail_peak_bytes(fn, b * seq)
        return best

    fit_fused = max_fit(tail_fused, fused_b)
    fit_unfused = max_fit(tail_unfused, unfused_b)
    out = {
        "train_peak_hbm_gb": round(fused_b / 1e9, 4),
        "train_peak_hbm_unfused_gb": round(unfused_b / 1e9, 4),
        "train_peak_hbm_ratio": round(unfused_b / max(fused_b, 1.0), 2),
        "train_peak_hbm_geometry": {"batch": batch, "seq": seq, "d": d,
                                    "v": v},
        "train_max_fit": {"batch": fit_fused, "seq": seq},
        "train_max_fit_unfused": {"batch": fit_unfused, "seq": seq},
    }
    if on_tpu:
        # the real chip's high-water mark across the sections run so
        # far (PJRT counts all live buffers — params included)
        try:
            peak = dev.memory_stats().get("peak_bytes_in_use")
            if peak:
                out["train_device_peak_hbm_gb"] = round(peak / 1e9, 4)
        except Exception:
            pass
    print(f"# train mem: lm_head+CE tail peak {fused_b/1e6:.1f} MB "
          f"fused vs {unfused_b/1e6:.1f} MB with [N, V] logits "
          f"(x{out['train_peak_hbm_ratio']:.1f}); max-fit batch @ seq "
          f"{seq}: {fit_fused} fused vs {fit_unfused} unfused",
          file=sys.stderr)
    return out


def _decode_bench(on_tpu):
    """Greedy KV-cache decode throughput (BASELINE config 5's serving
    shape, chip-sized): batch of streams, measure generated tokens/s in
    the steady state (prefill excluded via a timed second run whose extra
    length isolates decode)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        # long decode: the per-token time comes from a long-minus-short
        # difference, which must dominate dispatch variance
        batch, prompt, n_new = 8, 128, 512
    else:
        cfg = LlamaConfig.tiny()
        batch, prompt, n_new = 2, 8, 8
    cfg.tensor_parallel = False
    cfg.scan_layers = False

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    ids = paddle.to_tensor(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int64))

    def run(n, prompt):
        out, _ = model.generate(prompt, max_new_tokens=n,
                                decode_strategy="greedy_search",
                                eos_token_id=None, pad_token_id=0)
        return int(out[0, -1].item())   # scalar fetch = true sync

    # distinct prompts per call
    base = np.asarray(ids.numpy())
    import paddle_tpu as _p
    prompts = [_p.to_tensor(np.roll(base, i + 1, axis=1)) for i in range(6)]
    # n_new is part of the fused program's signature: warm up BOTH
    # trip counts so neither timed run pays compilation
    run(n_new, ids)
    run(4, prompts[0])

    def timed(n, prompt):
        t0 = time.perf_counter()
        run(n, prompt)
        return time.perf_counter() - t0

    # min over reps: dispatch latency varies; the
    # long-short difference isolates pure decode time
    dt_long = min(timed(n_new, prompts[1]), timed(n_new, prompts[2]))
    dt_short = min(timed(4, prompts[3]), timed(4, prompts[4]))
    per_tok = max(dt_long - dt_short, 1e-9) / (n_new - 4)
    tok_per_s = batch / per_tok
    print(f"# decode: {per_tok*1000:.2f} ms/token/batch, "
          f"{tok_per_s:.0f} tokens/s (batch {batch})", file=sys.stderr)
    return tok_per_s


def _cb_bench(on_tpu, autotune=False):
    """Continuous batching over paged KV (the serving-depth metric):
    mixed-length prompt streams scheduled through fixed decode slots,
    aggregate generated tokens/s. More streams than slots, so the run
    exercises drain + re-admit mid-flight.

    autotune=True makes this section the serving_chunks sweep vehicle
    (the surface needs a model + workload, so it cannot ride the
    standalone CLI builders): a few candidate ladders from the
    registered grid each get their own engine + timed run, the
    fastest commits to the tuning cache, and the tuned_serving_chunks
    record entry reports it."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        slots, page, chunk = 8, 32, 32
        max_len, pchunk = 384, 256
        specs = [(64, 128), (128, 96), (192, 128), (64, 64),
                 (128, 128), (192, 96), (64, 128), (128, 64),
                 (96, 128), (160, 96), (64, 96), (128, 128)]
        reps = 2
    else:
        cfg = LlamaConfig.tiny()
        slots, page, chunk = 2, 8, 4
        max_len, pchunk = 48, 16
        specs = [(6, 8), (12, 5), (9, 10), (4, 6)]
        reps = 1
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    # ONE engine across warmup + timed reps: the compiled prefill-bucket
    # and decode-chunk programs are cached per engine instance, and a
    # compile costs seconds — rebuilding the engine inside the timed
    # region would benchmark compilation
    eng = ContinuousBatchingEngine(model, num_slots=slots, page_size=page,
                                   max_len=max_len, decode_chunk=chunk,
                                   prefill_chunk=pchunk, greedy=True)

    def timed_engine(e):
        """warmup (compiles prefill + chunk ladder) then best timed
        rep; returns (tokens/s, wall_s of the best rep, tokens)."""
        def erun(seed):
            rng = np.random.RandomState(seed)
            for plen, n in specs:
                # distinct prompts per run
                e.add_request(rng.randint(0, cfg.vocab_size,
                                          (plen,)).astype(np.int32), n)
            done = e.run()
            return sum(len(r.tokens) for r in done)

        erun(100)
        e.reset_gauges()
        b, t, w = 0.0, 0, None
        for i in range(reps):
            t0 = time.perf_counter()
            t = erun(101 + i)
            dt = time.perf_counter() - t0
            if t / dt > b:
                b, w = t / dt, dt
        return b, w, t

    best, best_wall, toks = timed_engine(eng)

    tuned_cb = {}
    if autotune:
        # serving_chunks sweep: the bench ladder is the incumbent; a
        # few grid alternates each get a fresh engine (own compiled
        # programs) and the same workload. Winner commits to the cache
        # so every ctor that leaves the knobs None inherits it.
        from paddle_tpu import tuner
        from paddle_tpu.tuner.surface import sig_from_dict
        shape = {"slots": slots, "max_len": max_len, "page": page}
        dtype = next(iter(model.parameters()))._data.dtype
        backend = tuner.backend_signature()
        key = tuner.make_key("serving_chunks", sig_from_dict(shape),
                             str(dtype), backend)
        cache = tuner.get_cache()
        hit = cache.get(key)
        incumbent = {"decode_chunk": chunk,
                     "prefill_chunk": eng.prefill_chunk,
                     "admit_batch": eng.admit_batch}
        if hit is not None:
            tuned_cb = {"config": hit["config"], "cached_hit": True,
                        "shape_sig": sig_from_dict(shape)}
        else:
            surface = tuner.get_surface("serving_chunks")
            # small diverse slice of the grid (compile cost per
            # candidate is a whole engine); dropped breadth is implied
            # by candidates_tried in the record — not a silent cap
            cands = [c for c in surface.grid(shape)
                     if c != incumbent][:2]
            trials = [(incumbent, best_wall, best)]
            for c in cands:
                try:
                    e = ContinuousBatchingEngine(
                        model, num_slots=slots, page_size=page,
                        max_len=max_len,
                        decode_chunk=c["decode_chunk"],
                        prefill_chunk=c["prefill_chunk"],
                        admit_batch=c["admit_batch"], greedy=True)
                    tps, wall, _ = timed_engine(e)
                    trials.append((dict(c), wall, tps))
                except Exception as exc:  # candidate-scoped, like the
                    print(f"# cb autotune candidate {c} failed: "
                          f"{exc!r}", file=sys.stderr)  # trial engine
            win_cfg, win_wall, win_tps = min(trials, key=lambda t: t[1])
            cache.put(key, win_cfg, median_ms=win_wall * 1e3,
                      representative=on_tpu, source="search",
                      extra={"trials": len(trials),
                             "tok_s": round(win_tps, 2)})
            tuned_cb = {"config": win_cfg, "cached_hit": False,
                        "shape_sig": sig_from_dict(shape),
                        "tok_s": round(win_tps, 2),
                        "default_tok_s": round(best, 2),
                        "candidates_tried": len(trials)}
            print(f"# cb autotune: {win_cfg} {win_tps:.0f} tok/s vs "
                  f"incumbent {best:.0f} tok/s "
                  f"({len(trials)} candidates)", file=sys.stderr)
            best = max(best, win_tps)
    # occupancy / admission-overlap / latency gauges (profiler
    # subsystem): the numbers BASELINE.md's CB-ceiling argument was
    # previously deriving by hand, plus the TTFT/ITL percentiles and
    # the compiled-signature count (ONE unified batching-step program
    # — the PR-3 engine compiled 1 prefill + a decode-chunk ladder,
    # the per-bucket baseline one prefill per bucket AND per length)
    gauges = eng.gauges()
    print(f"# continuous batching: {toks} tokens across "
          f"{len(specs)} mixed-length streams, {best:.0f} tokens/s "
          f"(occupancy {gauges['slot_occupancy'] * 100:.0f}%, prefill "
          f"overlap {gauges['prefill_overlap_frac'] * 100:.0f}%, "
          f"ttft p50 {gauges['ttft_ms_p50']:.1f}ms, itl p50 "
          f"{gauges['itl_ms_p50']:.2f}ms, {gauges['compiled_programs']} "
          f"compiled programs, {gauges['unified_steps']} unified steps)",
          file=sys.stderr)
    return best, gauges, tuned_cb


def _cb_spec_bench(on_tpu, autotune=False):
    """Speculative decoding A/B (ISSUE 18): spec-on vs plain on the
    SAME model and geometry at decode batch 1/4/8 — the small-batch
    decode-bound regime where one compiled program per emitted token
    is the cost spec decoding amortizes. Both legs run decode_chunk=1
    so the A/B isolates per-program amortization (the scan-tail chunk
    ladder is the OTHER amortization axis, measured by cb_value); the
    workload is n-gram-friendly (prompts with repeated spans, the
    templated-text shape) so acceptance is high — cb_spec_accept_rate
    in the record says how high, and BASELINE.md documents the caveat.

    autotune=True makes this section the ``spec_decode`` surface's
    sweep vehicle (K ladder x draft source at the batch-1 geometry;
    the surface needs a model + workload, so it cannot ride the
    standalone CLI builders): the winner commits to the tuning cache,
    where every ctor that leaves spec_k/spec_draft None inherits it.

    Plus the goodput leg: the PR-15 HTTP load harness drives the
    ``short_chat_batch1`` trace mix (low concurrency, long
    generations) against a spec-backed and a plain-backed ApiServer.
    """
    import json as _json
    import subprocess
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ApiServer, ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        page, max_len, pchunk = 32, 384, 64
        base_len, tile, n_new, reps = 16, 3, 96, 2
        http_req, http_conc = 12, 2
    else:
        cfg = LlamaConfig.tiny()
        page, max_len, pchunk = 8, 64, 16
        base_len, tile, n_new, reps = 4, 3, 24, 2
        http_req, http_conc = 8, 2
    spec_k = 4
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    def make_engine(nslots, spec, **kw):
        skw = dict(spec_k=spec_k, spec_draft="ngram") if spec else {}
        skw.update(kw)
        return ContinuousBatchingEngine(
            model, num_slots=nslots, page_size=page, max_len=max_len,
            decode_chunk=1, prefill_chunk=pchunk, greedy=True, **skw)

    def prompts_for(nreq, seed):
        # repeated-span prompts: the generated stream re-walks its own
        # prompt, the n-gram source's best case
        rng = np.random.RandomState(seed)
        return [np.tile(rng.randint(0, cfg.vocab_size,
                                    (base_len,)).astype(np.int32),
                        tile) for _ in range(nreq)]

    def timed(eng, nreq, seed0):
        """Warmup (compiles) then best-of-reps tok/s + gauges."""
        def erun(seed):
            for p in prompts_for(nreq, seed):
                eng.add_request(p, n_new)
            done = eng.run()
            return sum(len(r.tokens) for r in done)

        erun(900)
        eng.reset_gauges()
        best = 0.0
        for i in range(reps):
            t0 = time.perf_counter()
            t = erun(seed0 + i)
            best = max(best, t / max(time.perf_counter() - t0, 1e-9))
        return best, eng.gauges()

    batches = {}
    for b in (1, 4, 8):
        nreq = b if on_tpu else max(b, 2)
        plain_tps, _ = timed(make_engine(b, spec=False), nreq, 910 + b)
        spec_tps, g = timed(make_engine(b, spec=True), nreq, 910 + b)
        batches[f"b{b}"] = {
            "tok_s": round(spec_tps, 2),
            "plain_tok_s": round(plain_tps, 2),
            "vs_plain": round(spec_tps / plain_tps, 4)
            if plain_tps else 0.0,
            "itl_ms_p99": round(g["itl_ms_p99"], 3),
            "accept_rate": round(g["spec_accept_rate"], 4),
        }
        print(f"# cb spec b{b}: {spec_tps:.1f} tok/s vs plain "
              f"{plain_tps:.1f} (x{batches[f'b{b}']['vs_plain']}), "
              f"accept {batches[f'b{b}']['accept_rate']}, itl p99 "
              f"{batches[f'b{b}']['itl_ms_p99']} ms", file=sys.stderr)

    b1 = batches["b1"]
    out = {
        # headline keys = the batch-1 interactive regime where one
        # program per token hurts most (acceptance criterion:
        # cb_spec_vs_plain >= 1.0 here on the CPU smoke)
        "cb_spec_tok_s": b1["tok_s"],
        "cb_spec_vs_plain": b1["vs_plain"],
        "cb_spec_accept_rate": b1["accept_rate"],
        "cb_spec_itl_ms_p99": b1["itl_ms_p99"],
        "cb_spec_batches": batches,
    }

    if autotune:
        # spec_decode sweep (K x source) at the batch-1 geometry; the
        # small slice is not a silent cap — candidates_tried reports it
        from paddle_tpu import tuner
        from paddle_tpu.tuner.surface import sig_from_dict
        shape = {"slots": 1, "max_len": max_len, "page": page}
        dtype = next(iter(model.parameters()))._data.dtype
        key = tuner.make_key("spec_decode", sig_from_dict(shape),
                             str(dtype), tuner.backend_signature())
        cache = tuner.get_cache()
        hit = cache.get(key)
        if hit is not None:
            out["tuned_spec_decode"] = {
                "config": hit["config"], "cached_hit": True,
                "shape_sig": sig_from_dict(shape)}
        else:
            surface = tuner.get_surface("spec_decode")
            incumbent = {"k": spec_k, "source": "ngram"}
            cands = [c for c in surface.grid(shape)
                     if c != incumbent][:3]
            trials = [(incumbent, b1["tok_s"])]
            for c in cands:
                try:
                    e = make_engine(1, spec=False, spec_k=c["k"],
                                    spec_draft=c["source"])
                    tps, _ = timed(e, 1 if on_tpu else 2, 950)
                    trials.append((dict(c), tps))
                except Exception as exc:
                    print(f"# spec autotune candidate {c} failed: "
                          f"{exc!r}", file=sys.stderr)
            win_cfg, win_tps = max(trials, key=lambda t: t[1])
            cache.put(key, win_cfg, median_ms=None,
                      representative=on_tpu, source="search",
                      extra={"trials": len(trials),
                             "tok_s": round(win_tps, 2)})
            out["tuned_spec_decode"] = {
                "config": win_cfg, "cached_hit": False,
                "shape_sig": sig_from_dict(shape),
                "tok_s": round(win_tps, 2),
                "candidates_tried": len(trials)}
            print(f"# spec autotune: {win_cfg} {win_tps:.1f} tok/s "
                  f"({len(trials)} candidates)", file=sys.stderr)

    # goodput leg: short_chat_batch1 through the HTTP front door,
    # spec-backed vs plain-backed ApiServer on the same trace
    def http_leg(spec):
        eng = make_engine(2, spec=spec)
        for p in prompts_for(2, 990):
            eng.add_request(p, 4)
        eng.run()                   # warm the compiles off the clock
        srv = ApiServer(eng, stream_chunk_tokens=8).start()
        try:
            with tempfile.NamedTemporaryFile(
                    suffix=".json", delete=False) as tf:
                rep_path = tf.name
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(
                     os.path.abspath(__file__)),
                     "tools", "load_harness.py"),
                 "--url", srv.url, "--requests", str(http_req),
                 "--concurrency", str(http_conc), "--mode", "closed",
                 "--vocab", str(cfg.vocab_size),
                 "--trace-mix", "short_chat_batch1",
                 "--seed", "18", "--report", rep_path],
                capture_output=True, text=True, timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"load harness failed: {proc.stderr[-500:]}")
            with open(rep_path) as f:
                report = _json.load(f)
            os.unlink(rep_path)
            return report
        finally:
            srv.stop()

    try:
        plain_rep = http_leg(spec=False)
        spec_rep = http_leg(spec=True)
        out["cb_spec_http_tok_s"] = round(spec_rep["tok_s"], 2)
        out["cb_spec_http_goodput_frac"] = round(
            spec_rep["goodput_frac"], 4)
        out["cb_spec_http_vs_plain"] = round(
            spec_rep["tok_s"] / plain_rep["tok_s"], 4) \
            if plain_rep["tok_s"] else 0.0
        print(f"# cb spec http: {out['cb_spec_http_tok_s']} tok/s "
              f"delivered (plain {plain_rep['tok_s']:.1f}, "
              f"x{out['cb_spec_http_vs_plain']}), goodput "
              f"{out['cb_spec_http_goodput_frac']}", file=sys.stderr)
    except Exception as exc:    # the A/B headline survives a flaky leg
        print(f"# cb spec http leg failed: {exc!r}", file=sys.stderr)
    return out


def _cb_overload_bench(on_tpu):
    """Serving-reliability economics under synthetic heavy traffic
    (ISSUE 10): drive the engine ~4x past its page capacity with
    mixed-priority, deadlined requests through the
    AdmissionController + EngineSupervisor stack and report the
    overload survival numbers — throughput, tail TTFT, shed fraction,
    preemption rate and SLO goodput. BASELINE.md documents the keys."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import (AdmissionController,
                                      ContinuousBatchingEngine,
                                      EngineSupervisor, Overloaded)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        slots, page, chunk, max_len = 8, 32, 32, 384
        n_req, plen_lo, plen_hi, new_lo, new_hi = 96, 48, 192, 32, 96
        ttft_slo_s, total_slo_s = 30.0, 120.0
    else:
        cfg = LlamaConfig.tiny()
        slots, page, chunk, max_len = 2, 8, 4, 48
        n_req, plen_lo, plen_hi, new_lo, new_hi = 16, 3, 11, 2, 7
        ttft_slo_s, total_slo_s = 60.0, 120.0
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    def factory():
        # default pool (slots * pages_per_slot + 1): the queue depth
        # below is what oversubscribes it ~4x
        return ContinuousBatchingEngine(
            model, num_slots=slots, page_size=page, max_len=max_len,
            decode_chunk=chunk, greedy=True)

    sup = EngineSupervisor(factory, max_restarts=2)
    # bound chosen so a slice of the offered load is SHED (the door is
    # part of what this section measures)
    adm = AdmissionController(sup, max_queue=max(4, n_req // 2),
                              default_ttft_slo_s=ttft_slo_s)
    rng = np.random.RandomState(33)
    offered = n_req
    accepted_ids, shed = [], 0
    slos = {}
    t0 = time.perf_counter()
    for i in range(n_req):
        plen = int(rng.randint(plen_lo, plen_hi + 1))
        n_new = int(rng.randint(new_lo, new_hi + 1))
        try:
            rid = adm.submit(
                rng.randint(0, cfg.vocab_size,
                            (plen,)).astype(np.int32),
                n_new, priority=int(rng.randint(0, 3)),
                ttft_deadline_s=ttft_slo_s, deadline_s=total_slo_s)
            accepted_ids.append(rid)
            slos[rid] = (ttft_slo_s, total_slo_s)
        except Overloaded:
            shed += 1
    done = sup.run()
    wall = max(time.perf_counter() - t0, 1e-9)
    by = {r.request_id: r for r in done}
    ok = [by[i] for i in accepted_ids if by[i].error is None]
    toks = sum(len(r.tokens) for r in ok)
    ttfts = sorted((r.t_first - r.t_arrive) * 1e3
                   for r in ok if r.t_first)
    p99 = ttfts[max(0, int(round(0.99 * (len(ttfts) - 1))))] \
        if ttfts else 0.0
    slo_met = [r for r in ok
               if (r.t_first - r.t_arrive) <= slos[r.request_id][0]
               and (r.t_done - r.t_arrive) <= slos[r.request_id][1]]
    g = sup.gauges()   # counters carried across supervised restarts
    out = {
        "cb_overload_tok_s": round(toks / wall, 2),
        "cb_overload_p99_ttft_ms": round(p99, 2),
        "cb_shed_frac": round(shed / offered, 4),
        "cb_preempt_rate": round(
            g["preempt_evictions"] / max(1, len(accepted_ids)), 4),
        "cb_goodput_frac": round(
            len(slo_met) / max(1, len(accepted_ids)), 4),
    }
    print(f"# cb overload: {offered} offered / {len(accepted_ids)} "
          f"accepted / {shed} shed, {toks} tokens in {wall:.1f}s "
          f"({out['cb_overload_tok_s']} tok/s), p99 ttft "
          f"{out['cb_overload_p99_ttft_ms']} ms, preempt rate "
          f"{out['cb_preempt_rate']}, goodput "
          f"{out['cb_goodput_frac']}, restarts {sup.restarts}",
          file=sys.stderr)
    return out


def _cb_fleet_bench(on_tpu):
    """Multi-replica serving fleet (ISSUE 11): the cb workload fanned
    across 4 supervised replicas behind the fault-tolerant router,
    with a MID-RUN replica kill hard enough to trip its circuit
    breaker — aggregate delivered tok/s (failover cost included), the
    tail TTFT a routed client sees, the failover latency itself, and
    the ratio vs the SAME workload on one engine. BASELINE.md
    documents the keys."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine, ServingFleet
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler.slo import SLORule
    from paddle_tpu.testing import FaultInjector

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        slots, page, chunk, max_len = 8, 32, 32, 384
        n_req, plen_lo, plen_hi, new_lo, new_hi = 64, 48, 192, 16, 48
        kill_after = 8
    else:
        cfg = LlamaConfig.tiny()
        slots, page, chunk, max_len = 2, 8, 4, 48
        n_req, plen_lo, plen_hi, new_lo, new_hi = 24, 3, 11, 2, 7
        kill_after = 3
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=slots, page_size=page, max_len=max_len,
            decode_chunk=chunk, greedy=True)

    rng = np.random.RandomState(44)
    specs = [(rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(plen_lo, plen_hi + 1)),))
              .astype(np.int32),
              int(rng.randint(new_lo, new_hi + 1)))
             for _ in range(n_req)]

    # single-engine A/B: the SAME workload through one engine (its own
    # warmup) — the denominator of cb_fleet_vs_single
    single = factory()
    single.add_request(specs[0][0], specs[0][1])
    single.run()                       # warmup compiles
    single.reset_gauges()
    t0 = time.perf_counter()
    for p, n in specs:
        single.add_request(p, n)
    sdone = single.run()
    single_wall = max(time.perf_counter() - t0, 1e-9)
    single_toks = sum(len(r.tokens) for r in sdone)
    single_tps = single_toks / single_wall

    # per-tenant SLO accounting (ISSUE 13): two synthetic tenants, a
    # generous TTFT objective (the kill + failover must not break it)
    # and a delivery-success objective — the record stamps worst
    # attainment + alerts fired so the regression sentinel can gate on
    # "we kept our SLOs through the chaos", not just raw tok/s
    fleet = ServingFleet(
        factory, num_replicas=4, max_restarts=1,
        retry_backoff_s=0.01,
        slo_rules=[SLORule("ttft", kind="ttft", threshold_ms=60_000,
                           target=0.9, min_events=5),
                   SLORule("success", kind="success", target=0.9,
                           min_events=5)])
    # warm every replica outside the timed region (compiles)
    for rep in fleet.replicas.values():
        fleet._warm(rep)
    t0 = time.perf_counter()
    with FaultInjector() as fi:
        # replica 1 dies for good after a few steps: supervisor
        # restart, budget exhaustion, breaker, failover — all inside
        # the timed region (the cost IS the metric)
        fi.kill_replica(1, times=10_000, after_steps=kill_after)
        fids = [fleet.submit(p, n, tenant=f"tenant{i % 2}")
                for i, (p, n) in enumerate(specs)]
        done = fleet.run()
    wall = max(time.perf_counter() - t0, 1e-9)
    by = {r.request_id: r for r in done}
    ok = [by[f] for f in fids if by[f].error is None]
    toks = sum(len(r.tokens) for r in ok)
    ttfts = sorted((r.t_first - r.t_arrive) * 1e3
                   for r in ok if r.t_first)
    p99 = ttfts[max(0, int(round(0.99 * (len(ttfts) - 1))))] \
        if ttfts else 0.0
    g = fleet.gauges()
    slo = fleet.slo.summary()
    out = {
        "cb_fleet_tok_s": round(toks / wall, 2),
        "cb_fleet_p99_ttft_ms": round(p99, 2),
        "cb_fleet_failover_ms": round(g["failover_ms_p99"], 2),
        "cb_fleet_vs_single": round(toks / wall / single_tps, 4)
        if single_tps else 0.0,
        # SLO accounting through the chaos (BASELINE.md): worst
        # per-tenant attainment across the declared rules + burn-rate
        # alerts fired — the sentinel gates obs_slo_attainment
        "obs_slo_attainment": round(slo["worst_attainment"], 4),
        "slo_alerts": int(slo["alerts_fired"]),
        "obs_fleet_overhead_frac": round(g["obs_overhead_frac"], 5),
    }
    print(f"# cb fleet: {len(fids)} requests over 4 replicas, "
          f"replica 1 killed mid-run (breaker "
          f"{'open' if g['breaker_open'] else 'CLOSED?'}), "
          f"{toks} tokens in {wall:.1f}s "
          f"({out['cb_fleet_tok_s']} tok/s), p99 ttft "
          f"{out['cb_fleet_p99_ttft_ms']} ms, failover "
          f"{out['cb_fleet_failover_ms']} ms, vs single engine "
          f"x{out['cb_fleet_vs_single']} "
          f"(requeued {g['requeued']}, retries {g['retries']}, "
          f"delivered {len(ok)}/{len(fids)}, slo attainment "
          f"{out['obs_slo_attainment']}, alerts {out['slo_alerts']})",
          file=sys.stderr)
    return out


def _cb_procfleet_bench(on_tpu):
    """Process-backed serving fleet (ISSUE 16): the fleet workload
    over 4 REAL worker processes (``ProcReplica`` spawning ``python -m
    paddle_tpu.inference.worker``), with one worker SIGKILLed mid-run
    hard enough to spend its respawn budget and trip the breaker —
    aggregate delivered tok/s with the wire + failover cost included,
    the routed p99 TTFT, the failover latency, and the ratio vs the
    SAME workload + kill on the in-process fleet (the process
    boundary's all-in cost; ``vs_*`` keys are never gated). The
    survivors then serve a small load-harness trace through an
    ``ApiServer`` mounted on the proc-backed fleet — the front-door
    smoke key. Workers always run the tiny CPU model, even on a TPU
    host: this section measures orchestration (wire RPCs, respawn,
    salvage, reroute), which the accelerator does not change, and N
    worker processes cannot share one chip. BASELINE.md documents the
    keys (and the TPU-host caveat on the in-proc denominator)."""
    import json as _json
    import subprocess
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import (ApiServer,
                                      ContinuousBatchingEngine,
                                      ProcReplica, ServingFleet)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.testing import FaultInjector

    eng_kw = dict(num_slots=2, page_size=8, max_len=48,
                  decode_chunk=4, prefill_chunk=16, greedy=True)
    spec = {"factory": "paddle_tpu.inference.worker:llama_engine",
            "kwargs": dict(model="tiny", num_hidden_layers=1, seed=0,
                           **eng_kw)}
    # kill at the SECOND step: any request costs >= 2 steps, so the
    # budget-spending kill always finds in-flight work to salvage —
    # a later kill can land on a replica whose whole share already
    # finished (the PR-15 kill-smoke lesson), zeroing the failover
    # sample the section exists to price
    n_req, kill_after = 24, 1
    h_req, h_conc = 12, 4

    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def factory():
        return ContinuousBatchingEngine(model, **eng_kw)

    rng = np.random.RandomState(44)
    specs = [(rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(3, 10)),)).astype(np.int32),
              int(rng.randint(2, 7))) for _ in range(n_req)]

    def run_leg(fleet, fi_install):
        for rep in fleet.replicas.values():
            fleet._warm(rep)
        t0 = time.perf_counter()
        with FaultInjector() as fi:
            fi_install(fi)
            fids = [fleet.submit(p, n) for p, n in specs]
            done = fleet.run()
        wall = max(time.perf_counter() - t0, 1e-9)
        by = {r.request_id: r for r in done}
        ok = [by[f] for f in fids if by[f].error is None]
        toks = sum(len(r.tokens) for r in ok)
        ttfts = sorted((r.t_first - r.t_arrive) * 1e3
                       for r in ok if r.t_first)
        p99 = ttfts[max(0, int(round(0.99 * (len(ttfts) - 1))))] \
            if ttfts else 0.0
        return toks / wall, p99, len(ok), len(fids)

    # in-process A/B: the SAME workload + mid-run kill through the
    # in-process fleet — the denominator of cb_procfleet_vs_inproc
    inproc = ServingFleet(factory, num_replicas=4, max_restarts=1,
                          retry_backoff_s=0.01)
    inproc_tps, _, _, _ = run_leg(
        inproc, lambda fi: fi.kill_replica(1, times=10_000,
                                           after_steps=kill_after))

    # worker processes inherit the parent's platform pin; force CPU
    # for the section's whole lifetime so RESPAWNS stay CPU too
    prev_plat = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    fleet = ServingFleet(spec, num_replicas=4, max_restarts=1,
                         retry_backoff_s=0.01,
                         replica_cls=ProcReplica,
                         replica_kwargs=dict(hb_timeout_s=5.0,
                                             respawn_backoff_s=0.01))
    srv = None
    try:
        tps, p99, n_ok, n_all = run_leg(
            fleet, lambda fi: fi.kill_worker(1, times=10_000,
                                             after_steps=kill_after))
        g = fleet.gauges()

        # front-door smoke: the surviving workers behind an ApiServer,
        # driven by the load harness as a separate client process
        srv = ApiServer(fleet).start()
        with tempfile.NamedTemporaryFile(suffix=".json",
                                         delete=False) as tf:
            rep_path = tf.name
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "load_harness.py"),
             "--url", srv.url, "--requests", str(h_req),
             "--concurrency", str(h_conc), "--mode", "closed",
             "--vocab", str(cfg.vocab_size),
             "--prompt-len", "3", "5", "--max-new", "2", "6",
             "--seed", "44", "--report", rep_path],
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"load harness failed: {proc.stderr[-500:]}")
        with open(rep_path) as f:
            report = _json.load(f)
        os.unlink(rep_path)
    finally:
        if srv is not None:
            srv.stop()
        fleet.close()
        if prev_plat is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_plat

    out = {
        "cb_procfleet_tok_s": round(tps, 2),
        "cb_procfleet_p99_ttft_ms": round(p99, 2),
        "cb_procfleet_failover_ms": round(g["failover_ms_p99"], 2),
        "cb_procfleet_vs_inproc": round(tps / inproc_tps, 4)
        if inproc_tps else 0.0,
        "cb_procfleet_http_goodput_frac": round(
            report["goodput_frac"], 4),
    }
    print(f"# cb procfleet: {n_all} requests over 4 process workers, "
          f"worker 1 SIGKILLed mid-run (breaker "
          f"{'open' if g['breaker_open'] else 'CLOSED?'}), "
          f"{out['cb_procfleet_tok_s']} tok/s delivered "
          f"({n_ok}/{n_all} ok, vs in-proc fleet "
          f"x{out['cb_procfleet_vs_inproc']}), p99 ttft "
          f"{out['cb_procfleet_p99_ttft_ms']} ms, failover "
          f"{out['cb_procfleet_failover_ms']} ms, http goodput "
          f"{out['cb_procfleet_http_goodput_frac']} "
          f"({report['completed_ok']}/{report['requests']} ok)",
          file=sys.stderr)
    return out


def _cb_disagg_bench(on_tpu):
    """Disaggregated prefill/decode A/B (ISSUE 17): the named
    ``long_prompt_flood`` trace mix through 2 prefill + 2 decode
    process workers (``DisaggServingFleet``) vs the SAME mix through 4
    colocated process workers — aggregate delivered tok/s with the KV
    migration cost included, the p99 TTFT of the SHORT-chat subset
    (the number disaggregation exists to protect: colocated replicas
    stall short prefills behind long ones and behind resident decode
    turns; prefill-role slots turn over after one prefill), the p99
    migration leg, and the tok/s ratio vs colocated (``vs_*`` keys are
    never gated). Workers always run the tiny CPU model, even on a TPU
    host: the section measures role-aware orchestration (routing,
    KV transfer, slot turnover), which the accelerator does not
    change. BASELINE.md documents the keys."""
    import numpy as np

    from paddle_tpu.inference import (DisaggServingFleet, ProcReplica,
                                      ServingFleet)
    from paddle_tpu.models import LlamaConfig

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from load_harness import build_trace_mix
    finally:
        sys.path.pop(0)

    # geometry fits the mix's long tail: prompts up to 40 tokens + 12
    # new -> max_len 64, a 40-wide prefill bucket for the floods and
    # an 8-wide one so short chats never pay the flood's padding.
    # num_pages leaves headroom for exported-page pins, so a parked
    # migration never blocks the next admission. Each role gets a
    # role-SHAPED program — the provisioning freedom that is the point
    # of disaggregation: prefill replicas keep the 40-wide mixed pass
    # but drop the decode tail they never use (decode_chunk=2), decode
    # replicas drop the 40-wide pass they never use (imported pages
    # re-prefill only short suffixes -> prefill_chunk=8); the
    # colocated baseline must provision one program for BOTH phases
    eng_kw = dict(num_slots=2, page_size=8, max_len=64,
                  num_pages=48, decode_chunk=4,
                  prefill_chunk=40, greedy=True)

    def _spec(**over):
        kw = dict(model="tiny", num_hidden_layers=1, seed=0,
                  **dict(eng_kw, **over))
        return {"factory": "paddle_tpu.inference.worker:llama_engine",
                "kwargs": kw}

    spec = _spec()
    n_req = 128
    cfg = LlamaConfig.tiny()
    mix = build_trace_mix("long_prompt_flood", n_req,
                          vocab=cfg.vocab_size, seed=17)

    def run_leg(fleet):
        try:
            for rep in fleet.replicas.values():
                fleet._warm(rep)
            # workload-shaped warm wave: the sacrificial warm request
            # compiles only the 8-wide bucket; one long prompt per
            # slot compiles the 40-wide pass (and, on the disagg
            # fleet, the KV import + decode-side programs) OUTSIDE
            # the timed region — the A/B measures serving structure,
            # not whose turn 1 pays which XLA compile
            for i in range(8):
                fleet.submit(((np.arange(40) + 97 * i)
                              % cfg.vocab_size).astype(np.int32), 12)
            fleet.run()
            h = getattr(fleet, "_h_migration", None)
            if h is not None:
                h.reset()
            g0 = fleet.gauges()
            t0 = time.perf_counter()
            fids = [fleet.submit(
                np.asarray(it["prompt"], dtype=np.int32),
                int(it["max_new"])) for it in mix]
            done = fleet.run()
            wall = max(time.perf_counter() - t0, 1e-9)
            by = {r.request_id: r for r in done}
            ok = [by[f] for f in fids if by[f].error is None]
            toks = sum(len(r.tokens) for r in ok)
            short = sorted(
                (by[f].t_first - by[f].t_arrive) * 1e3
                for f, it in zip(fids, mix)
                if it["kind"] == "short" and by[f].error is None
                and by[f].t_first)
            p99 = short[max(0, int(round(0.99 * (len(short) - 1))))] \
                if short else 0.0
            g = fleet.gauges()
            g["migrations"] = (g.get("migrations", 0)
                               - g0.get("migrations", 0))
            return toks / wall, p99, len(ok), g
        finally:
            fleet.close()

    # worker processes inherit the parent's platform pin; force CPU
    # for the section's whole lifetime (same rationale as procfleet)
    prev_plat = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        repl_kw = dict(replica_cls=ProcReplica,
                       replica_kwargs=dict(hb_timeout_s=10.0,
                                           respawn_backoff_s=0.01))
        colo_tps, colo_p99, colo_ok, _ = run_leg(
            ServingFleet(spec, num_replicas=4, **repl_kw))
        # role-shaped SLOT provisioning, the other half of the
        # disaggregation win: a prefill slot parks after one token, so
        # a prefill replica can hold 6 slots where a colocated replica
        # — whose slots carry decode residency for a request's whole
        # lifetime — holds 2. num_pages grows with the slot count
        # (6 slots x 64/8 pages + exported pins in flight).
        disagg = DisaggServingFleet(
            _spec(role="prefill", decode_chunk=2, num_slots=6,
                  num_pages=96), num_prefill=2,
            num_decode=0, **repl_kw)
        for _ in range(2):
            disagg.scale_up(
                engine_factory=_spec(role="decode",
                                     prefill_chunk=8),
                warm=False, role="decode")
        tps, p99, n_ok, g = run_leg(disagg)
    finally:
        if prev_plat is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_plat

    out = {
        "cb_disagg_tok_s": round(tps, 2),
        "cb_disagg_p99_ttft_ms": round(p99, 2),
        "cb_disagg_colocated_p99_ttft_ms": round(colo_p99, 2),
        "cb_disagg_migration_ms_p99": round(
            g.get("migration_ms_p99", 0.0), 2),
        "cb_disagg_vs_colocated": round(tps / colo_tps, 4)
        if colo_tps else 0.0,
    }
    print(f"# cb disagg: {n_req} long_prompt_flood requests, "
          f"2 prefill + 2 decode workers "
          f"({g.get('migrations', 0)} migrations, "
          f"{n_ok}/{n_req} ok) {out['cb_disagg_tok_s']} tok/s "
          f"(x{out['cb_disagg_vs_colocated']} vs 4 colocated, "
          f"{colo_ok}/{n_req} ok), short-chat p99 ttft "
          f"{out['cb_disagg_p99_ttft_ms']} ms vs "
          f"{out['cb_disagg_colocated_p99_ttft_ms']} ms colocated, "
          f"migration p99 {out['cb_disagg_migration_ms_p99']} ms",
          file=sys.stderr)
    return out


def _cb_autoscale_bench(on_tpu):
    """SLO-driven autoscaler A/B (ISSUE 19): the seeded ``diurnal``
    and ``flash_crowd`` scenarios through a fleet with the
    :class:`FleetAutoscaler` closing the loop (1..3 replicas) vs the
    SAME schedules through a max-size FIXED fleet (3 replicas pinned).
    The claim on the goodput-vs-chips frontier: goodput and the
    scenarios' own SLO attainment bars hold while the chip-seconds
    bill (the cost model's ready-replica integral on the harness's
    virtual clock) comes in under the fixed fleet's.
    ``autoscale_vs_fixed_chips`` is a vs_* ratio — never gated.
    Always the tiny 1-layer model: the section measures the control
    loop (signals, rules, hysteresis, warm spares, drains), which the
    accelerator does not change. BASELINE.md documents the keys."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      FleetAutoscaler, Overloaded,
                                      ServingFleet)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler.slo import SLORule

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from load_harness import (SCENARIOS, TickClock,
                                  build_scenario, run_fleet_scenario)
    finally:
        sys.path.pop(0)

    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 1
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=2, page_size=8, max_len=48,
            decode_chunk=4, prefill_chunk=16, greedy=True)

    max_r = 3
    ctl_kw = dict(min_replicas=1, max_replicas=max_r,
                  up_cooldown_s=2.0, down_cooldown_s=3.0,
                  queue_high=3.0, queue_low=0.5,
                  down_stable_ticks=3)
    # few fleet turns per tick so the bursts genuinely outrun a lone
    # replica and the controller has to act (same lever as the
    # scenario gate)
    steps = {"diurnal": 1, "flash_crowd": 2}
    goodputs, attains, legs = [], [], []
    chip_auto = chip_fixed = 0.0
    decisions = 0

    for name in ("diurnal", "flash_crowd"):
        sc = SCENARIOS[name]
        schedule = build_scenario(name, vocab=cfg.vocab_size, seed=23)
        rules = [SLORule(**d) for d in sc["slo_rules"]]

        # the fixed leg: max-size fleet, no controller
        fleet = ServingFleet(factory, max_r, slo_rules=rules,
                             hedge_delay_s=None, seed=0)
        clock = TickClock()
        try:
            fixed = run_fleet_scenario(
                fleet, schedule, clock=clock, shed_exc=Overloaded,
                steps_per_tick=steps[name])
        finally:
            fleet.close()
        chip_fixed += max_r * clock.t

        # the autoscaled leg: start at the floor, let the loop drive
        fleet = ServingFleet(factory, 1, slo_rules=rules,
                             hedge_delay_s=None, seed=0)
        clock = TickClock()
        ctl = FleetAutoscaler(fleet, now_fn=clock, **ctl_kw)
        try:
            rep = run_fleet_scenario(
                fleet, schedule, autoscaler=ctl, clock=clock,
                shed_exc=Overloaded, steps_per_tick=steps[name])
        finally:
            fleet.close()
        goodputs.append(rep["goodput_frac"])
        attains.append(rep["slo"]["worst_attainment"])
        chip_auto += rep["chip_seconds"]
        decisions += int(
            fleet.metrics.counter("autoscale/decisions").value)
        legs.append((name, rep, fixed))

    out = {
        # the gated pair: worst leg carries the claim
        "autoscale_goodput_frac": round(min(goodputs), 4),
        "autoscale_slo_attainment": round(min(attains), 4),
        # lower-is-better / diagnostics: never gated
        "autoscale_chip_seconds": round(chip_auto, 2),
        "autoscale_decisions": decisions,
        "autoscale_vs_fixed_chips": round(chip_auto / chip_fixed, 4)
        if chip_fixed else 0.0,
    }
    for name, rep, fixed in legs:
        print(f"# cb autoscale {name}: goodput "
              f"{rep['goodput_frac']} (fixed {fixed['goodput_frac']}),"
              f" attainment {rep['slo']['worst_attainment']}, peak "
              f"{rep['peak_ready']} ready, chip-s "
              f"{rep['chip_seconds']}", file=sys.stderr)
    print(f"# cb autoscale: attainment "
          f"{out['autoscale_slo_attainment']}, chip-s "
          f"{out['autoscale_chip_seconds']} "
          f"(x{out['autoscale_vs_fixed_chips']} vs fixed "
          f"{max_r}-replica fleet), {decisions} decisions",
          file=sys.stderr)
    return out


def _cb_prefix_bench(on_tpu):
    """Shared-prefix storm (ISSUE 12): the acceptance A/B for
    radix-tree prefix caching — N requests sharing one long prefix
    (>= 64 requests x >= 512 prefix tokens on TPU), run COLD (cache
    empty; it self-populates mid-run, which is exactly the production
    cold shape) then WARM (prefix resident) on ONE engine, compiled
    programs kept and the cache dropped in between. Reports hit rate,
    the fraction of prefill tokens skipped, p99 TTFT cold vs warm, and
    a token-identity check against a cache-OFF engine on the same
    workload. BASELINE.md documents the keys."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        slots, page, chunk, max_len = 8, 32, 32, 768
        n_req, prefix_len, tail_hi, n_new = 64, 512, 64, 32
        prefill_chunk = 256
    else:
        cfg = LlamaConfig.tiny()
        slots, page, chunk, max_len = 2, 8, 4, 48
        n_req, prefix_len, tail_hi, n_new = 12, 24, 5, 4
        prefill_chunk = 32
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    rng = np.random.RandomState(55)
    prefix = rng.randint(0, cfg.vocab_size,
                         (prefix_len,)).astype(np.int32)
    specs = []
    for _ in range(n_req):
        tail = rng.randint(0, cfg.vocab_size,
                           (int(rng.randint(0, tail_hi)),)
                           ).astype(np.int32)
        specs.append((np.concatenate([prefix, tail]), n_new))
    prompt_tokens = sum(len(p) for p, _ in specs)

    def make_engine(**kw):
        return ContinuousBatchingEngine(
            model, num_slots=slots, page_size=page, max_len=max_len,
            decode_chunk=chunk, prefill_chunk=prefill_chunk,
            greedy=True, **kw)

    def storm(e):
        """One timed storm pass; returns (tok_s, p99_ttft_ms,
        gauges, streams-by-spec-index)."""
        e.reset_gauges()
        t0 = time.perf_counter()
        ids = [e.add_request(p, n) for p, n in specs]
        done = e.run()
        wall = max(time.perf_counter() - t0, 1e-9)
        by = {r.request_id: r for r in done}
        toks = sum(len(r.tokens) for r in done)
        ttfts = sorted((by[i].t_first - by[i].t_arrive) * 1e3
                       for i in ids if by[i].t_first)
        p99 = ttfts[max(0, int(round(0.99 * (len(ttfts) - 1))))] \
            if ttfts else 0.0
        return (toks / wall, p99, e.gauges(),
                [by[i].tokens for i in ids])

    eng = make_engine()
    eng.add_request(specs[0][0], 2)
    eng.run()                            # warmup: compiles
    eng.reset_prefix_cache()             # drop the warmup's pages
    cold_tps, cold_p99, cold_g, cold_streams = storm(eng)
    warm_tps, warm_p99, warm_g, warm_streams = storm(eng)
    # token-identity oracle: the SAME storm, prefix cache OFF
    off = make_engine(prefix_cache=False)
    off.add_request(specs[0][0], 2)
    off.run()
    _, off_p99, _, off_streams = storm(off)
    identical = warm_streams == off_streams \
        and cold_streams == off_streams
    saved_frac = warm_g["prefix_cache_tokens_saved"] / prompt_tokens
    out = {
        "cb_prefix_warm_tok_s": round(warm_tps, 2),
        "cb_prefix_cold_tok_s": round(cold_tps, 2),
        "cb_prefix_hit_rate": round(warm_g["prefix_cache_hit_rate"],
                                    4),
        "cb_prefix_tokens_saved_frac": round(saved_frac, 4),
        "cb_prefix_p99_ttft_ms_warm": round(warm_p99, 2),
        "cb_prefix_p99_ttft_ms_cold": round(cold_p99, 2),
        "cb_prefix_p99_ttft_ms_off": round(off_p99, 2),
        "cb_prefix_cow_forks": int(warm_g["prefix_cache_cow_forks"]),
        "cb_prefix_identical": bool(identical),
    }
    print(f"# cb prefix storm: {n_req} requests x {prefix_len}-token "
          f"shared prefix, warm {out['cb_prefix_warm_tok_s']} tok/s "
          f"vs cold {out['cb_prefix_cold_tok_s']} (cache off: "
          f"{off_p99:.1f}ms p99 ttft), hit rate "
          f"{out['cb_prefix_hit_rate']}, prefill tokens saved "
          f"{out['cb_prefix_tokens_saved_frac'] * 100:.0f}%, p99 ttft "
          f"{out['cb_prefix_p99_ttft_ms_warm']}ms warm vs "
          f"{out['cb_prefix_p99_ttft_ms_cold']}ms cold, "
          f"{out['cb_prefix_cow_forks']} cow forks, greedy streams "
          f"{'IDENTICAL' if identical else 'DIVERGED!'} vs cache-off",
          file=sys.stderr)
    return out


def _cb_quant_bench(on_tpu, autotune=False):
    """Quantized serving A/B (ISSUE 20): int8 paged-KV + weight-only
    int8 against the full-precision engine on one custom model
    (hidden 256 / head_dim 64 — wide enough that the per-token f32
    scale column amortizes: page-byte ratio 2d/(d+4) ~ 1.88 under
    bf16 pools, ~3.56 under the CPU smoke's f32 pools).

    Legs:
    - capacity (the headline): the ``capacity_probe`` trace mix —
      every request carries a real prompt AND decode budget, so page
      demand is the binding constraint — through a base-precision
      engine and an int8-KV engine holding the SAME page-pool byte
      budget (the int8 page count is derived from the engines' own
      pool-byte gauges, so the budget can never drift from the real
      allocation). ``cb_quant_capacity_ratio`` is the peak-concurrent-
      residency ratio; admission reserves a request's whole-lifetime
      pages, so peak residency IS page capacity. ``*_ratio`` keys are
      never regression-gated (they move with the host's pool dtype);
      tok/s and the accuracy keys are.
    - accuracy: greedy token-level top-1 agreement vs a same-weights
      full-precision engine, for int8-KV and for weight-only int8,
      plus a teacher-forced perplexity delta for the weight path (KV
      quantization does not touch the cacheless forward).
    - residency: prefix-cache pages resident after the same storm at
      equal bytes — more pages per byte keeps more warm prefix.
    - wire: one exported prefill migration, base vs int8, through the
      disagg JSON codec — quantized pages ship natively (no
      dequant->requant), so wire bytes drop by ~the page-byte ratio.

    autotune=True additionally sweeps the QUANTIZED ragged-attention
    surface at this bench's geometry (the ``kvq`` shape-sig component
    keeps its winner apart from bf16 entries) and commits the winner
    to the tuning cache. BASELINE.md documents the keys."""
    import json as _json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.disagg import kv_payload_to_wire
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.quant import quantize_for_serving

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from load_harness import build_trace_mix
    finally:
        sys.path.pop(0)

    def make_cfg(**over):
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=512, max_position_embeddings=64, **over)
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        return cfg

    slots, page, max_len = 16, 8, 40
    base_pages = 17                    # 16 usable + trash page 0
    n_req = 64 if on_tpu else 36
    n_acc, acc_new = (8, 10) if on_tpu else (6, 8)

    paddle.seed(0)
    model = LlamaForCausalLM(make_cfg())
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    vocab = model.config.vocab_size

    def make_engine(m=None, pages=None, nslots=slots, **kw):
        return ContinuousBatchingEngine(
            m if m is not None else model, num_slots=nslots,
            page_size=page, max_len=max_len, num_pages=pages,
            decode_chunk=4, prefill_chunk=16, greedy=True, **kw)

    # equal-byte provisioning from the engines' OWN pool-byte gauges
    base_eng = make_engine(pages=base_pages)
    base_bytes = base_eng.gauges()["kv_quant_pool_bytes"]
    probe = make_engine(pages=base_pages, nslots=1, kv_quant="int8")
    gq = probe.gauges()
    per_page_q = (gq["kv_quant_pool_bytes"]
                  + gq["kv_quant_scale_pool_bytes"]) / base_pages
    q_pages = int(base_bytes // per_page_q)
    del probe
    quant_eng = make_engine(pages=q_pages, kv_quant="int8")

    mix = build_trace_mix("capacity_probe", n_req, vocab=vocab,
                          seed=20)

    def storm(e):
        e.add_request(np.asarray(mix[0]["prompt"], np.int32), 2)
        e.run()                      # warmup: compiles off the clock
        e.reset_prefix_cache()       # drop the warmup's pages
        e.reset_gauges()
        t0 = time.perf_counter()
        ids = [e.add_request(np.asarray(it["prompt"], np.int32),
                             int(it["max_new"])) for it in mix]
        done = e.run()
        wall = max(time.perf_counter() - t0, 1e-9)
        by = {r.request_id: r for r in done}
        ok = [by[i] for i in ids if by[i].error is None]
        toks = sum(len(r.tokens) for r in ok)
        # peak concurrent residency by interval overlap: a slot holds
        # its whole-lifetime page reservation from t_admit to t_done
        evs = sorted([(r.t_admit, 1) for r in ok if r.t_admit]
                     + [(r.t_done, -1) for r in ok if r.t_admit])
        cur = peak = 0
        for _, step in evs:
            cur += step
            peak = max(peak, cur)
        return toks / wall, peak, e.gauges()

    base_tps, base_peak, base_g = storm(base_eng)
    quant_tps, quant_peak, quant_g = storm(quant_eng)
    res_ratio = quant_g["prefix_cache_pages"] / \
        max(base_g["prefix_cache_pages"], 1)

    # accuracy: greedy token streams vs the full-precision engine on
    # the SAME weights (fresh small engines so pool pressure cannot
    # preempt and muddy the comparison)
    rng = np.random.RandomState(77)
    prompts = [rng.randint(0, vocab,
                           (int(rng.randint(6, 13)),)).astype(np.int32)
               for _ in range(n_acc)]

    def greedy_streams(e):
        ids = [e.add_request(p, acc_new) for p in prompts]
        done = e.run()
        by = {r.request_id: r for r in done}
        return [by[i].tokens for i in ids]

    def agreement(a, b):
        num = den = 0
        for x, y in zip(a, b):
            den += max(len(x), len(y))
            num += sum(1 for u, w in zip(x, y) if u == w)
        return num / max(den, 1)

    oracle = greedy_streams(make_engine(nslots=4))
    kv_top1 = agreement(oracle,
                        greedy_streams(make_engine(nslots=4,
                                                   kv_quant="int8")))

    paddle.seed(0)                     # identical init -> same weights
    wmodel = LlamaForCausalLM(make_cfg(
        weight_quant="weight_only_int8"))
    if on_tpu:
        wmodel.to(dtype="bfloat16")
    wmodel.eval()
    wstats = quantize_for_serving(wmodel)   # engine ctor then no-ops
    w_top1 = agreement(oracle, greedy_streams(make_engine(m=wmodel,
                                                          nslots=4)))
    wbytes_ratio = (wstats["bytes"] + wstats["bytes_saved"]) \
        / max(wstats["bytes"], 1)

    def mean_nll(m):
        rs = np.random.RandomState(88)
        tot = cnt = 0
        for _ in range(3):
            seq = rs.randint(0, vocab, (1, 24)).astype(np.int32)
            logits = np.asarray(m(Tensor(seq))._data, np.float32)[0]
            x = logits[:-1] - logits[:-1].max(-1, keepdims=True)
            lse = np.log(np.exp(x).sum(-1))
            tok = seq[0, 1:]
            tot += float((lse - x[np.arange(len(tok)), tok]).sum())
            cnt += len(tok)
        return tot / cnt
    ppl_delta = float(np.exp(mean_nll(wmodel)) - np.exp(mean_nll(model)))

    # wire: the disagg codec ships quantized pages natively — measure
    # one exported prefill migration base vs int8
    def wire_bytes(kvq):
        e = make_engine(nslots=2, role="prefill", kv_quant=kvq)
        e.add_request(prompts[0], 4)
        e.run()
        _, payload = e.take_migrations()[0]
        return len(_json.dumps(kv_payload_to_wire(payload)))

    wire_ratio = wire_bytes("none") / max(wire_bytes("int8"), 1)

    out = {
        "cb_quant_tok_s": round(quant_tps, 2),
        "cb_quant_base_tok_s": round(base_tps, 2),
        "cb_quant_capacity_ratio": round(
            quant_peak / max(base_peak, 1), 4),
        "cb_quant_peak_seqs": int(quant_peak),
        "cb_quant_base_peak_seqs": int(base_peak),
        "cb_quant_pages": int(q_pages - 1),
        "cb_quant_base_pages": int(base_pages - 1),
        "cb_quant_kv_bits": int(quant_g["kv_quant_bits"]),
        "cb_quant_top1_agreement": round(kv_top1, 4),
        "cb_quant_weight_top1_agreement": round(w_top1, 4),
        "cb_quant_ppl_delta": round(ppl_delta, 4),
        "cb_quant_prefix_residency_ratio": round(res_ratio, 4),
        "cb_quant_weight_bytes_ratio": round(wbytes_ratio, 4),
        "cb_quant_kv_wire_bytes_ratio": round(wire_ratio, 4),
    }

    if autotune:
        # sweep the quantized ragged surface at this bench's kernel
        # geometry; the "kvq" sig component keeps the winner apart
        # from bf16 entries (TrialEngine persists it to the cache)
        from paddle_tpu.tuner.engine import TrialEngine
        from paddle_tpu.tuner.sweeps import (ensure_builtin_surfaces,
                                             ragged_attention_builder)
        ensure_builtin_surfaces()
        d = model.config.hidden_size // model.config.num_attention_heads
        shape = {"c": 4, "pages": -(-max_len // page), "page": page,
                 "d": d, "kvq": 1}
        dtype = next(iter(model.parameters()))._data.dtype
        res = TrialEngine(warmup=1, repeats=3).search(
            "ragged_paged_attention", shape,
            ragged_attention_builder(dtype=str(dtype)),
            dtype=str(dtype))
        out["tuned_ragged_quant"] = {
            "config": dict(res.best_config),
            "shape_sig": res.shape_sig,
            "cached_hit": bool(res.cached_hit),
            "median_ms": res.best_ms}
        print(f"# quant autotune: {res.best_config} @ "
              f"{res.shape_sig} ({'cache hit' if res.cached_hit else f'{len(res.trials)} trials'})",
              file=sys.stderr)

    print(f"# cb quant: capacity x{out['cb_quant_capacity_ratio']} "
          f"({out['cb_quant_peak_seqs']} vs "
          f"{out['cb_quant_base_peak_seqs']} peak seqs at "
          f"{out['cb_quant_pages']} vs {out['cb_quant_base_pages']} "
          f"equal-byte pages), {out['cb_quant_tok_s']} tok/s (base "
          f"{out['cb_quant_base_tok_s']}), top1 agreement kv "
          f"{out['cb_quant_top1_agreement']} / weights "
          f"{out['cb_quant_weight_top1_agreement']} (ppl delta "
          f"{out['cb_quant_ppl_delta']:+.3f}), prefix residency "
          f"x{out['cb_quant_prefix_residency_ratio']}, weight bytes "
          f"x{out['cb_quant_weight_bytes_ratio']}, kv wire bytes "
          f"x{out['cb_quant_kv_wire_bytes_ratio']}", file=sys.stderr)
    return out


def _cb_http_bench(on_tpu):
    """HTTP front door overhead (ISSUE 15): the load harness drives
    the OpenAI-compatible API server (tools/load_harness.py as a
    SEPARATE process — a real client, not an in-process shortcut)
    against an engine-backed ApiServer, next to the SAME workload
    pushed straight into an identically configured engine. Interleaved
    best-of-N on both legs because single-core boxes drift; the ratio
    is the front door's all-in cost (asyncio sockets, SSE framing,
    pump bridging, AND the client's own parsing — which shares the
    engine's core when there is only one). BASELINE.md documents the
    keys and the single-core caveat."""
    import json as _json
    import subprocess
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ApiServer, ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig.llama_1b()
        slots, page, chunk, max_len = 8, 32, 32, 384
        n_req, conc, new_lo, new_hi = 64, 24, 128, 192
        sse_chunk, reps = 32, 2
    else:
        cfg = LlamaConfig.tiny()
        slots, page, chunk, max_len = 4, 8, 4, 128
        n_req, conc, new_lo, new_hi = 48, 16, 80, 100
        sse_chunk, reps = 32, 3
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()

    def factory():
        return ContinuousBatchingEngine(
            model, num_slots=slots, page_size=page, max_len=max_len,
            decode_chunk=chunk, prefill_chunk=16, greedy=True)

    rng = np.random.RandomState(44)
    specs = [(rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(3, 6)),)).astype(np.int32),
              int(rng.randint(new_lo, new_hi + 1)))
             for _ in range(n_req)]

    def warm(e):
        for p, n in specs[:8]:
            e.add_request(p, n)
        e.run()

    direct = factory()
    warm(direct)
    served = factory()
    warm(served)
    srv = ApiServer(served, stream_chunk_tokens=sse_chunk).start()

    def direct_once():
        t0 = time.perf_counter()
        for p, n in specs:
            direct.add_request(p, n)
        done = direct.run()
        wall = max(time.perf_counter() - t0, 1e-9)
        return sum(len(r.tokens) for r in done) / wall

    def http_once():
        with tempfile.NamedTemporaryFile(
                suffix=".json", delete=False) as tf:
            rep_path = tf.name
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "load_harness.py"),
             "--url", srv.url, "--requests", str(n_req),
             "--concurrency", str(conc), "--mode", "closed",
             "--vocab", str(cfg.vocab_size),
             "--prompt-len", "3", "5",
             "--max-new", str(new_lo), str(new_hi),
             "--prefix-frac", "0.25", "--prefix-len", "4",
             "--tenants", "tenant0,tenant1",
             "--seed", "44", "--report", rep_path],
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"load harness failed: {proc.stderr[-500:]}")
        with open(rep_path) as f:
            report = _json.load(f)
        os.unlink(rep_path)
        return report

    try:
        direct_tps = 0.0
        best = None
        for _ in range(reps):
            direct_tps = max(direct_tps, direct_once())
            rep = http_once()
            if best is None or rep["tok_s"] > best["tok_s"]:
                best = rep
    finally:
        srv.stop()

    out = {
        "cb_http_tok_s": round(best["tok_s"], 2),
        "cb_http_p99_ttft_ms": round(best["ttft_ms_p99"], 2),
        "cb_http_goodput_frac": round(best["goodput_frac"], 4),
        "cb_http_vs_engine": round(best["tok_s"] / direct_tps, 4)
        if direct_tps else 0.0,
    }
    print(f"# cb http: {n_req} SSE streams x{conc} concurrent through "
          f"the front door, {out['cb_http_tok_s']} tok/s delivered "
          f"(direct engine {direct_tps:.1f}, "
          f"x{out['cb_http_vs_engine']}), p99 ttft "
          f"{out['cb_http_p99_ttft_ms']} ms, goodput "
          f"{out['cb_http_goodput_frac']}, "
          f"{best['completed_ok']}/{best['requests']} ok, "
          f"errors {best['errors'] or '{}'}",
          file=sys.stderr)
    return out


def _moe_bench_config(on_tpu):
    """The BASELINE config-5 bench shape, shared by the MoE train
    section and the breakdown section (attribution fractions are only
    meaningful on the config whose MFU they explain)."""
    import dataclasses

    from paddle_tpu.models import Qwen2MoeConfig

    if on_tpu:
        cfg = Qwen2MoeConfig(
            vocab_size=32000, hidden_size=1024, num_hidden_layers=12,
            num_attention_heads=8, num_key_value_heads=4,
            intermediate_size=2816, max_position_embeddings=4096,
            rope_theta=10000.0, num_experts=16, num_experts_per_tok=2,
            moe_intermediate_size=1408,
            shared_expert_intermediate_size=2816,
            capacity_factor=2.0, scan_layers=False,
            # dropless grouped-matmul dispatch (Pallas): kills the
            # cf=2.0 capacity padding (2x executed expert FLOPs) for
            # ~12% tile padding. Measured round 5: 235 ms/step, 38.3%
            # MFU vs 34.6-37.3 capacity
            moe_dropless=True,
            use_recompute=True,
            # remat dose: every 2nd layer saves its activations whole —
            # fs=1 / batch 6-8 still OOM 16GB even dropless (measured)
            full_save_interval=2,
            # aux folded out: the per-layer aux attribute cannot cross
            # the recompute boundary (see qwen2.py); router still trains
            # through the dispatch gradient
            router_aux_loss_coef=0.0)
        # batch 8 OOMs 16GB: the un-rematerialized expert intermediates
        # ([E, C, moe_inter] per layer) dominate activation memory
        return cfg, 4, 2048
    cfg = dataclasses.replace(Qwen2MoeConfig.tiny(), scan_layers=False)
    return cfg, 2, 64


def _moe_train_bench(on_tpu, dev):
    """MoE train MFU (BASELINE config 5: Qwen2-MoE shape, chip-sized).

    MFU counts ACTIVATED FLOPs: 6·N_active·tokens + the S² attention
    term, where N_active replaces each layer's E-expert bank with the
    k experts a token actually visits (router + shared expert + attn
    params all included). Dispatch runs the index gather/scatter path
    (ops/moe.py), so expert matmuls dominate the step, not routing."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Qwen2MoeForCausalLM

    cfg, batch, seq = _moe_bench_config(on_tpu)
    steps, warmup = (8, 3) if on_tpu else (3, 1)

    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model.to(dtype="bfloat16")
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)).astype(np.int64))

    @paddle.jit.to_static
    def fwd_bwd(ids):
        _, loss = model(ids, labels=ids)
        loss.backward()
        gsum = None
        for p in model.parameters():
            if p.grad is not None:
                s = p.grad.astype("float32").sum()
                gsum = s if gsum is None else gsum + s
        for p in model.parameters():
            p.clear_grad()
        return loss, gsum

    step_ids = [paddle.to_tensor(np.roll(np.asarray(ids.numpy()), i,
                                         axis=1))
                for i in range(steps)]
    for _ in range(warmup):
        loss, gsum = fwd_bwd(ids)
    float(loss.item())

    t0 = time.perf_counter()
    acc = None
    for i in range(steps):
        loss, gsum = fwd_bwd(step_ids[i])
        acc = loss if acc is None else acc + loss
    float(acc.item())
    dt = (time.perf_counter() - t0) / steps

    tokens = batch * seq
    n_total = sum(p.size for p in model.parameters())
    L, d = cfg.num_hidden_layers, cfg.hidden_size
    per_expert = 3 * d * cfg.moe_intermediate_size
    n_active = n_total - L * (cfg.num_experts
                              - cfg.num_experts_per_tok) * per_expert
    flops_per_step = 6.0 * n_active * tokens \
        + 12.0 * L * batch * seq * seq * d
    mfu = _mfu(flops_per_step, dt, dev)
    tok_per_s = tokens / dt
    print(f"# moe train: step {dt*1000:.1f} ms, params {n_total/1e9:.3f}B "
          f"({n_active/1e9:.3f}B active), MFU {_pct(mfu)}, "
          f"loss {float(loss.item()):.3f}", file=sys.stderr)
    return n_total, tok_per_s, mfu


def _moe_breakdown_bench(on_tpu, dev):
    """Per-section attribution of the MoE train step (profiler
    subsystem): gating / sort / a2a / expert-matmul / other via
    compiled-variant ablation (paddle_tpu.profiler.moe_step_breakdown),
    with per-section MFU + roofline columns. This is the table VERDICT
    r5 demand 2 asked for before the next MoE tuning round — the ~60%
    non-matmul step time, attributed. Returns (breakdown_dict,
    chrome_trace_path)."""
    import os

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Qwen2MoeForCausalLM
    from paddle_tpu.profiler import moe_step_breakdown

    cfg, batch, seq = _moe_bench_config(on_tpu)
    # each ablation variant is a fresh compile (~5 programs); keep the
    # timed loop short — attribution needs deltas, not tight CIs
    steps, warmup = (3, 1) if on_tpu else (2, 1)

    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model.to(dtype="bfloat16")
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)).astype(np.int64))
    bd = moe_step_breakdown(model, ids, steps=steps, warmup=warmup)
    trace_path = os.path.join(
        os.environ.get("PADDLE_PROFILER_LOG_DIR", "./profiler_log"),
        "moe_breakdown_trace.json")
    bd.export_chrome_trace(trace_path)
    print("# moe breakdown: step "
          f"{bd.step_ms:.1f} ms; " + "  ".join(
              f"{r['section']}={r['frac'] * 100:.1f}%"
              + (f" (MFU {r['mfu'] * 100:.1f}%)"
                 if r.get("mfu") is not None else "")
              for r in bd.rows), file=sys.stderr)
    return bd.to_dict(), trace_path


def _moe_decode_bench(on_tpu):
    """DeepSeek-V2 greedy decode through the MLA LATENT KV cache
    (the memory-side point of MLA: the cache holds [B, T, R] latents
    + rope keys instead of full per-head K/V)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM

    if on_tpu:
        cfg = DeepseekV2Config(
            vocab_size=32000, hidden_size=1024, num_hidden_layers=12,
            num_attention_heads=16, q_lora_rank=384, kv_lora_rank=256,
            qk_nope_head_dim=64, qk_rope_head_dim=32, v_head_dim=64,
            intermediate_size=2816, moe_intermediate_size=704,
            n_routed_experts=16, n_shared_experts=2,
            num_experts_per_tok=2, first_k_dense_replace=1,
            routed_scaling_factor=1.0, norm_topk_prob=True,
            max_position_embeddings=2048)
        batch, prompt, n_new = 8, 128, 256
    else:
        cfg = DeepseekV2Config.tiny()
        batch, prompt, n_new = 2, 8, 8

    paddle.seed(0)
    model = DeepseekV2ForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    ids = paddle.to_tensor(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int64))

    def run(n, prompt_t):
        out, _ = model.generate(prompt_t, max_new_tokens=n,
                                decode_strategy="greedy_search",
                                eos_token_id=None, pad_token_id=0)
        return int(out[0, -1].item())

    base = np.asarray(ids.numpy())
    prompts = [paddle.to_tensor(np.roll(base, i + 1, axis=1))
               for i in range(5)]
    run(n_new, ids)
    run(4, prompts[0])

    def timed(n, prompt_t):
        t0 = time.perf_counter()
        run(n, prompt_t)
        return time.perf_counter() - t0

    dt_long = min(timed(n_new, prompts[1]), timed(n_new, prompts[2]))
    dt_short = min(timed(4, prompts[3]), timed(4, prompts[4]))
    per_tok = max(dt_long - dt_short, 1e-9) / (n_new - 4)
    tok_per_s = batch / per_tok
    print(f"# moe decode (MLA latent cache): {per_tok*1000:.2f} "
          f"ms/token/batch, {tok_per_s:.0f} tokens/s (batch {batch})",
          file=sys.stderr)
    return tok_per_s


def _autotune_bench(on_tpu):
    """--autotune mode: sweep the kernel tunable surfaces at THIS
    bench's workload shapes through the trial engine and emit
    ``tuned_*`` record keys (format reserved in BASELINE.md). Runs
    BEFORE the train/moe sections so the committed winners feed them
    (the kernels consult the cache at trace time). The default config
    is always in the trial table (default-first grid order), so the
    tuned pick matches or beats the static defaults by construction —
    ``vs_default`` reports the ratio. Resumable: every finished
    (surface, shape) key is already committed atomically; a re-run
    skips it."""
    from paddle_tpu import tuner
    from paddle_tpu.tuner import sweeps

    sweeps.ensure_builtin_surfaces()
    engine = tuner.TrialEngine(warmup=2 if on_tpu else 1,
                               repeats=5 if on_tpu else 2)
    if on_tpu:
        # the MoE bench bank (config 5: d 1024, moe_inter 1408, E 16,
        # rows = batch*seq*k) and the llama train attention shape
        jobs = [
            ("grouped_matmul", {"d": 1024, "h": 1408, "E": 16},
             sweeps.grouped_matmul_builder(rows=16384), 12),
            ("grouped_matmul", {"d": 1408, "h": 1024, "E": 16},
             sweeps.grouped_matmul_builder(rows=16384), 12),
            ("flash_attention", {"sq": 2048, "sk": 2048, "d": 128},
             sweeps.flash_attention_builder(batch=2, heads=20), 8),
            # the training-kernel suite (ISSUE 8) at the 2.4B train
            # bench geometry — swept BEFORE the train sections so the
            # committed winners feed the compiled fit step
            ("rms_norm_residual", {"d": 2560},
             sweeps.rms_norm_residual_builder(rows=4096), 5),
            ("swiglu", {"h": 6912},
             sweeps.swiglu_builder(rows=4096), 9),
            ("fused_ce", {"d": 2560, "v": 32000},
             sweeps.fused_ce_builder(rows=4096), 4),
            # the cb section's unified batching-step kernel at its v5e
            # bench geometry (llama_1b: chunk 32, 12 x 32-token pages,
            # head_dim 128, 16:8 GQA) — swept BEFORE the cb section so
            # the committed winner feeds the engine's traced kernel
            ("ragged_paged_attention",
             {"c": 32, "pages": 12, "page": 32, "d": 128},
             sweeps.ragged_attention_builder(slots=8, heads=16,
                                             kv_heads=8), 10),
        ]
    else:
        jobs = [
            ("grouped_matmul", {"d": 64, "h": 128, "E": 4},
             sweeps.grouped_matmul_builder(rows=1024), 3),
            ("flash_attention", {"sq": 128, "sk": 128, "d": 64},
             sweeps.flash_attention_builder(batch=1, heads=2), 2),
            ("rms_norm_residual", {"d": 128},
             sweeps.rms_norm_residual_builder(rows=256), 2),
            ("swiglu", {"h": 256},
             sweeps.swiglu_builder(rows=256), 2),
            ("fused_ce", {"d": 64, "v": 1024},
             sweeps.fused_ce_builder(rows=256), 2),
            ("ragged_paged_attention",
             {"c": 8, "pages": 4, "page": 8, "d": 16},
             sweeps.ragged_attention_builder(slots=2, heads=4,
                                             kv_heads=2), 2),
        ]

    out = {"tuned_cache_path": engine.cache.path,
           "tuned_backend": engine.backend}
    for surface, shape, builder, max_trials in jobs:
        res = engine.search(surface, shape, builder,
                            max_trials=max_trials)
        entry = {"config": res.best_config,
                 "median_ms": None if res.best_ms is None
                 else round(res.best_ms, 4),
                 "shape_sig": res.shape_sig,
                 "representative": res.representative,
                 "cached_hit": res.cached_hit,
                 # the static default can be INVALID at a shape (e.g.
                 # flash 256/512 at sq=128 smoke shapes): the grid
                 # drops it and no default trial exists — flagged, not
                 # silently absent (BASELINE.md key reservation)
                 "default_timed": False}
        default = tuner.get_surface(surface).default
        for cfg, ms in res.trials:
            if cfg == default:
                entry["default_timed"] = True
                entry["default_ms"] = round(ms, 4)
                if res.best_ms:
                    entry["vs_default"] = round(ms / res.best_ms, 4)
                break
        key = f"tuned_{surface}_{res.shape_sig.replace(',', '_')}"
        out[key] = entry
        print(f"# autotune {surface} @ {res.shape_sig}: "
              f"{entry['config']}"
              + (f" {entry['median_ms']:.2f} ms" if entry["median_ms"]
                 else "")
              + (f" (default {entry['default_ms']:.2f} ms, "
                 f"x{entry['vs_default']:.3f})"
                 if "default_ms" in entry else "")
              + (" [cached]" if res.cached_hit else "")
              + ("" if res.representative
                 else " [NON-REPRESENTATIVE backend]"),
              file=sys.stderr)
    return out


def _emit_record(record, path=None):
    """Print the running record line AND (when ``path`` is set) flush
    it to disk with the atomic stage-then-rename protocol. Called
    after EVERY completed section: a round that times out or dies on a
    backend outage mid-run (BENCH_r04/r05 left nothing parseable)
    still leaves a complete JSON file carrying every section measured
    so far, which tools/check_bench_regression.py compares key-by-key
    against the trajectory."""
    line = json.dumps(record)
    print(line, flush=True)
    if path:
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                f.write(line + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:    # the flush is telemetry durability,
            print(f"# record flush to {path} failed: {e}",
                  file=sys.stderr)    # never a bench failure
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _timed_section(what, fn):
    """Run a bench section, logging wall time to stderr (budget telemetry:
    round-4's record never printed because the sections overran the
    driver's limit — per-section times make the budget auditable)."""
    t0 = time.perf_counter()
    try:
        return fn()
    finally:
        print(f"# [{what}: {time.perf_counter() - t0:.0f}s]",
              file=sys.stderr)


def main():
    import argparse

    import jax

    ap = argparse.ArgumentParser(description="paddle_tpu driver bench")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep kernel tunable surfaces at the bench "
                         "shapes first (paddle_tpu.tuner) and emit "
                         "tuned_* record keys; winners persist to the "
                         "tuning cache and feed the timed sections")
    ap.add_argument("--record-out", default=os.environ.get(
                        "PADDLE_BENCH_RECORD"),
                    help="atomically rewrite the running record to "
                         "this file after every completed section — a "
                         "timed-out round leaves a parseable partial "
                         "record (also via $PADDLE_BENCH_RECORD)")
    args, _unknown = ap.parse_known_args()
    rec_out = args.record_out

    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    # no retry: a missing chip fails here, at once
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    import gc
    suffix = "" if on_tpu else "_cpu_smoke"
    tuned = {}
    if args.autotune:
        # before the timed sections: committed winners feed them (the
        # kernels read the cache at trace time); a sweep failure must
        # never sink the headline metrics
        try:
            tuned = _timed_section(
                "autotune", lambda: _autotune_bench(on_tpu))
        except Exception as e:
            print(f"# autotune bench failed: {e!r}", file=sys.stderr)
            tuned = {}
    # The running record is re-printed after EVERY completed section:
    # whichever complete JSON line is last when the driver's time limit
    # hits carries everything measured so far. Round-4's record printed
    # only at the very end — one slow section erased every completed
    # metric (BENCH_r04.json parsed:null).
    n_params, train_tok_s, mfu = _timed_section(
        "train", lambda: _train_bench(on_tpu, dev))
    record = {
        "metric": f"llama_{n_params/1e9:.2f}B_fwd_bwd_bf16_tokens_per_sec"
                  + suffix,
        "value": round(train_tok_s, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": (round(mfu / 0.40, 4)
                        if mfu is not None else None),
        # provenance rides every printed line (the record is re-printed
        # incrementally; each line stays attributable on its own)
        "provenance": _provenance(dev),
    }
    record.update(tuned)
    _emit_record(record, rec_out)
    gc.collect()

    # fit-loop e2e (ISSUE 5): right after the headline train metric —
    # the whole point is fit() reaching the raw step's rate
    try:
        fit_e2e = _timed_section(
            "fit e2e", lambda: _fit_e2e_bench(on_tpu, dev,
                                       autotune=args.autotune))
    except Exception as e:
        print(f"# fit e2e bench failed: {e!r}", file=sys.stderr)
        fit_e2e = None
    gc.collect()
    if fit_e2e is not None:
        record["train_e2e_metric"] = ("llama_fit_loop_compiled_step"
                                      + suffix)
        record["train_e2e_unit"] = "tokens/s/chip"
        record.update(fit_e2e)
        _emit_record(record, rec_out)

    # peak-HBM accounting (ISSUE 8): compile-only probe — cheap, so it
    # sits right after the fit section whose memory story it documents
    try:
        mem_keys = _timed_section(
            "train mem", lambda: _train_mem_bench(on_tpu, dev))
    except Exception as e:
        print(f"# train mem bench failed: {e!r}", file=sys.stderr)
        mem_keys = None
    if mem_keys is not None:
        record.update(mem_keys)
        _emit_record(record, rec_out)

    # Section order = evidentiary priority under the driver's time
    # limit (measured round 5: train 593s, decode 353s — mostly
    # init/compile, not measurement): the MoE train MFU is the
    # round's headline addition, then serving depth (cb), then the
    # decode secondaries.
    try:
        moe_params, moe_tok_s, moe_mfu = _timed_section(
            "moe train", lambda: _moe_train_bench(on_tpu, dev))
    except Exception as e:
        print(f"# moe train bench failed: {e!r}", file=sys.stderr)
        moe_params = moe_tok_s = moe_mfu = None
    # a failed section's exception traceback pins its model (frames hold
    # locals) — without this collect, one OOM sinks every later section
    gc.collect()
    if moe_tok_s is not None:
        record["moe_metric"] = (
            f"qwen2_moe_{moe_params/1e9:.2f}B_fwd_bwd_bf16_tokens_per_sec"
            + suffix)
        record["moe_value"] = round(moe_tok_s, 2)
        record["moe_unit"] = "tokens/s/chip"
        record["moe_mfu"] = (round(moe_mfu, 4)
                             if moe_mfu is not None else None)
        _emit_record(record, rec_out)

    try:
        cb_tok_s, cb_gauges, cb_tuned = _timed_section(
            "cb", lambda: _cb_bench(on_tpu, autotune=args.autotune))
    except Exception as e:
        print(f"# continuous-batching bench failed: {e!r}", file=sys.stderr)
        cb_tok_s = cb_gauges = cb_tuned = None
    if cb_tok_s is not None:
        record["cb_metric"] = ("llama_1B_continuous_batching_mixed_lengths"
                               + suffix)
        record["cb_value"] = round(cb_tok_s, 2)
        record["cb_unit"] = "tokens/s/chip"
        record["cb_occupancy"] = round(cb_gauges["slot_occupancy"], 4)
        record["cb_prefill_overlap"] = round(
            cb_gauges["prefill_overlap_frac"], 4)
        # ISSUE-3 latency + compile-budget keys (engine gauges ride the
        # PR-2 tracer; these are the headline serving-latency numbers)
        record["cb_ttft_ms_p50"] = round(cb_gauges["ttft_ms_p50"], 2)
        record["cb_ttft_ms_p99"] = round(cb_gauges["ttft_ms_p99"], 2)
        record["cb_itl_ms_p50"] = round(cb_gauges["itl_ms_p50"], 3)
        record["cb_itl_ms_p99"] = round(cb_gauges["itl_ms_p99"], 3)
        record["cb_compiles"] = cb_gauges["compiled_programs"]
        # ISSUE-7 unified-batching-step keys: the engine now runs ONE
        # compiled program per scheduler turn (cb_compiles expected
        # ~1 steady-state)
        # (aliases of cb_value / cb_gauges.unified_steps so rounds
        # grep ONE name — assigned from the record, cannot diverge)
        record["cb_unified_tok_s"] = record["cb_value"]
        record["cb_unified_steps"] = cb_gauges["unified_steps"]
        # observability self-measurement: instrumentation's share of
        # the serving hot loop (<2% pinned by test_metrics)
        record["obs_overhead_frac"] = round(
            cb_gauges.get("obs_overhead_frac", 0.0), 6)
        record["cb_gauges"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in cb_gauges.items()}
        if cb_tuned:
            record["tuned_serving_chunks"] = cb_tuned
        _emit_record(record, rec_out)
    gc.collect()

    # speculative decoding A/B (ISSUE 18): this round's headline
    # addition, right after the cb section whose engine it accelerates
    # — the decode-batch-1/4/8 sweep, the accept-rate economics, and
    # the short_chat_batch1 goodput leg through the HTTP front door
    try:
        cb_spec = _timed_section(
            "cb spec", lambda: _cb_spec_bench(on_tpu, autotune=args.autotune))
    except Exception as e:
        print(f"# cb spec bench failed: {e!r}", file=sys.stderr)
        cb_spec = None
    gc.collect()
    if cb_spec is not None:
        record.update(cb_spec)
        _emit_record(record, rec_out)

    # serving reliability under overload (ISSUE 10): right after the
    # cb section whose engine it stresses — the survival economics
    # (shed/preempt/goodput) contextualize the throughput number above
    try:
        cb_overload = _timed_section(
            "cb overload", lambda: _cb_overload_bench(on_tpu))
    except Exception as e:
        print(f"# cb overload bench failed: {e!r}", file=sys.stderr)
        cb_overload = None
    gc.collect()
    if cb_overload is not None:
        record.update(cb_overload)
        _emit_record(record, rec_out)

    # multi-replica fleet (ISSUE 11): the scale-out + failover
    # economics next to the single-engine numbers they contextualize
    try:
        cb_fleet = _timed_section(
            "cb fleet", lambda: _cb_fleet_bench(on_tpu))
    except Exception as e:
        print(f"# cb fleet bench failed: {e!r}", file=sys.stderr)
        cb_fleet = None
    gc.collect()
    if cb_fleet is not None:
        record.update(cb_fleet)
        _emit_record(record, rec_out)

    # process-backed fleet (ISSUE 16): the same failover economics
    # with REAL worker processes on the wire, next to the in-process
    # fleet numbers they contextualize
    try:
        cb_procfleet = _timed_section(
            "cb procfleet", lambda: _cb_procfleet_bench(on_tpu))
    except Exception as e:
        print(f"# cb procfleet bench failed: {e!r}", file=sys.stderr)
        cb_procfleet = None
    gc.collect()
    if cb_procfleet is not None:
        record.update(cb_procfleet)
        _emit_record(record, rec_out)

    # disaggregated prefill/decode (ISSUE 17): the colocated-vs-disagg
    # A/B on the long_prompt_flood mix, right after the proc fleet
    # whose wire + worker machinery it rides
    try:
        cb_disagg = _timed_section(
            "cb disagg", lambda: _cb_disagg_bench(on_tpu))
    except Exception as e:
        print(f"# cb disagg bench failed: {e!r}", file=sys.stderr)
        cb_disagg = None
    gc.collect()
    if cb_disagg is not None:
        record.update(cb_disagg)
        _emit_record(record, rec_out)

    # SLO-driven autoscaler (ISSUE 19): the goodput-vs-chips frontier
    # A/B right after the fleets whose control loop it closes
    try:
        cb_autoscale = _timed_section(
            "cb autoscale", lambda: _cb_autoscale_bench(on_tpu))
    except Exception as e:
        print(f"# cb autoscale bench failed: {e!r}", file=sys.stderr)
        cb_autoscale = None
    gc.collect()
    if cb_autoscale is not None:
        record.update(cb_autoscale)
        _emit_record(record, rec_out)

    # shared-prefix storm (ISSUE 12): the prefix-cache cold/warm A/B
    # right after the serving sections whose capacity it multiplies
    try:
        cb_prefix = _timed_section(
            "cb prefix", lambda: _cb_prefix_bench(on_tpu))
    except Exception as e:
        print(f"# cb prefix bench failed: {e!r}", file=sys.stderr)
        cb_prefix = None
    gc.collect()
    if cb_prefix is not None:
        record.update(cb_prefix)
        _emit_record(record, rec_out)

    # quantized serving (ISSUE 20): the equal-byte capacity A/B plus
    # the accuracy gate's numbers, right after the prefix cache whose
    # residency the quantized pools multiply
    try:
        cb_quant = _timed_section(
            "cb quant", lambda: _cb_quant_bench(on_tpu,
                                        autotune=args.autotune))
    except Exception as e:
        print(f"# cb quant bench failed: {e!r}", file=sys.stderr)
        cb_quant = None
    gc.collect()
    if cb_quant is not None:
        record.update(cb_quant)
        _emit_record(record, rec_out)

    # HTTP front door (ISSUE 15): what serving costs once a real
    # client on a real socket is in the loop, next to the raw engine
    try:
        cb_http = _timed_section(
            "cb http", lambda: _cb_http_bench(on_tpu))
    except Exception as e:
        print(f"# cb http bench failed: {e!r}", file=sys.stderr)
        cb_http = None
    gc.collect()
    if cb_http is not None:
        record.update(cb_http)
        _emit_record(record, rec_out)

    try:
        decode_tok_s = _timed_section(
            "decode", lambda: _decode_bench(on_tpu))
    except Exception as e:  # decode is secondary: never sink the headline
        print(f"# decode bench failed: {e!r}", file=sys.stderr)
        decode_tok_s = None
    if decode_tok_s is not None:
        record["decode_metric"] = "llama_1B_kv_cache_greedy_decode" + suffix
        record["decode_value"] = round(decode_tok_s, 2)
        record["decode_unit"] = "tokens/s/chip"
        _emit_record(record, rec_out)
    gc.collect()

    try:
        moe_decode_tok_s = _timed_section(
            "moe decode", lambda: _moe_decode_bench(on_tpu))
    except Exception as e:
        print(f"# moe decode bench failed: {e!r}", file=sys.stderr)
        moe_decode_tok_s = None
    gc.collect()
    if moe_decode_tok_s is not None:
        record["moe_decode_metric"] = (
            "deepseek_v2_mla_latent_cache_greedy_decode" + suffix)
        record["moe_decode_value"] = round(moe_decode_tok_s, 2)
        record["moe_decode_unit"] = "tokens/s/chip"
        _emit_record(record, rec_out)

    # MoE step-time attribution (the tentpole evidence table): LAST,
    # after every headline metric has printed — its ~5 fresh variant
    # compiles can never starve a metric a prior round recorded; the
    # record line re-prints with the breakdown attached when it lands.
    try:
        moe_bd, moe_bd_trace = _timed_section(
            "moe breakdown", lambda: _moe_breakdown_bench(on_tpu, dev))
    except Exception as e:
        print(f"# moe breakdown bench failed: {e!r}", file=sys.stderr)
        moe_bd = moe_bd_trace = None
    gc.collect()
    if moe_bd is not None:
        record["moe_breakdown"] = moe_bd
        record["moe_breakdown_trace"] = moe_bd_trace
        _emit_record(record, rec_out)


if __name__ == "__main__":
    main()
