"""Driving a compiled ``to_static`` AdamW step the way
``examples/train_gpt2.py`` does: one call per batch, the loss fetched after
every step (the example's own and only host sync)."""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

from . import traffic as T

WARM_STEPS = 3      # steps 1..3 are set-up, and what the reference follows


@dataclass
class TrainRun:
    t_start: float = 0.0
    t_end: float = 0.0
    step_ends: list = field(default_factory=list)
    losses: list = field(default_factory=list)       # window's losses
    first_losses: list = field(default_factory=list)  # steps 1..3
    first_batches: list = field(default_factory=list)
    g1_norms: np.ndarray = None     # clipped gradient of step 1, per piece
    g2_norms: np.ndarray = None     # ... of step 2, the compiled program's
    after3: list = None             # the parameters after step 3 (copies)
    programs_in_window: int = 0


def pieces(cfg):
    """The tensors the comparison is made by: the reference's leaves, those
    that hold several published tensors side by side split into them.
    Returns (labels, norms) — ``norms(dict name -> array)`` is the vector of
    every piece's L2 norm (traceable)."""
    import re

    import jax.numpy as jnp

    from . import spec
    ref = spec.reference_module(cfg)
    names = [n for n, _, _ in ref.param_specs(cfg["sizes"])]
    splits = getattr(ref, "COMPARE_SPLITS", [])

    def how(name):
        for rx, axis, parts in splits:
            if re.search(rx, name):
                return axis, parts
        return None

    labels = []
    for n in names:
        h = how(n)
        labels += [n] if h is None else [f"{n}[{i}/{h[1]}]"
                                         for i in range(h[1])]

    def norms(leaves):
        out = []
        for n in names:
            x, h = leaves[n].astype(jnp.float32), how(n)
            for part in ([x] if h is None else jnp.split(x, h[1], axis=h[0])):
                out.append(jnp.sqrt(jnp.sum(jnp.square(part))))
        return jnp.stack(out)

    return names, labels, norms


def build_step(cfg, model):
    """The optimizer and the ONE compiled step, as the configuration's
    ``optimizer`` group states them."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    o = cfg["optimizer"]
    model.train()
    opt = getattr(paddle.optimizer, o["class"])(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], parameters=model.parameters(),
        weight_decay=o["weight_decay"],
        grad_clip=nn.ClipGradByGlobalNorm(o["grad_clip_global_norm"]))

    @paddle.jit.to_static
    def step(ids):
        with paddle.amp.auto_cast(level=o["amp_level"], dtype=o["amp_dtype"]):
            _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return opt, step


def gradient_norms(cfg, opt, model, before=None):
    """Per piece, the norm of the gradient the optimizer was handed at its
    last step, from its state: m = beta1 * m_before + (1 - beta1) * g, with
    ``before`` the first moments as they were (None: nought, step 1). The
    program's first step is the eager discovery run of the ``to_static``
    function and its second the compiled program the window times, so the
    harness reads both. Returns (norms, copies of the moments)."""
    import jax
    names, _, norms = pieces(cfg)
    b1 = cfg["optimizer"]["beta1"]
    sd = opt.state_dict()
    ms = {}
    for i, (n, p) in enumerate(zip(names, model.parameters())):
        key = f"{p.name or f'param_{i}'}_moment1"
        if key not in sd:
            raise SystemExit(f"perfbench: optimizer state has no {key}")
        ms[n] = sd[key]._data

    def fn(ms, before):
        g = {n: (m - b1 * before[n] if before else m) / (1.0 - b1)
             for n, m in ms.items()}
        return norms(g), {n: m + 0 for n, m in ms.items()}

    got, kept = jax.jit(fn)(ms, before)
    return np.asarray(got), kept


def run(cfg, model, traffic, seed, seconds, tracer, annotate, compiles, say):
    import jax
    import paddle_tpu as paddle

    opt, step = build_step(cfg, model)
    batches = T.train_batches(traffic, seed)
    run_ = TrainRun()

    def one(ids):
        with annotate("bench/train.step"):
            loss = step(paddle.to_tensor(ids))
            return float(loss.item())

    # steps 1..3: the window's own object, call and feed; the reference
    # follows them once the window has closed
    built = []
    for i in range(WARM_STEPS):
        ids = next(batches)
        compiles.mark()             # the readings' own programs: not a step's
        t = time.perf_counter()
        loss = one(ids)
        built.append((round(time.perf_counter() - t, 2), compiles.mark()[0]))
        run_.first_batches.append(ids)
        run_.first_losses.append(loss)
        if i == 0:
            run_.g1_norms, m1 = gradient_norms(cfg, opt, model)
        if i == 1:
            run_.g2_norms = gradient_norms(cfg, opt, model, before=m1)[0]
            del m1
        if i == 2:
            copy = jax.jit(lambda xs: [x + 0 for x in xs])
            run_.after3 = copy([p._data for p in model.parameters()])
    say("warmup", steps=[b[0] for b in built],
        programs_per_step=[b[1] for b in built],
        losses=[round(x, 4) for x in run_.first_losses])

    trace_s = float(traffic["trace_seconds"]) if tracer else 0.0
    compiles.mark()
    run_.t_start = time.perf_counter()
    t_end = run_.t_start + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if tracer and not tracer.started and now >= t_end - trace_s:
            tracer.start()
        run_.losses.append(one(next(batches)))
        run_.step_ends.append(time.perf_counter())
    run_.t_end = run_.step_ends[-1]
    if tracer:
        tracer.stop()
    run_.programs_in_window = compiles.mark()[0]
    del opt, step
    gc.collect()
    return run_


# ---- correct ----------------------------------------------------------------

def reference_readings(cfg, traffic, seed, batches, mm=None, batch_fault=None,
                       w0=None):
    """Follow the first three steps in the plain reference. Returns
    ((losses[3], g1 norm per piece, g2 norm per piece, update norm per
    piece), w0 leaves)."""
    import jax
    import jax.numpy as jnp

    from . import spec, weights
    from .model import jnp_dtype
    ref = spec.reference_module(cfg)
    m = cfg["sizes"]
    names, _, norms = pieces(cfg)
    if w0 is None:
        # the seed is an ARGUMENT of the program that makes the leaves: as a
        # constant it would give every seed a program (and a compile) of
        # its own
        w0 = jax.jit(lambda words: weights.LeafSource(
            ref.param_specs(m), words, cfg["init_std"],
            jnp_dtype(cfg["dtype"]), ref.LAYER_PATTERN).all())(
                weights.seed_words(seed))
    kw = {} if mm is None else {"mm": mm}
    losses, gn, w3 = ref.train_steps(m, cfg["optimizer"], w0,
                                     [jnp.asarray(b) for b in batches], norms,
                                     batch_fault=batch_fault, **kw)
    gn = np.asarray(gn)
    return (np.asarray(losses), gn[0], gn[1], update_norms(cfg, w3, w0)), w0


def update_norms(cfg, after, w0):
    """Norm of (after - w0) per piece; ``after`` a dict or a list in the
    reference's leaf order."""
    import jax
    names, _, norms = pieces(cfg)
    if not isinstance(after, dict):
        after = dict(zip(names, after))
    return np.asarray(jax.jit(lambda a, b: norms(
        {n: a[n].astype(b[n].dtype) - b[n] for n in names}))(after, w0))


def norm_gap(got, want):
    """Per leaf: |got - want| against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, np.median(want))


def compare(cfg, names, prog, ref):
    """The numbers compared, each beside its limit; and ``correct``.
    ``prog`` and ``ref``: (losses, g1 norms, g2 norms, update norms)."""
    lim = cfg["check"]
    prog_losses, prog_g1, prog_g2, prog_upd = prog
    ref_losses, ref_g1, ref_g2, ref_upd = ref
    loss_rel = float(np.max(np.abs(np.asarray(prog_losses) - ref_losses)
                            / np.abs(ref_losses)))
    g_gap, g2_gap = norm_gap(prog_g1, ref_g1), norm_gap(prog_g2, ref_g2)
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: not compared in the change
    alive = ref_g1 >= 1e-3 * np.median(ref_g1)
    u_gap = np.where(alive, norm_gap(prog_upd, ref_upd), 0.0)
    gi, ui = int(np.argmax(g_gap)), int(np.argmax(u_gap))
    g2i = int(np.argmax(g2_gap))
    out = {
        "loss_rel_max": {"value": loss_rel, "limit": lim["loss_rel"]},
        "grad_norm_gap_max": {"value": float(g_gap[gi]),
                              "limit": lim["grad_norm_gap"],
                              "leaf": names[gi]},
        "grad2_norm_gap_max": {"value": float(g2_gap[g2i]),
                               "limit": lim["grad2_norm_gap"],
                               "leaf": names[g2i]},
        "update_norm_gap_max": {"value": float(u_gap[ui]),
                                "limit": lim["update_norm_gap"],
                                "leaf": names[ui],
                                "leaves_left_out": int((~alive).sum())},
    }
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return bool(ok), out


def check(cfg, traffic, seed, run_, say):
    t0 = time.perf_counter()
    _, labels, _ = pieces(cfg)
    ref, w0 = reference_readings(cfg, traffic, seed, run_.first_batches)
    ref_losses = ref[0]
    prog = (run_.first_losses, run_.g1_norms, run_.g2_norms,
            update_norms(cfg, run_.after3, w0))
    ok, out = compare(cfg, labels, prog, ref)
    nonfinite = sum(1 for x in run_.losses if not np.isfinite(x))
    out["nonfinite_losses"] = {"value": nonfinite, "limit": 0}
    say("check", ref_losses=[round(float(x), 5) for x in ref_losses],
        prog_losses=[round(float(x), 5) for x in run_.first_losses],
        reference_s=round(time.perf_counter() - t0, 1))
    return bool(ok and not nonfinite), out
