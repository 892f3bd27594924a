"""In-program step-breakdown harness: section ablation -> attribution.

Host spans cannot see inside one compiled XLA program, so per-section
time inside a jitted train step is measured the way the round-4 CB
breakdown was (BASELINE.md): compile N+1 VARIANTS of the step — the full
program plus one with each section knocked out (replaced by a
shape-preserving placeholder that XLA cannot constant-fold away) — time
each, and attribute ``t(section) = t(full) - t(without section)``.

Attribution caveats (documented, not hidden):

- Sections that XLA overlaps (e.g. an all-to-all hidden behind matmuls)
  attribute only their EXPOSED time — which is the number that matters
  for optimization priority.
- If the per-section attributions sum past the full step time (overlap
  reclaimed twice), they are scaled proportionally so the table always
  sums to 100%; the residual is reported as ``other``.
- Ablated programs produce garbage NUMERICS by design; the harness must
  never share compiled programs or parameters with a real training run.

``moe_step_breakdown`` wires this into the MoE stack: gating / sort /
a2a / expert-matmul sections via ``ops.moe.moe_ablation``.
"""

from __future__ import annotations

import time

from . import cost as _cost
from .trace import TraceEvent, get_tracer

__all__ = ["StepBreakdown", "ablation_breakdown", "moe_step_breakdown"]


class StepBreakdown:
    """Machine-readable per-section step attribution.

    ``rows`` is a list of dicts — one per section plus ``other`` — with
    ``section``, ``ms``, ``frac`` (fractions sum to 1.0), and, when
    costs were provided, ``flops``/``bytes``/``mfu``/``bound``.
    """

    def __init__(self, step_ms: float, rows: list, meta: dict | None = None):
        self.step_ms = step_ms
        self.rows = rows
        self.meta = dict(meta or {})

    def to_dict(self) -> dict:
        return {"step_ms": round(self.step_ms, 4),
                "sections": self.rows, "meta": self.meta}

    def to_markdown(self) -> str:
        lines = ["| section | ms | % of step | MFU | bound |",
                 "|---|---|---|---|---|"]
        for r in self.rows:
            mfu = f"{r['mfu'] * 100:.1f}%" if r.get("mfu") is not None \
                else "—"
            lines.append(
                f"| {r['section']} | {r['ms']:.2f} | "
                f"{r['frac'] * 100:.1f}% | {mfu} | "
                f"{r.get('bound', '—')} |")
        lines.append(f"| **step** | {self.step_ms:.2f} | 100% | | |")
        return "\n".join(lines)

    def emit(self, tracer=None):
        """Record the breakdown into a tracer as back-to-back spans (one
        synthetic timeline slice per section) + per-section gauges, so
        ``export_chrome_trace`` shows the attribution visually."""
        tracer = tracer or get_tracer()
        if not tracer.enabled:
            # never inject synthetic spans into a disabled tracer (they
            # would leak into a later, unrelated tracing session)
            return self
        t0 = (time.perf_counter() - tracer._epoch) * 1e6
        off = 0.0
        for r in self.rows:
            args = {k: r[k] for k in ("frac", "flops", "bytes", "mfu",
                                      "bound") if r.get(k) is not None}
            tracer._record(TraceEvent(
                name=f"breakdown/{r['section']}", ph="X", cat="breakdown",
                ts=t0 + off, dur=r["ms"] * 1e3, args=args))
            tracer.counter(f"breakdown/{r['section']}_frac", r["frac"])
            off += r["ms"] * 1e3
        return self

    def export_chrome_trace(self, path) -> str:
        """One-shot chrome-trace export of just this breakdown."""
        from .trace import Tracer
        t = Tracer(enabled=True)
        self.emit(t)
        return t.export_chrome_trace(path)


def _timeit(run, steps, warmup) -> float:
    """Min over individually-timed steps: attribution subtracts two
    close numbers, and min filters one-off dispatch spikes far better
    than a mean over few steps — the
    same reason bench.py's decode metric takes min over reps."""
    for _ in range(warmup):
        run()
    best = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def ablation_breakdown(build_step, sections, steps=4, warmup=2,
                       costs=None, peaks=None, meta=None) -> StepBreakdown:
    """Generic attribution harness.

    build_step(ablate: frozenset[str]) -> zero-arg callable running ONE
    step and BLOCKING until device work completes (an unsynced step
    times dispatch, not execution). Called once per variant:
    ``frozenset()`` for the full step, ``{s}`` for each section.

    costs: optional {section: SectionCost} giving each row its MFU +
    roofline columns (profiler.cost.moe_section_costs builds these).
    peaks: the chip's Peaks; default the device's table entry. On a
    device the table does not know the two columns are left out.
    """
    sections = list(sections)
    peaks = peaks or _cost.known_peaks()   # None: no MFU/bound columns
    full = _timeit(build_step(frozenset()), steps, warmup)
    attr = {}
    for s in sections:
        without = _timeit(build_step(frozenset((s,))), steps, warmup)
        attr[s] = max(full - without, 0.0)
    total_attr = sum(attr.values())
    if total_attr > full > 0:
        # overlapped sections double-counted their reclaimed time:
        # scale so the table still sums to the measured step
        scale = full / total_attr
        attr = {s: v * scale for s, v in attr.items()}
        total_attr = full
    other = max(full - total_attr, 0.0)

    rows = []
    for s in sections + ["other"]:
        sec_s = other if s == "other" else attr[s]
        row = {"section": s, "ms": round(sec_s * 1e3, 4),
               "frac": round(sec_s / full, 6) if full else 0.0}
        c = (costs or {}).get(s)
        if c is not None:
            row["flops"] = c.flops
            row["bytes"] = c.bytes
        if c is not None and peaks is not None:
            row["mfu"] = round(_cost.mfu(c.flops, sec_s, peaks.flops), 6) \
                if sec_s else None
            row["bound"] = _cost.roofline(c.flops, c.bytes, peaks)["bound"]
        rows.append(row)
    # force exact 100%: dump rounding residue into 'other'
    resid = 1.0 - sum(r["frac"] for r in rows)
    rows[-1]["frac"] = round(rows[-1]["frac"] + resid, 6)
    m = {"steps": steps, "warmup": warmup,
         "device_kind": peaks.kind if peaks else None,
         "peak_flops": peaks.flops if peaks else None}
    m.update(meta or {})
    return StepBreakdown(full * 1e3, rows, m)


def moe_step_breakdown(model, input_ids, sections=None, steps=4,
                       warmup=2, peaks=None) -> StepBreakdown:
    """Attribute a MoE train step: gating / sort / a2a / expert-matmul /
    other, with per-section MFU and roofline columns.

    model: a CausalLM whose sparse FFN routes through ``ops.moe``
    (Qwen2MoeForCausalLM, MoELayer users). input_ids: [B, S+1] Tensor
    (labels = inputs, the bench convention). Each ablation variant is
    compiled fresh via ``jit.to_static`` — parameters are shared but
    gradients are cleared every step, so the model is unchanged after.

    The a2a section only attributes under expert parallelism; on a
    single device it reports ~0 (present in the table for schema
    stability — the acceptance schema is gating/sort/a2a/expert-matmul/
    other summing to 100%).
    """
    from ..framework.core import Tensor  # noqa: F401 (typing aid)
    from ..jit import to_static
    from ..ops import moe as moe_ops

    cfg = model.config
    if sections is None:
        sections = ["gating", "sort", "a2a", "expert_matmul"]

    batch, seqp1 = input_ids.shape
    tokens = batch * (seqp1 - 1)
    n_moe_layers = getattr(cfg, "num_hidden_layers", 1)
    first_dense = getattr(cfg, "first_k_dense_replace", 0)
    costs = _cost.moe_section_costs(
        tokens, cfg.hidden_size,
        getattr(cfg, "moe_intermediate_size", cfg.hidden_size),
        getattr(cfg, "num_experts", getattr(cfg, "n_routed_experts", 1)),
        getattr(cfg, "num_experts_per_tok", 1),
        num_moe_layers=max(n_moe_layers - first_dense, 1),
        capacity_factor=getattr(cfg, "capacity_factor", None),
        dropless=getattr(cfg, "moe_dropless", False), train=True)

    def build_step(ablate):
        def step_fn(ids):
            _, loss = model(ids, labels=ids)
            loss.backward()
            gsum = None
            for p in model.parameters():
                if p.grad is not None:
                    s = p.grad.flatten()[0].astype("float32")
                    gsum = s if gsum is None else gsum + s
            for p in model.parameters():
                p.clear_grad()
            return loss, gsum

        fn = to_static(step_fn)           # fresh program per variant

        def run():
            # the ablation context must cover the first (tracing) call:
            # the knocked-out sections are a trace-time decision
            with moe_ops.moe_ablation(ablate):
                loss, _ = fn(input_ids)
            float(loss.item())            # true device sync
        return run

    bd = ablation_breakdown(
        build_step, sections, steps=steps, warmup=warmup, costs=costs,
        peaks=peaks,
        meta={"tokens_per_step": tokens,
              "model": type(model).__name__,
              "accounting": "model FLOPs only; remat re-forward time "
                            "counted, FLOPs not (BASELINE.md caveat)"})
    return bd
