"""Trace-layer + cost-accounting correctness (profiler subsystem,
ISSUE 2 satellite: nesting, exception-safety, chrome-trace schema
validity, FLOPs accounting on known shapes, atomic export under fault
injection). Pure-python + tiny jax only — fast tier by design (the
model-level breadth tests live in test_perf_observability.py, slow
tier)."""

import json
import logging

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import cost, trace


class TestSpans:
    def test_nesting_depths_recorded(self):
        tr = trace.Tracer(enabled=True)
        with tr.span("outer"):
            with tr.span("mid"):
                with tr.span("leaf"):
                    pass
            with tr.span("mid2"):
                pass
        by_name = {e.name: e for e in tr.events}
        assert by_name["outer"].depth == 0
        assert by_name["mid"].depth == by_name["mid2"].depth == 1
        assert by_name["leaf"].depth == 2
        # children close before parents -> recorded first
        assert [e.name for e in tr.events] == ["leaf", "mid", "mid2",
                                               "outer"]

    def test_span_timing_and_containment(self):
        tr = trace.Tracer(enabled=True)
        import time
        with tr.span("outer"):
            with tr.span("inner"):
                time.sleep(0.01)
        inner, outer = tr.events
        assert inner.dur >= 10_000                  # >= 10 ms in us
        assert outer.dur >= inner.dur
        assert outer.ts <= inner.ts
        assert inner.ts + inner.dur <= outer.ts + outer.dur + 1.0

    def test_exception_safety(self):
        """A raising body still records the span (annotated), never
        swallows the exception, and restores the nesting depth."""
        tr = trace.Tracer(enabled=True)
        with pytest.raises(ValueError, match="boom"):
            with tr.span("will_raise"):
                raise ValueError("boom")
        assert len(tr.events) == 1
        ev = tr.events[0]
        assert ev.name == "will_raise"
        assert "ValueError: boom" in ev.args["error"]
        # depth restored: a following span is top-level again
        with tr.span("after"):
            pass
        assert tr.events[-1].depth == 0

    def test_disabled_tracer_records_nothing(self):
        tr = trace.Tracer(enabled=False)
        with tr.span("x"):
            pass
        tr.counter("c", 1)
        tr.instant("i")
        assert tr.events == []

    def test_span_args_and_set_args(self):
        tr = trace.Tracer(enabled=True)
        with tr.span("op", flops=100.0) as sp:
            sp.set_args(bytes=50.0)
        assert tr.events[0].args == {"flops": 100.0, "bytes": 50.0}

    def test_device_sync_point(self):
        tr = trace.Tracer(enabled=True)
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        with tr.span("matmul", sync=None):
            y = x @ x
        waited = tr.device_sync(y)
        assert waited >= 0.0
        assert any(e.cat == "sync" for e in tr.events)


class TestChromeExport:
    def _trace(self):
        tr = trace.Tracer(enabled=True)
        with tr.span("sec", cat="train", flops=1e6):
            pass
        tr.counter("gauge", 0.5)
        tr.instant("marker")
        return tr

    def test_chrome_trace_schema(self, tmp_path):
        """The export must be valid chrome trace-event JSON: a
        traceEvents list whose entries carry name/ph/ts/pid/tid, X
        events a dur, C events args."""
        tr = self._trace()
        path = tr.export_chrome_trace(tmp_path / "t.json")
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        assert {e["ph"] for e in evs} == {"X", "C", "i"}
        for e in evs:
            for key in ("name", "ph", "ts", "pid", "tid"):
                assert key in e, e
            assert isinstance(e["ts"], (int, float))
        x = next(e for e in evs if e["ph"] == "X")
        assert "dur" in x and x["args"]["flops"] == 1e6
        c = next(e for e in evs if e["ph"] == "C")
        assert c["args"]["value"] == 0.5

    def test_json_export_has_sections(self, tmp_path):
        tr = self._trace()
        doc = json.load(open(tr.export_json(tmp_path / "raw.json")))
        assert doc["sections"]["sec"]["count"] == 1
        assert doc["sections"]["sec"]["flops"] == 1e6

    def test_export_is_atomic_under_fault(self, tmp_path):
        """ENOSPC mid-export (PR-1 fault harness) must never leave a
        torn half-JSON file; a retry after the fault clears succeeds."""
        import errno

        from paddle_tpu.testing import FaultInjector

        tr = self._trace()
        target = tmp_path / "trace.json"
        with FaultInjector() as fi:
            fi.fail_write(str(target), errno_=errno.ENOSPC,
                          after_bytes=10)
            with pytest.raises(OSError):
                tr.export_chrome_trace(target)
            assert fi.fires() == 1
        import os
        assert not target.exists()          # no torn file
        assert not os.path.exists(str(target) + ".tmp")
        path = tr.export_chrome_trace(target)   # clean retry wins
        assert json.load(open(path))["traceEvents"]


class TestCostAccounting:
    def test_matmul_flops_known_shape(self):
        """2mkn on a known-shape matmul, operands+result bytes."""
        c = cost.matmul_cost(64, 128, 32)
        assert c.flops == 2 * 64 * 128 * 32
        assert c.bytes == 2 * (64 * 128 + 128 * 32 + 64 * 32)
        assert cost.matmul_cost(64, 128, 32, batch=3).flops == 3 * c.flops

    def test_span_flops_to_mfu(self):
        """A span annotated with flops yields achieved FLOP/s and MFU in
        the section summary."""
        tr = trace.Tracer(enabled=True)
        x = paddle.to_tensor(np.random.rand(64, 128).astype("float32"))
        w = paddle.to_tensor(np.random.rand(128, 32).astype("float32"))
        c = cost.matmul_cost(64, 128, 32, dtype_bytes=4)
        with tr.span("mm", flops=c.flops, bytes=c.bytes):
            y = x @ w
            trace.block_on(y)
        s = tr.section_summary(      # the CPU has no published peak
            peaks=cost.Peaks(1e12, 1e11))["mm"]
        assert s["flops"] == c.flops
        assert s["flops_per_s"] > 0
        assert 0 < s["mfu"] < 1
        assert s["roofline"]["bound"] in ("compute", "memory")

    def test_roofline_classification(self):
        peaks = cost.Peaks(flops=100e12, hbm_bw=1e12)    # ridge = 100
        big = cost.matmul_cost(4096, 4096, 4096)         # intensity >> 100
        small = cost.matmul_cost(16, 16, 16)             # intensity << 100
        assert cost.roofline(big.flops, big.bytes, peaks)["bound"] \
            == "compute"
        assert cost.roofline(small.flops, small.bytes, peaks)["bound"] \
            == "memory"
        r = cost.roofline(small.flops, small.bytes, peaks)
        assert r["attainable_flops_per_s"] <= peaks.flops
        assert r["ridge"] == pytest.approx(100.0)

    def test_transformer_step_flops_matches_bench_formula(self):
        n_params, tokens, L, b, s, d = 1e9, 4096, 16, 2, 2048, 1024
        assert cost.transformer_step_flops(n_params, tokens, L, b, s, d) \
            == 6.0 * n_params * tokens + 12.0 * L * b * s * s * d

    def test_moe_section_costs_schema(self):
        costs = cost.moe_section_costs(
            4096, 1024, 1408, 16, 2, num_moe_layers=12, dropless=True)
        assert set(costs) == {"gating", "sort", "a2a", "expert_matmul"}
        assert costs["expert_matmul"].flops > costs["gating"].flops
        assert costs["sort"].flops == 0 and costs["sort"].bytes > 0
        # capacity path executes cf x the dropless rows
        cap = cost.moe_section_costs(4096, 1024, 1408, 16, 2,
                                     num_moe_layers=12,
                                     dropless=False, capacity_factor=2.0)
        assert cap["expert_matmul"].flops > costs["expert_matmul"].flops

    def test_kernel_cost_surfaces(self):
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_cost
        from paddle_tpu.ops.pallas.grouped_matmul import \
            grouped_matmul_cost
        g = grouped_matmul_cost((512, 64), (8, 64, 128))
        assert g.flops == 2 * 512 * 64 * 128
        assert grouped_matmul_cost((512, 64), (8, 64, 128),
                                   train=True).flops == 3 * g.flops
        f = flash_attention_cost((2, 128, 4, 64))
        assert f.flops == 4 * 2 * 4 * 128 * 128 * 64
        assert flash_attention_cost((2, 128, 4, 64),
                                    causal=True).flops == f.flops / 2


class TestOptionsSurface:
    def test_options_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_PROFILER_TRACE", "1")
        monkeypatch.setenv("PADDLE_PROFILER_LOG_DIR", "/tmp/xyz")
        monkeypatch.setenv("PADDLE_PROFILER_WITH_FLOPS", "true")
        opts = profiler.ProfilerOptions.from_env()
        assert opts.trace_enabled and opts.with_flops
        assert opts.output_dir == "/tmp/xyz"

    def test_enable_disable_exports(self, tmp_path):
        tr = profiler.enable(profiler.ProfilerOptions(
            output_dir=str(tmp_path)))
        assert tr is profiler.get_tracer() and tr.enabled
        try:
            with profiler.trace_span("spanned"):
                pass
        finally:
            path = profiler.disable()
        assert not tr.enabled
        assert path and json.load(open(path))["traceEvents"]
        tr.clear()

    def test_flags_toggle(self):
        paddle.set_flags({"FLAGS_enable_host_trace": True})
        try:
            assert profiler.get_tracer().enabled
        finally:
            paddle.set_flags({"FLAGS_enable_host_trace": False})
        assert not profiler.get_tracer().enabled
        profiler.get_tracer().clear()

    def test_record_event_lands_in_structured_trace(self):
        tr = profiler.enable(profiler.ProfilerOptions(
            export_on_disable=False))
        try:
            with profiler.RecordEvent("annotated_op"):
                pass
        finally:
            profiler.disable(export=False)
        assert any(e.name == "annotated_op" and e.ph == "X"
                   for e in tr.events)
        tr.clear()


class TestPerfEventLog:
    def test_log_and_dedupe(self, caplog):
        with caplog.at_level(logging.INFO, logger="paddle_tpu.perf"):
            assert trace.log_perf_event("unit/evt", "first",
                                        once_key=("unit", 1))
            assert not trace.log_perf_event("unit/evt", "second",
                                            once_key=("unit", 1))
        msgs = [r.message for r in caplog.records]
        assert any("first" in m for m in msgs)
        assert not any("second" in m for m in msgs)


class TestFitPipelineGaugeSchema:
    def test_fit_gauges_in_chrome_export(self, tmp_path):
        """ISSUE 5: the compiled fit loop's pipeline gauges
        (input_wait_ms, steps_in_flight, h2d_bytes) must land in the
        trace export as chrome counter events with numeric values."""
        from paddle_tpu import nn
        from paddle_tpu.hapi import Model

        xs = np.random.RandomState(0).rand(8, 4).astype("float32")
        ys = np.random.RandomState(1).rand(8, 1).astype("float32")
        ds = [(xs[i], ys[i]) for i in range(8)]
        paddle.seed(0)
        net = nn.Linear(4, 1)
        model = Model(net)
        import paddle_tpu.optimizer as opt
        model.prepare(opt.SGD(0.01, parameters=net.parameters()),
                      lambda out, y: ((out - y) ** 2).mean())
        tr = profiler.enable(profiler.ProfilerOptions(
            output_dir=str(tmp_path), export_on_disable=False))
        tr.clear()
        try:
            model.fit(ds, batch_size=4, epochs=1, verbose=0,
                      compiled=True)
        finally:
            profiler.disable(export=False)
        path = tr.export_chrome_trace(tmp_path / "fit.json")
        doc = json.load(open(path))
        counters = {e["name"]: e for e in doc["traceEvents"]
                    if e["ph"] == "C"}
        for gauge in ("hapi/input_wait_ms", "hapi/steps_in_flight",
                      "hapi/h2d_bytes"):
            assert gauge in counters, sorted(counters)
            val = counters[gauge]["args"]["value"]
            assert isinstance(val, (int, float)) and val >= 0
        # the per-step span keeps its name and marks the mode
        spans = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "hapi/train_batch"]
        assert len(spans) == 2
        assert all(s["args"]["mode"] == "compiled" for s in spans)
        tr.clear()
