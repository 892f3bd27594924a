"""Model-level perf-observability breadth (ISSUE 2): MoE step-breakdown
attribution, serving-engine gauges (incl. under fault injection),
hapi per-epoch summaries, scan-decline / remat-dose-drop logging, and
the MoELayer dropless->EP downgrade warning.

Slow tier by default (ISSUE 2 satellite: defend the <5-min fast gate —
these compile real model programs). The pure-python trace/cost tests
are the fast-tier counterpart (test_trace.py)."""

import dataclasses
import json
import logging

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import trace


SECTION_SCHEMA = {"gating", "sort", "a2a", "expert_matmul", "other"}


class TestMoeStepBreakdown:
    def _model_and_ids(self, dropless=False):
        from paddle_tpu.models import Qwen2MoeConfig, Qwen2MoeForCausalLM
        cfg = dataclasses.replace(Qwen2MoeConfig.tiny(),
                                  scan_layers=False,
                                  moe_dropless=dropless)
        paddle.seed(0)
        model = Qwen2MoeForCausalLM(cfg)
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 17)).astype(np.int64))
        return model, ids

    def test_breakdown_schema_and_fractions(self):
        """The acceptance-criterion shape: machine-readable gating /
        sort / a2a / expert-matmul / other rows summing to ~100% of the
        step, each with MFU + roofline columns where costed."""
        model, ids = self._model_and_ids()
        # the CPU has no published peak: the MFU/roofline columns need
        # one passed explicitly
        bd = profiler.moe_step_breakdown(
            model, ids, steps=2, warmup=1,
            peaks=profiler.cost.Peaks(197e12, 819e9, "v5e"))
        d = bd.to_dict()
        assert d["step_ms"] > 0
        names = [r["section"] for r in d["sections"]]
        assert set(names) == SECTION_SCHEMA
        assert names[-1] == "other"
        total = sum(r["frac"] for r in d["sections"])
        assert total == pytest.approx(1.0, abs=1e-6)
        for r in d["sections"]:
            assert 0.0 <= r["frac"] <= 1.0
            assert r["ms"] >= 0.0
            if r["section"] != "other":
                assert r["flops"] >= 0 and r["bytes"] > 0
                assert r.get("bound") in ("compute", "memory")
        assert "accounting" in d["meta"]      # the remat caveat rides along

    def test_breakdown_chrome_export_and_markdown(self, tmp_path):
        model, ids = self._model_and_ids()
        bd = profiler.moe_step_breakdown(
            model, ids, sections=["gating", "expert_matmul"],
            steps=1, warmup=1)
        path = bd.export_chrome_trace(tmp_path / "bd.json")
        doc = json.load(open(path))
        x_names = {e["name"] for e in doc["traceEvents"]
                   if e["ph"] == "X"}
        assert {"breakdown/gating", "breakdown/expert_matmul",
                "breakdown/other"} <= x_names
        md = bd.to_markdown()
        assert "| section |" in md and "expert_matmul" in md

    def test_breakdown_leaves_model_intact(self):
        """Ablation variants share parameters: after the harness, grads
        are cleared and a normal forward still works."""
        model, ids = self._model_and_ids()
        profiler.moe_step_breakdown(model, ids,
                                    sections=["expert_matmul"],
                                    steps=1, warmup=0)
        assert all(p.grad is None for p in model.parameters())
        logits, loss = model(ids, labels=ids)
        assert np.isfinite(float(loss.item()))

    def test_ablated_program_differs_but_keeps_shapes(self):
        """Knocking a section out must keep output shapes/dtypes (the
        variant compiles the same step signature) while changing the
        computation (numerics differ from the full program)."""
        from paddle_tpu.ops import moe as moe_ops
        rng = np.random.RandomState(0)
        import jax.numpy as jnp
        x = jnp.asarray(rng.randn(16, 8).astype(np.float32))
        rw = jnp.asarray(rng.randn(8, 4).astype(np.float32))
        wg = jnp.asarray(rng.randn(4, 8, 16).astype(np.float32))
        wu = jnp.asarray(rng.randn(4, 8, 16).astype(np.float32))
        wd = jnp.asarray(rng.randn(4, 16, 8).astype(np.float32))
        full, aux, z = moe_ops.moe_forward(
            x, rw, lambda t: moe_ops.moe_ffn_grouped(t, wg, wu, wd), k=2)
        for section in ("gating", "sort", "expert_matmul"):
            with moe_ops.moe_ablation({section}):
                abl, aux_a, z_a = moe_ops.moe_forward(
                    x, rw,
                    lambda t: moe_ops.moe_ffn_grouped(t, wg, wu, wd), k=2)
            assert abl.shape == full.shape and abl.dtype == full.dtype
            assert not np.allclose(np.asarray(abl), np.asarray(full)), \
                f"ablating {section} changed nothing"
        # context restored: the full path is back
        again, _, _ = moe_ops.moe_forward(
            x, rw, lambda t: moe_ops.moe_ffn_grouped(t, wg, wu, wd), k=2)
        np.testing.assert_allclose(np.asarray(again), np.asarray(full))


class TestServingGauges:
    def _engine(self):
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny()
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        model.eval()
        eng = ContinuousBatchingEngine(
            model, num_slots=2, page_size=8, max_len=48, decode_chunk=4,
            prefill_chunk=16, greedy=True)
        rng = np.random.RandomState(0)
        for plen, n in [(6, 8), (12, 5), (9, 10), (4, 6)]:
            eng.add_request(rng.randint(0, cfg.vocab_size,
                                        (plen,)).astype(np.int32), n)
        return eng

    def test_gauges_consistency(self):
        eng = self._engine()
        done = eng.run()
        g = eng.gauges()
        assert g["tokens_emitted"] == sum(len(r.tokens) for r in done)
        assert g["requests_completed"] == len(done) == 4
        assert 0.0 < g["slot_occupancy"] <= 1.0
        assert 0.0 <= g["active_occupancy"] <= 1.0
        # every emitted token comes from a slot credited as advancing
        # at dispatch (ISSUE 7: a completing prompt's first token AND
        # its in-program decode tail both ride the unified step, whose
        # accounting counts prompt-streaming slots as advancing)
        assert g["tokens_emitted"] <= \
            eng._stats["active_slot_steps"] + g["prefills"]
        assert 0.0 <= g["prefill_overlap_frac"] <= 1.0
        assert g["prefills"] == 4
        assert g["tokens_per_s"] > 0
        assert g["chunks_dispatched"] * eng.decode_chunk \
            * eng.num_slots >= g["tokens_emitted"]
        # latency gauges present and ordered on this surface too
        assert 0 < g["ttft_ms_p50"] <= g["ttft_ms_p99"]
        assert g["compiled_programs"] == 1   # ONE unified signature
        assert g["unified_steps"] == g["chunks_dispatched"] > 0
        assert g["chunks_empty"] == 0        # eos-free workload

    def test_gauges_emitted_as_trace_counters(self, tmp_path):
        tr = profiler.enable(profiler.ProfilerOptions(
            output_dir=str(tmp_path), export_on_disable=False))
        tr.clear()
        try:
            eng = self._engine()
            eng.run()
        finally:
            profiler.disable(export=False)
        names = {e.name for e in tr.events if e.ph == "C"}
        assert {"serving/slot_occupancy", "serving/prefill_overlap_frac",
                "serving/active_slots",
                "serving/tokens_per_s"} <= names
        assert any(e.name == "serving/prefill" for e in tr.events)
        tr.clear()

    def test_gauges_survive_faulted_export(self, tmp_path):
        """PR-1 fault harness against the observability path: an ENOSPC
        on trace export neither corrupts the engine's gauges nor leaves
        a torn trace; the engine keeps serving afterwards."""
        import errno

        from paddle_tpu.testing import FaultInjector

        tr = profiler.enable(profiler.ProfilerOptions(
            output_dir=str(tmp_path), export_on_disable=False))
        tr.clear()
        try:
            eng = self._engine()
            eng.run()
            g1 = eng.gauges()
            target = tmp_path / "serving_trace.json"
            with FaultInjector() as fi:
                fi.fail_write(str(target), errno_=errno.ENOSPC)
                with pytest.raises(OSError):
                    tr.export_chrome_trace(target)
                assert fi.fires() == 1
            assert not target.exists()
            assert eng.gauges() == g1          # gauges untouched
            # engine still serves after the observer failed
            eng.add_request(np.arange(5, dtype=np.int32), 3)
            done = eng.run()
            assert len(done) == 1 and len(done[0].tokens) == 3
            assert eng.gauges()["requests_completed"] == 5
            assert json.load(open(tr.export_chrome_trace(target)))
        finally:
            profiler.disable(export=False)
            tr.clear()


class TestHapiEpochSummary:
    def test_fit_emits_epoch_summary(self, capsys, caplog, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model

        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        model = Model(net)
        import paddle_tpu.optimizer as opt
        model.prepare(optimizer=opt.SGD(learning_rate=0.01,
                                        parameters=net.parameters()),
                      loss=lambda out, y: ((out - y) ** 2).mean())
        xs = np.random.RandomState(0).rand(8, 4).astype("float32")
        ys = np.random.RandomState(1).rand(8, 1).astype("float32")
        ds = [(xs[i], ys[i]) for i in range(8)]
        tr = profiler.enable(profiler.ProfilerOptions(
            output_dir=str(tmp_path), export_on_disable=False))
        tr.clear()
        try:
            with caplog.at_level(logging.INFO, logger="paddle_tpu.perf"):
                model.fit(ds, batch_size=4, epochs=2, verbose=1)
        finally:
            profiler.disable(export=False)
        # INFO summary per epoch
        epoch_logs = [r.message for r in caplog.records
                      if "hapi/epoch" in r.message]
        assert len(epoch_logs) == 2
        parsed = json.loads(epoch_logs[0].split("] ", 1)[1])
        assert parsed["steps"] == 2 and parsed["avg_step_ms"] > 0
        # span per train batch + per-epoch gauge in the trace
        spans = [e for e in tr.events if e.name == "hapi/train_batch"]
        assert len(spans) == 4
        assert any(e.name == "hapi/avg_step_ms" for e in tr.events
                   if e.ph == "C")
        assert model._last_epoch_summary["epoch"] == 1
        out = capsys.readouterr().out
        assert "done:" in out and "ms/step" in out
        tr.clear()


class TestScanDeclineLogging:
    def test_can_scan_decline_logs_info(self, caplog):
        import paddle_tpu.nn as nn
        from paddle_tpu.nn.scan import can_scan
        mismatched = [nn.Linear(4, 4), nn.Linear(4, 8)]
        with caplog.at_level(logging.INFO, logger="paddle_tpu.perf"):
            assert not can_scan(mismatched)
        assert any("scan/declined" in r.message
                   and "parameter shapes" in r.message
                   for r in caplog.records)
        # matching stacks stay silent
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="paddle_tpu.perf"):
            assert can_scan([nn.Linear(4, 4), nn.Linear(4, 4)])
        assert not any("scan/declined" in r.message
                       for r in caplog.records)

    def test_full_save_interval_drop_logs_info(self, caplog):
        import paddle_tpu.nn as nn
        from paddle_tpu.nn.scan import scan_layers
        layers = [nn.Linear(4, 4) for _ in range(4)]
        x = paddle.to_tensor(np.random.RandomState(0)
                             .rand(2, 4).astype("float32"))
        with caplog.at_level(logging.INFO, logger="paddle_tpu.perf"):
            with pytest.warns(UserWarning, match="full_save_interval"):
                out = scan_layers(layers, x, remat=True,
                                  full_save_interval=3)   # 3 !| 4
        assert tuple(out.shape) == (2, 4)
        assert any("scan/full_save_interval_dropped" in r.message
                   for r in caplog.records)


class TestMoeDroplessDowngradeWarning:
    def test_warns_once_under_ep(self, reset_fleet):
        import jax
        if jax.device_count() < 4:
            pytest.skip("needs 4 virtual devices")
        from paddle_tpu.distributed import fleet
        from paddle_tpu.incubate.distributed.models.moe import MoELayer
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": -1, "mp_degree": 1,
                            "pp_degree": 1, "sharding_degree": 1,
                            "sep_degree": 1, "ep_degree": 4}
        fleet.init(strategy=s)
        with pytest.warns(UserWarning, match="dropless=True requested"):
            MoELayer(8, 16, 4, gate={"top_k": 2, "dropless": True})
        # non-dropless gate under EP stays silent
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            MoELayer(8, 16, 4, gate={"top_k": 2})
