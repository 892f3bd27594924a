"""ISSUE-9 tentpole: the typed metrics registry — semantics, thread
safety (exact totals under concurrent increment), BOUNDED reservoirs
(memory flat over 100k completions), exposition formats, atomic export
under fault injection, and the <2% instrumentation-overhead pin on the
hot serving loop."""

import errno
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import metrics
from paddle_tpu.profiler.metrics import (Histogram, MetricsRegistry,
                                         declare)
from paddle_tpu.testing import FaultInjector

# every test-local metric name must satisfy the convention AND be
# catalog-invisible to the docs lint (the lint only scans paddle_tpu/
# + bench.py, not tests)


# ---- registry semantics ---------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t/c", help="test counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("t/g")
    g.set(2.5)
    assert g.value == 2.5
    g.inc(0.5)
    assert g.value == 3.0
    h = reg.histogram("t/h", capacity=16)
    for v in range(10):
        h.observe(float(v))
    assert h.count == 10 and h.sum == 45.0
    assert h.min == 0.0 and h.max == 9.0
    assert h.percentile(0) == 0.0 and h.percentile(100) == 9.0
    assert 4.0 <= h.percentile(50) <= 5.0


def test_get_or_create_idempotent_and_kind_conflict():
    reg = MetricsRegistry()
    c1 = reg.counter("t/x")
    c2 = reg.counter("t/x")
    assert c1 is c2
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t/x")


def test_name_convention_enforced():
    reg = MetricsRegistry()
    for bad in ("nochannel", "Upper/name", "a/b/c", "a/", "/b",
                "a-b/c", "a/b c"):
        with pytest.raises(ValueError, match="convention"):
            reg.counter(bad)
    with pytest.raises(ValueError, match="convention"):
        declare("Bad/Name", "counter", "x")


def test_declare_catalog_and_kind_consistency():
    declare("t/declared", "counter", "a test declaration")
    cat = metrics.catalog()
    assert cat["t/declared"] == ("counter", "a test declaration")
    with pytest.raises(ValueError, match="re-declared"):
        declare("t/declared", "gauge", "different kind")
    # registration pulls help from the catalog when not given
    reg = MetricsRegistry()
    c = reg.counter("t/declared")
    assert c.help == "a test declaration"
    # registering under a conflicting kind vs the declaration raises
    with pytest.raises(ValueError, match="declared"):
        MetricsRegistry().gauge("t/declared")
    md = metrics.catalog_markdown()
    assert "| `t/declared` | counter | a test declaration |" in md


def test_labels_children():
    reg = MetricsRegistry()
    c = reg.counter("t/lab")
    c.labels(outcome="eos").inc(3)
    c.labels(outcome="length").inc(2)
    c.labels(outcome="eos").inc()          # same child
    snap = reg.snapshot()
    assert snap['t/lab{outcome="eos"}'] == 4
    assert snap['t/lab{outcome="length"}'] == 2
    assert snap["t/lab"] == 0              # parent unlabeled series


def test_snapshot_shapes():
    reg = MetricsRegistry()
    reg.counter("t/c").inc(7)
    reg.gauge("t/g").set(1.5)
    h = reg.histogram("t/h")
    h.observe(2.0)
    snap = reg.snapshot()
    assert snap["t/c"] == 7 and snap["t/g"] == 1.5
    assert snap["t/h"]["count"] == 1 and snap["t/h"]["sum"] == 2.0
    assert snap["t/h"]["p50"] == 2.0
    json.dumps(snap)                       # JSON-ready


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("t/c", help="a counter").inc(3)
    reg.gauge("t/g").set(0.25)
    h = reg.histogram("t/h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = reg.export()
    assert "# HELP paddle_t_c a counter" in text
    assert "# TYPE paddle_t_c counter" in text
    assert "paddle_t_c 3" in text
    assert "# TYPE paddle_t_g gauge" in text
    assert "paddle_t_g 0.25" in text
    assert "# TYPE paddle_t_h summary" in text
    assert 'paddle_t_h{quantile="0.5"} 2.0' in text
    assert "paddle_t_h_sum 6.0" in text
    assert "paddle_t_h_count 3" in text


def test_export_files_atomic_and_valid(tmp_path):
    reg = MetricsRegistry()
    reg.counter("t/c").inc(2)
    p = tmp_path / "metrics.prom"
    reg.export(str(p))
    assert "paddle_t_c 2" in p.read_text()
    j = tmp_path / "metrics.json"
    reg.export_json(str(j))
    assert json.loads(j.read_text())["t/c"] == 2


@pytest.mark.fault
def test_export_fault_never_leaves_torn_file(tmp_path):
    """ENOSPC mid-export: the previous complete file survives, no
    .tmp litter, and the registry itself is unharmed."""
    reg = MetricsRegistry()
    reg.counter("t/c").inc(1)
    p = tmp_path / "m.json"
    reg.export_json(str(p))
    reg.counter("t/c").inc(99)
    with FaultInjector() as fi:
        fi.fail_write("m.json", errno_=errno.ENOSPC)
        with pytest.raises(OSError):
            reg.export_json(str(p))
    assert json.loads(p.read_text())["t/c"] == 1   # old file intact
    assert not os.path.exists(str(p) + ".tmp")
    reg.export_json(str(p))                        # retry wins
    assert json.loads(p.read_text())["t/c"] == 100


# ---- thread safety --------------------------------------------------------

def test_counter_exact_under_concurrent_increment():
    """The prefetcher/scheduler-thread contract: N threads x K incs
    land EXACTLY N*K (python += on a shared int would lose updates)."""
    reg = MetricsRegistry()
    c = reg.counter("t/conc")
    n_threads, per = 8, 5000
    start = threading.Barrier(n_threads)

    def worker():
        start.wait()
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


def test_histogram_exact_count_under_concurrent_observe():
    reg = MetricsRegistry()
    h = reg.histogram("t/hconc", capacity=64)
    n_threads, per = 6, 4000
    start = threading.Barrier(n_threads)

    def worker(seed):
        start.wait()
        for i in range(per):
            h.observe(float(seed * per + i))

    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n_threads * per
    assert h.sample_count <= 64


# ---- bounded reservoirs ---------------------------------------------------

def test_reservoir_bounded_and_faithful_over_100k():
    h = Histogram("t/res", capacity=512)
    rng = np.random.RandomState(7)
    xs = rng.exponential(scale=10.0, size=100_000)
    for v in xs:
        h.observe(float(v))
    assert h.count == 100_000
    assert h.sample_count == 512           # memory flat, forever
    # reservoir percentiles track the true distribution
    true_p50 = float(np.percentile(xs, 50))
    true_p99 = float(np.percentile(xs, 99))
    assert abs(h.percentile(50) - true_p50) / true_p50 < 0.25
    assert abs(h.percentile(99) - true_p99) / true_p99 < 0.40
    assert h.min == float(xs.min()) and h.max == float(xs.max())


def test_reservoir_deterministic_across_instances():
    h1 = Histogram("t/det", capacity=32)
    h2 = Histogram("t/det", capacity=32)
    for v in range(1000):
        h1.observe(float(v))
        h2.observe(float(v))
    assert h1._samples == h2._samples      # crc32-seeded, not hash()


# ---- serving integration --------------------------------------------------

def _tiny_engine(**kw):
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 1
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=16, greedy=True,
                                   **kw)
    return eng, cfg


def test_serving_latency_memory_flat_over_100k_completions():
    """ISSUE-9 satellite: the unbounded _ttft_ms/_itl_ms lists are
    gone — 100k synthetic completions through the engine's latency
    recording path leave a bounded reservoir, exact counts, and a
    working gauges() surface."""
    from paddle_tpu.inference.serving import ServedRequest
    eng, _ = _tiny_engine(latency_reservoir=1024,
                          trace_sample_rate=0.0)
    t = 1000.0
    for i in range(100_000):
        req = ServedRequest(i, np.zeros(4, np.int32), 8)
        req.t_arrive = t
        req.t_first = t + 0.010 + (i % 17) * 1e-4
        req.t_done = req.t_first + 0.050
        req.tokens = [1] * 8
        eng._record_latency(req)
        t += 0.001
    assert eng._h_ttft.count == 100_000
    assert eng._h_itl.count == 100_000
    assert eng._h_ttft.sample_count <= 1024
    assert eng._h_itl.sample_count <= 1024
    g = eng.gauges()
    assert 10.0 <= g["ttft_ms_p50"] <= 12.0
    assert g["ttft_ms_p50"] <= g["ttft_ms_p99"]
    # and the per-engine registry snapshot carries the histograms
    snap = eng.metrics.snapshot()
    assert snap["serving/ttft_ms"]["count"] == 100_000


def test_engine_gauges_schema_unchanged_with_registry_backing():
    """The PR-3/PR-7 gauge schema keys survive the registry migration
    verbatim (schema consumers: bench.py, serving tests)."""
    eng, _ = _tiny_engine()
    g = eng.gauges()
    for k in ("slot_occupancy", "active_occupancy",
              "prefill_overlap_frac", "tokens_per_s",
              "ttft_ms_p50", "ttft_ms_p99", "itl_ms_p50", "itl_ms_p99",
              "compiled_programs", "chunks_dispatched", "chunks_empty",
              "prefill_waves", "unified_steps", "tokens_emitted",
              "prefills", "requests_completed"):
        assert k in g, k
    # _stats keeps its historical mapping surface
    assert eng._stats["tokens_emitted"] == 0
    eng._stats.inc("tokens_emitted", 3)
    assert eng._stats["tokens_emitted"] == 3
    eng.reset_gauges()
    assert eng._stats["tokens_emitted"] == 0


def test_obs_overhead_under_two_percent_on_hot_serving_loop(tmp_path):
    """THE pinned self-measurement contract: with the flight recorder
    installed and per-request tracing sampled, instrumentation costs
    < 2% of the serving hot loop (acceptance criterion; bench emits
    obs_overhead_frac every round)."""
    from paddle_tpu.profiler import flight_recorder as fr
    eng, cfg = _tiny_engine(trace_sample_rate=0.5)
    fr.install(capacity=256, bundle_dir=str(tmp_path))
    try:
        rng = np.random.RandomState(3)
        for plen, n in [(5, 8), (9, 8), (13, 8), (7, 8), (11, 8)]:
            eng.add_request(rng.randint(0, cfg.vocab_size,
                                        (plen,)).astype(np.int32), n)
        done = eng.run()
        assert len(done) == 5
    finally:
        fr.uninstall()
    g = eng.gauges()
    assert g["obs_overhead_frac"] > 0.0       # actually self-measured
    assert g["obs_overhead_frac"] < 0.02, g["obs_overhead_frac"]
    # the registry gauge is the same measurement snapshotted at
    # _emit_gauges time (before its own cost was booked): same bound,
    # within the drift of that last booking
    reg_val = eng.metrics.gauge("obs/overhead_frac").value
    assert 0.0 < reg_val < 0.02
    assert reg_val == pytest.approx(g["obs_overhead_frac"], rel=0.5)
