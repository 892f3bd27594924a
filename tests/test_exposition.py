"""ISSUE 13: metrics federation + the live exposition endpoint.

Fast observability-gate tests (``tools/run_gates.py`` observability
gate; ``-m observability``):

- FederatedRegistry semantics: counters summed with replica-labeled
  children, MONOTONIC totals across a supervised-rebuild registry
  swap and remove_source, gauges per-replica only, deterministic
  histogram merges.
- ObservabilityServer endpoints: /metrics parses as Prometheus text,
  /statusz is one JSON document with guarded sections, /healthz,
  unknown paths 404 — and responses are never torn.
- The ISSUE-13 churn contract: /metrics + /statusz scraped
  concurrently while the fleet kills and rebuilds a replica — every
  scrape parses, federated counters never go backwards.
- Flight-recorder bundles dumped while a fleet is live carry the
  FEDERATED snapshot (sibling state in a replica-death post-mortem).
- The docs reconciliation pins: every ``engine.gauges()`` /
  ``fleet.gauges()`` key is documented in docs/serving.md.
- The fleet-tier observability overhead stays under the 2% pin.
"""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, ServingFleet
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import flight_recorder as frec
from paddle_tpu.profiler.exposition import ObservabilityServer
from paddle_tpu.profiler.metrics import (FederatedRegistry,
                                         MetricsRegistry)
from paddle_tpu.testing import FaultInjector

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        cfg.num_hidden_layers = 1
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        _MODEL = (m, cfg)
    return _MODEL


def _factory(**kw):
    m, _ = _model()
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 48)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("greedy", True)
    return lambda: ContinuousBatchingEngine(m, **kw)


def _prompts(n, seed=0, lo=4, hi=9):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        (int(rng.randint(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


# ---- FederatedRegistry semantics -------------------------------------------

def test_federated_counters_sum_with_labels():
    fed = FederatedRegistry(include_default=False)
    fed.counter("fleet/submitted").inc(3)
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("serving/tokens_emitted").inc(10)
    r1.counter("serving/tokens_emitted").inc(7)
    fed.add_source("0", lambda: r0)
    fed.add_source("1", lambda: r1)
    s = fed.snapshot()
    assert s["fleet/submitted"] == 3          # local metrics intact
    assert s["serving/tokens_emitted"] == 17  # summed total
    assert s['serving/tokens_emitted{replica="0"}'] == 10
    assert s['serving/tokens_emitted{replica="1"}'] == 7


def test_federated_totals_monotonic_across_registry_swap():
    """A supervised engine rebuild swaps engine.metrics for a fresh
    registry whose counters restart at zero — the fleet total must
    NOT go backwards (the watermark folds the dead instance's mass
    into the base)."""
    fed = FederatedRegistry(include_default=False)
    src = {"0": MetricsRegistry()}
    src["0"].counter("serving/tokens_emitted").inc(100)
    fed.add_source("0", lambda: src["0"])
    assert fed.snapshot()["serving/tokens_emitted"] == 100
    # rebuild: fresh registry, new instance, restarts at 2
    src["0"] = MetricsRegistry()
    src["0"].counter("serving/tokens_emitted").inc(2)
    s = fed.snapshot()
    assert s["serving/tokens_emitted"] == 102
    assert s['serving/tokens_emitted{replica="0"}'] == 102
    # an in-place reset (counter goes backwards) is also banked
    src["0"].counter("serving/tokens_emitted").set(0)
    assert fed.snapshot()["serving/tokens_emitted"] == 102
    src["0"].counter("serving/tokens_emitted").inc(5)
    assert fed.snapshot()["serving/tokens_emitted"] == 107


def test_federated_rebuild_keeps_unminted_families():
    """A rebuilt engine that cancelled requests in a past life but
    not (yet) this one must still show the banked mass — emitting
    only families present in the FRESH registry would make the fleet
    total dip to zero (review fix)."""
    fed = FederatedRegistry(include_default=False)
    src = {"0": MetricsRegistry()}
    src["0"].counter("serving/requests_cancelled").inc(5)
    src["0"].counter("serving/tokens_emitted").inc(50)
    fed.add_source("0", lambda: src["0"])
    assert fed.snapshot()["serving/requests_cancelled"] == 5
    # rebuild: the fresh registry only ever mints tokens_emitted
    src["0"] = MetricsRegistry()
    src["0"].counter("serving/tokens_emitted").inc(3)
    s = fed.snapshot()
    assert s["serving/requests_cancelled"] == 5          # banked mass
    assert s['serving/requests_cancelled{replica="0"}'] == 5
    assert s["serving/tokens_emitted"] == 53
    # prometheus render carries it too
    assert "paddle_serving_requests_cancelled 5" \
        in fed.export_prometheus()


def test_federated_remove_source_retires_totals():
    fed = FederatedRegistry(include_default=False)
    r0 = MetricsRegistry()
    r0.counter("serving/prefills").inc(9)
    fed.add_source("0", lambda: r0)
    assert fed.snapshot()["serving/prefills"] == 9
    fed.remove_source("0")
    s = fed.snapshot()
    assert s["serving/prefills"] == 9          # scale_down keeps history
    assert 'serving/prefills{replica="0"}' not in s


def test_federated_gauges_stay_per_replica():
    """Summing two occupancy gauges means nothing: gauges federate as
    labeled children ONLY, never an unlabeled total."""
    fed = FederatedRegistry(include_default=False)
    r0 = MetricsRegistry()
    r0.gauge("obs/overhead_frac").set(0.01)
    fed.add_source("0", lambda: r0)
    s = fed.snapshot()
    assert s['obs/overhead_frac{replica="0"}'] == 0.01
    assert "obs/overhead_frac" not in s


def test_federated_histogram_merge_deterministic():
    fed = FederatedRegistry(include_default=False)
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    for v in (1.0, 2.0, 3.0):
        r0.histogram("serving/ttft_ms").observe(v)
    for v in (10.0, 20.0):
        r1.histogram("serving/ttft_ms").observe(v)
    fed.add_source("0", lambda: r0)
    fed.add_source("1", lambda: r1)
    a = fed.snapshot()["serving/ttft_ms"]
    b = fed.snapshot()["serving/ttft_ms"]
    assert a == b                      # same fleet state, same answer
    assert a["count"] == 5
    assert a["sum"] == 36.0
    assert a["min"] == 1.0 and a["max"] == 20.0
    assert a["p50"] == 3.0             # merged reservoir percentile
    # labeled children keep the per-replica view
    s = fed.snapshot()
    assert s['serving/ttft_ms{replica="1"}']["count"] == 2


def test_federated_prometheus_render():
    fed = FederatedRegistry(include_default=False)
    r0 = MetricsRegistry()
    r0.counter("serving/tokens_emitted").inc(4)
    r0.histogram("serving/ttft_ms").observe(5.0)
    fed.add_source("0", lambda: r0)
    txt = fed.export_prometheus()
    assert "paddle_serving_tokens_emitted 4" in txt
    assert 'paddle_serving_tokens_emitted{replica="0"} 4' in txt
    assert 'quantile="0.99"' in txt
    assert "paddle_serving_ttft_ms_count 1" in txt


def test_federated_dead_provider_keeps_last_totals():
    """A provider that raises mid-teardown must not dip the totals or
    fail the scrape."""
    fed = FederatedRegistry(include_default=False)
    r0 = MetricsRegistry()
    r0.counter("serving/prefills").inc(6)
    alive = [True]

    def provider():
        if not alive[0]:
            raise RuntimeError("torn down")
        return r0

    fed.add_source("0", provider)
    assert fed.snapshot()["serving/prefills"] == 6
    alive[0] = False
    assert fed.snapshot()["serving/prefills"] == 6


# ---- ObservabilityServer ---------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


_PROM_LINE = re.compile(
    r"^[A-Za-z_:][A-Za-z0-9_:]*(\{[^}]*\})? (\S+)$")


def _assert_prom_parses(text):
    assert text.endswith("\n")
    types = []
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            types.append(line.split()[2])
            continue
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"unparseable prom line: {line!r}"
        float(m.group(2))      # value must be numeric (inf/nan legal)
    # Prometheus parsers reject a second TYPE header for one family
    assert len(types) == len(set(types)), \
        [t for t in types if types.count(t) > 1]


def test_server_endpoints_and_guarded_sections():
    reg = MetricsRegistry()
    reg.counter("serving/tokens_emitted").inc(11)
    reg.histogram("serving/ttft_ms").observe(3.5)
    with ObservabilityServer(registry=reg, sections={
            "ok": lambda: {"n": 1},
            "boom": lambda: (_ for _ in ()).throw(RuntimeError("x")),
    }) as srv:
        m = _get(srv.url + "/metrics")
        _assert_prom_parses(m)
        assert "paddle_serving_tokens_emitted 11" in m
        doc = json.loads(_get(srv.url + "/statusz"))
        assert doc["ok"] == {"n": 1}
        assert "RuntimeError" in doc["boom"]["error"]   # guarded
        assert _get(srv.url + "/healthz") == "ok\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/nope")
        assert ei.value.code == 404


def test_scrapes_are_metered():
    from paddle_tpu.profiler.metrics import get_registry
    before = get_registry().counter("obs/scrapes").value
    with ObservabilityServer(registry=MetricsRegistry()) as srv:
        _get(srv.url + "/healthz")
        _get(srv.url + "/metrics")
    assert get_registry().counter("obs/scrapes").value >= before + 2


# ---- docs reconciliation pins (ISSUE-13 satellite) -------------------------

def _serving_md_names():
    with open(os.path.join(REPO, "docs", "serving.md"),
              encoding="utf-8") as f:
        return set(re.findall(r"`([A-Za-z0-9_./]+)`", f.read()))


def test_engine_gauges_match_docs():
    """Every engine.gauges() key is documented in docs/serving.md —
    the PR-12 prefix_cache keys outgrew the docs once; never again."""
    eng = _factory()()
    documented = _serving_md_names()
    missing = set(eng.gauges()) - documented
    assert not missing, f"undocumented gauges() keys: {sorted(missing)}"


def test_fleet_gauges_match_docs():
    fleet = ServingFleet(_factory(), num_replicas=1)
    documented = _serving_md_names()
    missing = set(fleet.gauges()) - documented
    assert not missing, \
        f"undocumented fleet.gauges() keys: {sorted(missing)}"


# ---- fleet federation end-to-end -------------------------------------------

@pytest.mark.slow
def test_fleet_metrics_is_federated():
    fleet = ServingFleet(_factory(), num_replicas=2,
                         retry_backoff_s=0.01)
    prompts = _prompts(6)
    fids = [fleet.submit(p, 3) for p in prompts]
    done = fleet.run()
    assert len(done) == len(fids)
    s = fleet.metrics.snapshot()
    total = s["serving/tokens_emitted"]
    per = [s.get(f'serving/tokens_emitted{{replica="{i}"}}', 0)
           for i in (0, 1)]
    assert total == sum(per) and total > 0
    assert s["fleet/completed"] == len(fids)
    # the default registry rides along unlabeled
    assert "obs/ring_events" in s


@pytest.mark.slow
def test_fleet_obs_overhead_under_pin():
    """The fleet-tier instrumentation (SLO booking, trace-log feeds,
    timeline reconstruction) stays under the 2% obs overhead pin."""
    from paddle_tpu.profiler.slo import SLORule
    fleet = ServingFleet(
        _factory(), num_replicas=2, retry_backoff_s=0.01,
        slo_rules=[SLORule("ttft", kind="ttft", threshold_ms=60_000,
                           target=0.9)])
    fids = [fleet.submit(p, 4, tenant=f"t{i % 2}")
            for i, p in enumerate(_prompts(8, seed=3))]
    done = fleet.run()
    assert len(done) == len(fids)
    frac = fleet.gauges()["obs_overhead_frac"]
    assert 0.0 <= frac < 0.02, frac


# ---- exposition under churn (the chaos contract) ---------------------------

@pytest.mark.fault
def test_exposition_under_replica_churn():
    """Scrape /metrics and /statusz concurrently while a replica is
    killed hard enough to trip its breaker mid-run: every scrape
    parses, federated counters stay monotonic across the supervised
    rebuilds, no torn snapshot."""
    fleet = ServingFleet(_factory(), num_replicas=3, max_restarts=1,
                         retry_backoff_s=0.01)
    prompts = _prompts(10, seed=7)
    stop = threading.Event()
    metrics_bodies, statusz_bodies, errors = [], [], []

    def scraper(path, sink):
        while not stop.is_set():
            try:
                sink.append(_get(srv.url + path))
            except Exception as e:  # noqa: BLE001 — a failed scrape
                errors.append(repr(e))   # IS the test failure
    srv = fleet.observability_server()
    threads = [threading.Thread(target=scraper,
                                args=("/metrics", metrics_bodies)),
               threading.Thread(target=scraper,
                                args=("/statusz", statusz_bodies))]
    try:
        for t in threads:
            t.start()
        with FaultInjector() as fi:
            # after ONE step: tiny CPU workloads drain in very few
            # scheduler turns, and the kill must land mid-run
            fi.kill_replica(1, times=10_000, after_steps=1)
            fids = [fleet.submit(p, 6) for p in prompts]
            done = fleet.run()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        srv.stop()
    assert not errors, errors
    assert len(done) == len(fids)
    assert fleet.gauges()["breaker_open"] >= 1    # the kill landed
    assert metrics_bodies and statusz_bodies      # scrapes happened
    totals = []
    for body in metrics_bodies:
        _assert_prom_parses(body)                 # never torn
        m = re.search(r"^paddle_serving_tokens_emitted ([0-9.]+)$",
                      body, re.M)
        if m:
            totals.append(float(m.group(1)))
    # monotonic across the rebuild: the dead replica's counters fold
    # into the federated base instead of vanishing
    assert all(b >= a for a, b in zip(totals, totals[1:])), totals
    for body in statusz_bodies:
        doc = json.loads(body)                    # always parseable
        assert {"fleet", "replicas", "slowest_traces"} <= set(doc)


# ---- flight-recorder federated bundles (ISSUE-13 satellite) ----------------

@pytest.mark.slow
@pytest.mark.fault
def test_bundle_carries_federated_snapshot(tmp_path):
    """A replica-death post-mortem dumped while the fleet is live
    shows SIBLING state: the bundle metrics are the federated
    snapshot, replica-labeled."""
    rec = frec.FlightRecorder(bundle_dir=str(tmp_path))
    frec.install(rec)
    try:
        fleet = ServingFleet(_factory(), num_replicas=2,
                             max_restarts=1, retry_backoff_s=0.01)
        with FaultInjector() as fi:
            fi.kill_replica(1, times=10_000, after_steps=1)
            fids = [fleet.submit(p, 6) for p in _prompts(12, seed=5)]
            done = fleet.run()
        assert len(done) == len(fids)
        bundle_path = tmp_path / "flight_bundle.json"
        assert bundle_path.exists()    # the supervisor dumped
        doc = json.loads(bundle_path.read_text())
        labeled = [k for k in doc["metrics"]
                   if k.startswith("serving/tokens_emitted{replica=")]
        assert labeled, sorted(doc["metrics"])[:20]
        assert rec.incidents()         # post-mortems preserved
        # the registration is run()-scoped: restored afterwards
        assert rec.fleet_registry is None
    finally:
        frec.uninstall()
