"""ISSUE 13: per-tenant SLO accounting + the bench regression sentinel.

SLO half (``profiler/slo.py``): declarative rule validation, the
request-level predicates, rolling-window attainment, error-budget
burn-rate math, alert fire/clear hysteresis, per-tenant label
partitioning (with the bounded-label overflow), and the ``slo/*``
metric family landing in the tracker's registry. Deterministic — the
clock is injected, no sleeps.

Sentinel half (``tools/check_bench_regression.py``): the acceptance
criteria as subprocess tests — ``--self-test`` passes, a synthetic 20%
decode tok/s drop is flagged nonzero, an empty trajectory has nothing
to compare, and cross-backend records are skipped.

Part of the ``observability`` gate (``-m observability``).
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from paddle_tpu.profiler.metrics import MetricsRegistry
from paddle_tpu.profiler.slo import SLORule, SLOTracker

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SENTINEL = os.path.join(REPO, "tools", "check_bench_regression.py")


def _req(ttft_s=0.01, total_s=0.1, error=None, tenant="a",
         priority=0, first=True):
    return SimpleNamespace(t_arrive=0.0,
                           t_first=ttft_s if first else 0.0,
                           t_done=total_s, error=error,
                           tenant=tenant, priority=priority)


def _tracker(rule, **kw):
    clock = [0.0]
    reg = MetricsRegistry()
    tr = SLOTracker([rule], registry=reg,
                    now_fn=lambda: clock[0], **kw)
    return tr, clock, reg


# ---- rule validation + predicates ------------------------------------------

def test_rule_validation():
    with pytest.raises(ValueError):
        SLORule("x", kind="nope")
    with pytest.raises(ValueError):
        SLORule("x", kind="ttft")            # threshold required
    with pytest.raises(ValueError):
        SLORule("x", kind="success", target=1.0)   # no budget to burn
    with pytest.raises(ValueError):
        SLOTracker([SLORule("a", kind="success"),
                    SLORule("a", kind="success")])  # dup names


def test_predicates():
    ttft = SLORule("t", kind="ttft", threshold_ms=50)
    assert ttft.good(_req(ttft_s=0.049))
    assert not ttft.good(_req(ttft_s=0.051))
    assert not ttft.good(_req(first=False))   # no first token = miss
    e2e = SLORule("e", kind="e2e", threshold_ms=200)
    assert e2e.good(_req(total_s=0.199))
    assert not e2e.good(_req(total_s=0.3))
    ok = SLORule("s", kind="success")
    assert ok.good(_req())
    assert not ok.good(_req(error=RuntimeError("x")))


# ---- windows, burn, alerts -------------------------------------------------

def test_burn_rate_math_and_alert_hysteresis():
    """target 0.9 → budget 0.1. Ten good then five bad: attainment
    10/15, burn (1/3)/0.1 ≈ 3.33 ≥ 2.0 → ONE alert fires (not one
    per event); recovery clears it; re-breach fires a second."""
    rule = SLORule("ttft", kind="ttft", threshold_ms=50, target=0.9,
                   burn_alert=2.0, min_events=5, window_s=100.0)
    tr, clock, reg = _tracker(rule)
    for _ in range(10):
        clock[0] += 1.0
        assert tr.record(_req()) == []
    assert tr.attainment("ttft", tenant="a") == 1.0
    fired = []
    for _ in range(5):
        clock[0] += 1.0
        fired += tr.record(_req(ttft_s=9.9))
    assert len(fired) == 1
    a = fired[0]
    assert a["rule"] == "ttft" and a["labels"] == {"tenant": "a"}
    # the alert fires at the FIRST breaching event: the 3rd miss
    # (3/13 missing / 0.1 budget = 2.31 ≥ 2.0), not after the batch
    assert a["burn_rate"] == pytest.approx((3 / 13) / 0.1, rel=1e-3)
    # the live record refreshes as the burn worsens
    assert tr.alerts()[0]["burn_rate"] == pytest.approx(
        (5 / 15) / 0.1, rel=1e-3)
    assert tr.alerts() and tr.alerts()[0]["rule"] == "ttft"
    # the window expires the misses → burn drops → alert clears
    clock[0] += 200.0
    assert tr.record(_req()) == []
    assert tr.alerts() == []
    # a fresh breach is a NEW alert activation
    fired = []
    for _ in range(20):
        clock[0] += 1.0
        fired += tr.record(_req(ttft_s=9.9))
    assert len(fired) == 1
    assert tr.summary()["alerts_fired"] == 2


def test_min_events_guards_cold_windows():
    """One unlucky request in a nearly-empty window must not page
    anyone."""
    rule = SLORule("t", kind="ttft", threshold_ms=50, target=0.99,
                   min_events=10)
    tr, clock, _ = _tracker(rule)
    clock[0] += 1.0
    assert tr.record(_req(ttft_s=9.9)) == []   # burn huge, n=1: quiet
    assert tr.alerts() == []


def test_per_tenant_partitioning_and_metrics():
    rule = SLORule("t", kind="ttft", threshold_ms=50, target=0.9,
                   min_events=2, burn_alert=2.0)
    tr, clock, reg = _tracker(rule)
    for _ in range(4):
        clock[0] += 1.0
        tr.record(_req(tenant="good"))
        tr.record(_req(ttft_s=9.9, tenant="bad"))
    assert tr.attainment("t", tenant="good") == 1.0
    assert tr.attainment("t", tenant="bad") == 0.0
    alerts = tr.alerts()
    assert len(alerts) == 1                     # only the bad tenant
    assert alerts[0]["labels"] == {"tenant": "bad"}
    snap = reg.snapshot()
    assert snap['slo/attainment{rule="t",tenant="good"}'] == 1.0
    assert snap['slo/misses{rule="t",tenant="bad"}'] == 4
    assert snap['slo/alerts_fired{rule="t",tenant="bad"}'] == 1
    assert snap["slo/alerts_active"] == 1
    s = tr.summary()
    assert s["worst_attainment"] == 0.0
    assert s["rules"]["t"]["labels"]["bad"]["alerting"] is True
    assert s["rules"]["t"]["labels"]["good"]["alerting"] is False


def test_label_space_is_bounded():
    """An adversarial tenant-id stream folds into "_overflow" instead
    of growing the tracker without limit."""
    rule = SLORule("t", kind="success", target=0.9, by=("tenant",))
    tr, clock, _ = _tracker(rule, max_labels=8)
    for i in range(50):
        clock[0] += 1.0
        tr.record(_req(tenant=f"tenant-{i}"))
    assert len(tr._windows) <= 9    # 8 + the overflow bucket
    assert ("t", ("_overflow",)) in tr._windows


def test_alert_self_resolves_without_new_traffic():
    """A tenant that had a bad minute and then went SILENT must not
    page forever: the read side prunes the window and clears the
    alert once the misses age out (review fix)."""
    rule = SLORule("t", kind="ttft", threshold_ms=50, target=0.9,
                   min_events=3, burn_alert=2.0, window_s=100.0)
    tr, clock, reg = _tracker(rule)
    for _ in range(5):
        clock[0] += 1.0
        tr.record(_req(ttft_s=9.9))
    assert tr.alerts()            # firing
    clock[0] += 1000.0            # tenant goes silent; window ages out
    assert tr.alerts() == []      # read side cleared it — no record()
    assert tr.summary()["alerts_active"] == []
    assert reg.snapshot()["slo/alerts_active"] == 0


def test_metrics_scrape_path_refreshes_gauges():
    """A Prometheus-only deployment (no /statusz reads) must not page
    forever on an expired breach: the exposition pre_scrape hook
    calls tracker.refresh(), which prunes windows and rewrites the
    burn/attainment/alerts_active gauges (review fix)."""
    rule = SLORule("t", kind="ttft", threshold_ms=50, target=0.9,
                   min_events=3, burn_alert=2.0, window_s=100.0)
    tr, clock, reg = _tracker(rule)
    for _ in range(5):
        clock[0] += 1.0
        tr.record(_req(ttft_s=9.9))
    kv = 'slo/burn_rate{rule="t",tenant="a"}'
    assert reg.snapshot()[kv] == 10.0
    assert reg.snapshot()["slo/alerts_active"] == 1
    clock[0] += 1000.0      # tenant silent; ONLY /metrics is scraped
    tr.refresh()            # what the server's pre_scrape hook runs
    snap = reg.snapshot()
    assert snap[kv] == 0.0
    assert snap['slo/attainment{rule="t",tenant="a"}'] == 1.0
    assert snap["slo/alerts_active"] == 0


def test_cancelled_requests_do_not_burn_budget():
    """Client cancellations are voluntary: excluded from the window
    by default (review fix); count_cancelled=True opts back in."""
    rule = SLORule("s", kind="success", target=0.9, min_events=2,
                   burn_alert=2.0)
    tr, clock, _ = _tracker(rule)
    for _ in range(5):
        clock[0] += 1.0
        cancelled = _req(error=RuntimeError("cancelled"))
        cancelled.finish_reason = "cancelled"
        assert tr.record(cancelled) == []
    assert tr.attainment("s", tenant="a") == 1.0   # nothing booked
    assert tr.alerts() == []
    strict = SLORule("s2", kind="success", target=0.9, min_events=2,
                     burn_alert=2.0, count_cancelled=True)
    tr2, clock2, _ = _tracker(strict)
    for _ in range(5):
        clock2[0] += 1.0
        cancelled = _req(error=RuntimeError("cancelled"))
        cancelled.finish_reason = "cancelled"
        tr2.record(cancelled)
    assert tr2.alerts()            # opted in: misses count


def test_partition_by_priority():
    rule = SLORule("t", kind="success", target=0.9,
                   by=("tenant", "priority"))
    tr, clock, _ = _tracker(rule)
    clock[0] += 1.0
    tr.record(_req(tenant="a", priority=1))
    tr.record(_req(tenant="a", priority=0,
                   error=RuntimeError("x")))
    s = tr.summary()["rules"]["t"]["labels"]
    assert s["a,1"]["attainment"] == 1.0
    assert s["a,0"]["attainment"] == 0.0


# ---- the bench regression sentinel -----------------------------------------

def _sentinel(*args):
    return subprocess.run([sys.executable, SENTINEL, *args],
                          capture_output=True, text=True, cwd=REPO)


def test_sentinel_self_test_passes():
    p = _sentinel("--self-test")
    assert p.returncode == 0, p.stdout + p.stderr
    assert "all scenarios behave" in p.stdout


def test_sentinel_with_no_trajectory_has_nothing_to_compare(tmp_path):
    """The repo keeps no bench rounds of its own (the ledger is the
    driver's): with the default glob matching nothing, with or without
    a fresh record, the sentinel says so and passes."""
    p = _sentinel()
    assert p.returncode == 0, p.stdout + p.stderr
    assert "nothing to compare" in p.stdout + p.stderr
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        {"decode_value": 1.0, "provenance": {"backend": "tpu"}}))
    p = _sentinel("--fresh", str(fresh))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "nothing to compare" in p.stdout + p.stderr


def test_sentinel_flags_synthetic_20pct_decode_drop(tmp_path):
    """THE acceptance scenario: decode tok/s drops 20% vs the
    trajectory → nonzero exit naming the key."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 2270.73,
                    "provenance": {"backend": "tpu"}}}))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        {"decode_value": 2270.73 * 0.80,
         "provenance": {"backend": "tpu"}}))
    p = _sentinel("--fresh", str(fresh), "--glob",
                  str(tmp_path / "BENCH_r0*.json"))
    assert p.returncode == 1, p.stdout + p.stderr
    assert "REGRESSION" in p.stdout + p.stderr
    assert "decode_value" in p.stdout + p.stderr


def test_sentinel_skips_cross_backend(tmp_path):
    """A CPU-smoke record can never 'regress' against a TPU round —
    but only when BOTH backends are known and differ."""
    base = tmp_path / "BENCH_r01.json"
    base.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 2254.0,
                    "provenance": {"backend": "tpu"}}}))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        {"decode_value": 30.0, "provenance": {"backend": "cpu"}}))
    p = _sentinel("--fresh", str(fresh), "--glob",
                  str(tmp_path / "BENCH_r0*.json"))
    assert p.returncode == 0, p.stdout + p.stderr


def test_sentinel_never_compares_fresh_against_itself(tmp_path):
    """--fresh pointing at a file already in the trajectory must be
    compared against the EARLIER rounds, not itself (review fix: a
    committed regression would otherwise self-mask at +0.0%)."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 2000.0}}))
    bad = tmp_path / "BENCH_r02.json"
    bad.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 1500.0}}))   # -25% vs r01
    p = _sentinel("--fresh", str(bad), "--glob",
                  str(tmp_path / "BENCH_r0*.json"))
    assert p.returncode == 1, p.stdout + p.stderr
    assert "BENCH_r01.json" in p.stdout + p.stderr


def test_sentinel_wrapper_and_outage_rounds(tmp_path):
    """Driver wrappers unwrap; parsed=null outage rounds are skipped;
    the newest parsed round is the fresh record by default."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 2000.0}}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"cmd": "x", "rc": 124, "tail": "boom", "parsed": None}))
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"cmd": "x", "rc": 0, "tail": "",
         "parsed": {"decode_value": 1500.0}}))   # -25% vs r01
    p = _sentinel("--glob", str(tmp_path / "BENCH_r0*.json"))
    assert p.returncode == 1, p.stdout + p.stderr
    assert "BENCH_r01.json" in p.stdout + p.stderr
