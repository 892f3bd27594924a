"""The seeded production-scenario suite (ISSUE 19) on REAL fleets.

Each test drives one named scenario from ``tools/load_harness.
SCENARIOS`` — a deterministic tick-indexed arrival schedule — through
a real tiny-model :class:`ServingFleet` with a :class:`FleetAutoscaler`
closing the loop, and asserts the scenario's own acceptance criteria:
SLO attainment over its declared bar, zero lost work, the autoscaler
reacting when the story says it must (flash-crowd scale-up within a
handful of ticks of onset, backfill after an operator drain, capacity
given back on the idle tail), the flapping invariant, a chip-seconds
bill under the max-size fixed fleet's, and every decision
reconstructable from the fleet's /statusz ``autoscaler`` section.

Hysteresis is paced on the harness's :class:`TickClock` (one virtual
second per tick) so a loaded CI box cannot flake a quiet-period
assertion. The ``autoscale_scenarios`` gate runs this whole module
(slow included); the fast tier gets the flash-crowd and
rolling-upgrade stories.
"""

import os
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  DisaggServingFleet, FleetAutoscaler,
                                  Overloaded, ServingFleet)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler.slo import SLORule

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
import load_harness  # noqa: E402

pytestmark = pytest.mark.autoscale

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

    def make(role=None, **_ignored):
        extra = {"role": role} if role is not None else {}
        return ContinuousBatchingEngine(m, **kw, **extra)
    return make


_CTL_KW = dict(min_replicas=1, max_replicas=3,
               up_cooldown_s=2.0, down_cooldown_s=3.0,
               queue_high=3.0, queue_low=0.5,
               occupancy_high=0.85, occupancy_low=0.35,
               down_stable_ticks=3)


def _run(name, *, num_replicas=1, factory_kw=None, ctl_kw=None,
         fleet_kw=None, steps_per_tick=4):
    sc = load_harness.SCENARIOS[name]
    _, cfg = _model()
    schedule = load_harness.build_scenario(name, vocab=cfg.vocab_size,
                                           seed=0)
    fleet = ServingFleet(_factory(**(factory_kw or {})), num_replicas,
                         slo_rules=[SLORule(**d)
                                    for d in sc["slo_rules"]],
                         hedge_delay_s=None, seed=0,
                         **(fleet_kw or {}))
    clock = load_harness.TickClock()
    kw = dict(_CTL_KW, now_fn=clock)
    kw.update(ctl_kw or {})
    ctl = FleetAutoscaler(fleet, **kw)
    try:
        report = load_harness.run_fleet_scenario(
            fleet, schedule, autoscaler=ctl, clock=clock,
            events=sc.get("events"), shed_exc=Overloaded,
            steps_per_tick=steps_per_tick)
    finally:
        fleet.close()
    return sc, fleet, ctl, clock, report


def _assert_common(sc, ctl, clock, report):
    """The criteria every scenario shares."""
    # the scenario's own SLO bar, judged by the fleet's tracker
    assert report["failed"] == 0, report
    assert report["slo"]["worst_attainment"] >= sc["attainment_bar"], \
        report["slo"]
    # flapping invariant: adjacent applied actions never land closer
    # than the FIRST action's cooldown
    cool = {"scale_up": ctl.up_cooldown_s,
            "scale_down": ctl.down_cooldown_s}
    acts = ctl.actions()
    for a, b in zip(acts, acts[1:]):
        assert b["t"] - a["t"] >= cool[a["action"]], (a, b)
    # the cost model: strictly cheaper than max_replicas provisioned
    # for the whole (virtual) run
    assert report["chip_seconds"] < ctl.max_replicas \
        * ctl.chips_per_replica * clock.t, report["chip_seconds"]
    # every decision reconstructable from the log alone
    for d in report["decisions"]:
        assert {"tick", "t", "action", "rule", "reason",
                "signals"} <= set(d), d
        assert "queue_per_replica" in d["signals"], d


def _statusz_autoscaler(fleet):
    sections = fleet.statusz_sections()
    assert "autoscaler" in sections
    return sections["autoscaler"]()


# ---- fast tier ------------------------------------------------------------

def test_flash_crowd_scales_up_within_onset_window():
    """6x crowd on one shared prefix from tick 8: the controller must
    add capacity within ~6 ticks of onset, shed nothing it accepted,
    and give the capacity back on the quiet tail."""
    sc, fleet, ctl, clock, report = _run("flash_crowd")
    _assert_common(sc, ctl, clock, report)
    assert report["goodput_frac"] >= 0.95, report
    ups = [a for a in ctl.actions() if a["action"] == "scale_up"]
    assert ups, "flash crowd never triggered a scale-up"
    onset = sc["window"][0]
    assert ups[0]["tick"] <= onset + 7, ups[0]
    assert report["peak_ready"] >= 2, report
    # the tail: drains completed, capacity went back toward the floor
    downs = [a for a in ctl.actions() if a["action"] == "scale_down"]
    assert downs, "idle tail never gave capacity back"
    final_ready = sum(1 for r in fleet.replicas.values()
                      if r.takes_weight())
    assert final_ready < report["peak_ready"], report
    # the /statusz section carries the whole story
    sz = _statusz_autoscaler(fleet)
    assert sz["scale_ups"] == len(ups)
    assert sz["scale_downs"] == len(downs)
    logged = [(d["tick"], d["action"]) for d in sz["decisions"]]
    for a in ctl.actions():
        assert (a["tick"], a["action"]) in logged


def test_rolling_upgrade_backfills_drained_capacity():
    """Operator drains at ticks 10 and 22 under steady load: in-flight
    work survives the drains (zero failed) and the controller
    backfills capacity after each drain."""
    sc, fleet, ctl, clock, report = _run(
        "rolling_upgrade", num_replicas=2,
        ctl_kw=dict(min_replicas=2, max_replicas=3))
    _assert_common(sc, ctl, clock, report)
    assert report["shed"] == 0 and report["goodput_frac"] == 1.0, \
        report
    ups = [a for a in ctl.actions() if a["action"] == "scale_up"]
    drain_ticks = sorted(sc["events"])
    assert len(ups) >= 2, "no backfill after the operator drains"
    assert any(a["tick"] > drain_ticks[0] for a in ups), ups
    assert any(a["tick"] > drain_ticks[1] for a in ups), ups
    assert all(a["rule"] == "below_min_replicas" for a in ups), ups
    assert report["min_ready"] >= 1, report
    # the operator's drains are NOT autoscaler decisions — with the
    # floor pinned at 2 the controller itself never drains here
    assert all(a["action"] == "scale_up" for a in ctl.actions())


# ---- slow tier (the gate runs these; tier-1 does not) ---------------------

@pytest.mark.slow
def test_diurnal_capacity_follows_the_curve():
    # 1 fleet turn per tick: the peak's 4 arrivals/tick genuinely
    # outrun a lone 2-slot replica, so capacity has to follow
    sc, fleet, ctl, clock, report = _run("diurnal", steps_per_tick=1)
    _assert_common(sc, ctl, clock, report)
    assert report["peak_ready"] > 1, report
    assert [a for a in ctl.actions()
            if a["action"] == "scale_down"], \
        "capacity never followed the trough back down"


@pytest.mark.slow
def test_tenant_hotspot_attainment_for_both_tenants():
    sc, fleet, ctl, clock, report = _run("tenant_hotspot",
                                         steps_per_tick=1)
    _assert_common(sc, ctl, clock, report)
    labels = report["slo"]["rules"]["ttft"]["labels"]
    assert any("hot" in k for k in labels), labels
    ups = [a for a in ctl.actions() if a["action"] == "scale_up"]
    assert ups, "hot tenant never triggered a scale-up"


@pytest.mark.slow
def test_long_prompt_flood_holds_short_chat_slo():
    sc, fleet, ctl, clock, report = _run(
        "long_prompt_flood",
        factory_kw=dict(max_len=64, prefill_chunk=48))
    _assert_common(sc, ctl, clock, report)
    assert report["goodput_frac"] >= sc["attainment_bar"], report


@pytest.mark.slow
def test_long_prompt_flood_on_disagg_picks_role_from_signals():
    """On a disagg fleet the flood's pressure is role-shaped: every
    scale-up must carry a role, and the role must be the one the
    decision's OWN signal snapshot indicts (deep prefill queue ->
    prefill, saturated decode slots -> decode, both -> both) — the
    role choice is reconstructable from the record, per-role floors
    hold throughout."""
    sc = load_harness.SCENARIOS["long_prompt_flood"]
    _, cfg = _model()
    schedule = load_harness.build_scenario(
        "long_prompt_flood", vocab=cfg.vocab_size, seed=0)
    fleet = DisaggServingFleet(
        _factory(max_len=64, prefill_chunk=48),
        num_prefill=1, num_decode=1, hedge_delay_s=None, seed=0,
        slo_rules=[SLORule(**d) for d in sc["slo_rules"]])
    clock = load_harness.TickClock()
    ctl = FleetAutoscaler(fleet, now_fn=clock,
                          **dict(_CTL_KW, min_replicas=2,
                                 max_replicas=4, queue_high=2.0))
    try:
        report = load_harness.run_fleet_scenario(
            fleet, schedule, autoscaler=ctl, clock=clock,
            shed_exc=Overloaded, steps_per_tick=2)
    finally:
        fleet.close()
    _assert_common(sc, ctl, clock, report)
    ups = [a for a in ctl.actions() if a["action"] == "scale_up"]
    assert ups, "the flood never triggered a scale-up"
    for a in ups:
        sig = a["signals"]
        pre_hot = sig["prefill_queue_per_replica"] >= ctl.queue_high \
            or sig["prefill_ready"] == 0
        dec_hot = sig["decode_occupancy"] >= ctl.occupancy_high \
            or sig["decode_ready"] == 0
        expect = "both" if (pre_hot and dec_hot) \
            else ("decode" if dec_hot else "prefill")
        assert a.get("role") == expect, a
    # role floor held: the drain side never took a role dark
    assert sum(1 for r in fleet.replicas.values()
               if r.live() and fleet._prefill_capable(r)) >= 1
    assert sum(1 for r in fleet.replicas.values()
               if r.live() and fleet._decode_capable(r)) >= 1
