"""ISSUE 13: end-to-end request traces that survive failover/hedging.

The acceptance pin: a SINGLE trace id follows a request through
priority-preemption replay, a supervised engine restart, replica
failover (breaker), and a hedge — with the hedge winner and its
cancelled loser recorded as parts of ONE trace. Plus: the
RequestTraceLog feeds /statusz's slowest-traces render, standalone
engines trace without a fleet, and Tracer.complete reconstructs the
cross-replica chrome timeline on one track.

Part of the ``observability`` gate (``-m observability``).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, ServingFleet
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler.trace import get_trace_log, get_tracer
from paddle_tpu.testing import FaultInjector

pytestmark = pytest.mark.observability

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
    kw.setdefault("num_slots", 1)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("greedy", True)
    return lambda: ContinuousBatchingEngine(m, **kw)


def _prompt(n, seed=0):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)


def _kinds(req):
    return [h["kind"] for h in req.hops]


def _drive_until(fleet, pred, max_turns=200):
    for _ in range(max_turns):
        fleet.step()
        if pred():
            return True
    return False


# ---- standalone engine -----------------------------------------------------

def test_standalone_engine_hops_and_trace_log():
    """Without a fleet, the engine itself records admit/finish hops
    and feeds the process trace log at completion (trace id =
    request id)."""
    log = get_trace_log()
    log.clear()
    eng = _factory(num_slots=2)()
    rid = eng.add_request(_prompt(6), 3, tenant="solo")
    done = eng.run()
    req = done[-1]
    assert req.trace_id is None            # standalone: no fleet mint
    assert _kinds(req) == ["admit", "finish"]
    entries = [e for e in log.recent() if e["trace_id"] == rid]
    assert len(entries) == 1
    e = entries[0]
    assert e["tenant"] == "solo"
    assert e["tokens"] == 3
    assert [h["kind"] for h in e["hops"]] == ["admit", "finish"]


@pytest.mark.slow
def test_preemption_replay_hops():
    """A priority preemption inside ONE engine shows up as
    admit → preempt → admit on the victim's one hop list."""
    eng = _factory(num_slots=1)()
    victim = eng.add_request(_prompt(6), 6, priority=0)
    # drive until the victim occupies the slot
    for _ in range(50):
        eng.step()
        if eng.slot_req[0] is not None:
            break
    assert eng.slot_req[0] is not None
    eng.add_request(_prompt(5, seed=1), 3, priority=5)
    done = {r.request_id: r for r in eng.run()}
    v = done[victim]
    assert v.preemptions >= 1
    kinds = _kinds(v)
    assert kinds.count("admit") >= 2
    assert "preempt" in kinds
    assert kinds.index("preempt") > kinds.index("admit")
    assert kinds[-1] == "finish"


# ---- THE acceptance pin ----------------------------------------------------

@pytest.mark.fault
def test_single_trace_id_through_preempt_restart_failover_and_hedge():
    """One client request experiences, in order: priority preemption
    with recompute replay, a supervised engine restart, replica
    failover past the restart budget (breaker), and a hedge to a
    second sibling — all under ONE trace id, with the hedge winner
    and its loser both recorded in the one hop list, and exactly one
    delivery."""
    get_trace_log().clear()    # the log is process-wide; earlier
    # tests' request ids collide with this fleet's trace ids
    # hedging starts DISABLED (huge delay) so the failover happens
    # first; the delay is dropped after the breaker opens, staging
    # the four mechanisms in a deterministic order
    fleet = ServingFleet(_factory(), num_replicas=1, max_restarts=1,
                         retry_backoff_s=0.001,
                         hedge_delay_s=1e9)
    # a long prompt (4 prefill chunks) so the victim is mid-prefill
    # (no first token) through every disruption — hedging requires a
    # straggler that never produced a token
    vfid = fleet.submit(_prompt(30), 4, priority=0)
    v = fleet.request(vfid)
    assert v.trace_id == vfid
    assert _drive_until(
        fleet, lambda: "admit" in _kinds(fleet.request(vfid)))
    # (1) PREEMPTION: a strictly-higher-priority arrival takes the
    # only slot; the victim is evicted for recompute
    hfid = fleet.submit(_prompt(5, seed=2), 2, priority=5)
    assert _drive_until(
        fleet, lambda: "preempt" in _kinds(fleet.request(vfid)))
    # two cold siblings (warm=False: keep their hop lists clean) for
    # the failover target and the hedge target
    fleet.scale_up(warm=False)
    fleet.scale_up(warm=False)
    with FaultInjector() as fi:
        # (2)+(3): replica 0 dies on every step from here on — the
        # first death is absorbed by the supervisor (engine_restart
        # hop), the second exhausts max_restarts=1 and opens the
        # breaker; the victim fails over to a sibling, and with no
        # first token after hedge_delay_s it is (4) hedged to the
        # other sibling
        fi.kill_replica(0, times=10_000, after_steps=0)
        # drive until the breaker has opened and the victim was
        # salvaged onto a sibling...
        assert _drive_until(
            fleet, lambda: "salvage" in _kinds(fleet.request(vfid)))
        # ...then enable hedging: the victim is mid-prefill on its
        # failover replica with no first token — a straggler
        fleet.hedge_delay_s = 0.0005
        fleet.run()
    # fleet.completed accumulates every delivery, including the high-
    # priority request if it finished during the staged drive turns
    by = {}
    for r in fleet.completed:
        assert r.request_id not in by, "duplicated delivery"
        by[r.request_id] = r
    assert sorted(by) == sorted([vfid, hfid])      # exactly-once
    vreq = by[vfid]
    assert vreq.error is None, vreq.error
    assert vreq.trace_id == vfid

    hops = vreq.hops
    kinds = [h["kind"] for h in hops]
    # every stage left its hop, in causal order, in ONE list
    for stage in ("submit", "assign", "admit", "preempt",
                  "engine_restart", "salvage", "hedge", "finish",
                  "deliver"):
        assert stage in kinds, (stage, kinds)
    assert kinds.index("preempt") < kinds.index("engine_restart") \
        < kinds.index("salvage") < kinds.index("hedge")
    assert kinds.count("deliver") == 1             # one delivery
    # the trace crossed replicas: admitted on the dead replica AND on
    # at least one sibling (failover or hedge copy)
    admit_reps = {h.get("replica") for h in hops
                  if h["kind"] == "admit"}
    assert 0 in admit_reps and (1 in admit_reps or 2 in admit_reps), \
        admit_reps
    # winner + loser both recorded: the hedge produced two attempts,
    # each of which reached a terminal hop in this same trace
    assert kinds.count("finish") >= 2, kinds
    g = fleet.gauges()
    assert g["hedges"] == 1
    assert g["breaker_open"] == 1
    assert g["completed"] == 2

    # the trace log carries the same single-trace timeline; the
    # snapshot is taken at DELIVERY, so the losing hedge copy's
    # post-delivery cancellation hops may trail it — the logged hops
    # are a prefix of the live list
    entries = [e for e in get_trace_log().recent()
               if e["trace_id"] == vfid]
    assert len(entries) == 1
    logged = [h["kind"] for h in entries[0]["hops"]]
    assert logged == kinds[:len(logged)]
    assert "deliver" in logged


@pytest.mark.slow
@pytest.mark.fault
def test_failover_timeline_reconstructed_in_tracer():
    """With the chrome tracer on, Tracer.complete rebuilds the
    cross-replica timeline on ONE track: a fleet/request parent span,
    fleet/attempt child spans on ≥2 distinct replicas, and req/hop
    markers — all tid = the trace id."""
    tracer = get_tracer()
    tracer.clear()
    tracer.enabled = True
    try:
        fleet = ServingFleet(_factory(), num_replicas=2,
                             max_restarts=0, retry_backoff_s=0.001)
        fid = fleet.submit(_prompt(30, seed=4), 4)
        assert _drive_until(
            fleet, lambda: "admit" in _kinds(fleet.request(fid)))
        (rid0,) = {h.get("replica")
                   for h in fleet.request(fid).hops
                   if h["kind"] == "admit"}
        with FaultInjector() as fi:
            fi.kill_replica(rid0, times=10_000, after_steps=0)
            done = fleet.run()
        assert done[-1].error is None
    finally:
        tracer.enabled = False
    evs = list(tracer.events)
    tracer.clear()
    parents = [e for e in evs if e.name == "fleet/request"]
    assert len(parents) == 1
    assert parents[0].tid == fid
    assert parents[0].args["reason"] in ("eos", "length")
    attempts = [e for e in evs if e.name == "fleet/attempt"]
    reps = {e.args["replica"] for e in attempts}
    assert len(reps) >= 2, reps         # the timeline crossed replicas
    assert all(e.tid == fid for e in attempts)
    hops = [e for e in evs if e.name == "req/hop"]
    assert hops and all(e.tid == fid for e in hops)
    assert any(e.args["kind"] == "salvage" for e in hops)


def test_trace_log_slowest_ordering():
    log = get_trace_log()
    log.clear()
    for i, ms in enumerate([5.0, 50.0, 20.0]):
        log.record({"trace_id": i, "latency_ms": ms})
    slow = log.slowest(2)
    assert [e["trace_id"] for e in slow] == [1, 2]
    assert len(log.recent()) == 3
    log.clear()


def test_hop_list_is_bounded():
    """A preemption storm cannot grow a request's trace without
    limit: past the bound the list's last slot becomes a truncation
    marker counting the overflow — IN the shared list, so a hedge
    sibling's drops stay visible in the winner's summary."""
    from paddle_tpu.inference.serving import (_MAX_HOPS, ServedRequest,
                                              record_hop,
                                              request_trace_summary)
    req = ServedRequest(0, np.zeros((4,), np.int32), 4)
    for _ in range(_MAX_HOPS + 10):
        record_hop(req, "preempt")
    assert len(req.hops) == _MAX_HOPS
    # 74 calls, 63 real hops kept + the marker: 11 hops lost (the
    # displaced 64th + the 10 overflow calls)
    assert req.hops[-1] == {"kind": "truncated",
                            "t": req.hops[-1]["t"], "dropped": 11}
    # a sibling attempt sharing the list reports the same drops
    sibling = ServedRequest(0, np.zeros((4,), np.int32), 4)
    sibling.hops = req.hops
    assert request_trace_summary(sibling)["hops_dropped"] == 11
