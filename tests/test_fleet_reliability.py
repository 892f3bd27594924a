"""ServingFleet reliability contracts (ISSUE 11).

The multi-replica router's pinned semantics, one scenario per test:

- **failover token-identity** — killing a replica mid-stream loses
  zero requests and every affected greedy stream is token-identical
  to an uncontended single-engine run (the supervisor salvage /
  recompute-replay contract, end to end through the fleet);
- **hedged dispatch** — a straggler replica's request is duplicated
  to a sibling after the hedge delay; the first completion wins and
  the loser is cancelled, exactly one completion per fleet id;
- **circuit breaking** — a replica that burns its supervisor restart
  budget is ejected and its queue requeued to siblings;
- **no-progress ejection** — a wedged replica (heartbeats, no
  progress) is ejected by the health check, not the liveness check,
  without tripping the engine's true-deadlock stall diagnostic;
- **graceful draining** — scale-down stops admission, lets in-flight
  finish under the deadline, and deadline-evicts stragglers for
  recompute on siblings;
- **fleet-wide shed** — all breakers open raises ``Overloaded``; a
  partial shed propagates the MAX computed retry-after across the
  replicas that shed (the ISSUE-11 ``retry_after_s`` fix), and the
  retry backoff honors such a value as its floor.

The 4-replica randomized kill/wedge/slow sweep lives in
``tests/test_fleet_chaos.py`` (the ``fleet_chaos`` gate).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine, Overloaded,
                                  ReplicaFailed, RequestCancelled,
                                  ServingFleet)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import FaultInjector

_MODEL = None
_REF_ENG = None
_REF_TOKENS = {}


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


def _reference(prompt, n_new):
    """Uncontended single-engine greedy tokens for one request (one
    shared reference engine: each request runs ALONE, and its compiled
    program is reused across every test in this module)."""
    global _REF_ENG
    key = (prompt.tobytes(), int(n_new))
    if key not in _REF_TOKENS:
        if _REF_ENG is None:
            _REF_ENG = _factory()()
        _REF_ENG.add_request(prompt, n_new)
        _REF_TOKENS[key] = _REF_ENG.run()[-1].tokens
    return _REF_TOKENS[key]


def _prompts(seed, n, lo=3, hi=10):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        (int(rng.randint(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


def _assert_identity(fleet, done, fids, specs):
    """Every fid delivered exactly once, error-free, token-identical
    to its uncontended single-engine stream."""
    assert len(done) == len(fids), "lost or duplicated completions"
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(fids)
    for fid, (prompt, n_new) in zip(fids, specs):
        r = by[fid]
        assert r.error is None, (fid, r.error)
        assert r.tokens == _reference(prompt, n_new), fid


# ---- failover --------------------------------------------------------------

@pytest.mark.fault
def test_failover_token_identity_supervisor_restart():
    """ACCEPTANCE PIN: a replica dying mid-stream loses zero requests
    and every affected greedy stream is token-identical to an
    uncontended single-engine run — the in-replica supervisor restart
    path (death absorbed below the fleet's breaker)."""
    prompts = _prompts(1, 6)
    specs = [(p, 5) for p in prompts]
    fleet = ServingFleet(_factory(), num_replicas=2, max_restarts=2,
                         retry_backoff_s=0.01)
    fids = [fleet.submit(p, n) for p, n in specs]
    with FaultInjector() as fi:
        fi.kill_replica(0, times=1, after_steps=2)
        done = fleet.run()
        assert fi.fires() == 1
    _assert_identity(fleet, done, fids, specs)
    g = fleet.gauges()
    assert fleet.replicas[0].supervisor.restarts == 1
    assert g["breaker_open"] == 0        # absorbed in-replica
    assert fleet.replicas[0].state == "ready"


@pytest.mark.fault
@pytest.mark.slow
def test_breaker_ejection_requeues_to_siblings():
    """A replica that keeps dying past its supervisor budget trips the
    circuit breaker: it is ejected, its queue + in-flight requeue to
    the sibling with bounded backoff-retries, streams stay
    token-identical."""
    prompts = _prompts(2, 4, lo=6, hi=7)
    specs = [(p, 5) for p in prompts]
    fleet = ServingFleet(_factory(), num_replicas=2, max_restarts=1,
                         retry_backoff_s=0.01)
    fids = [fleet.submit(p, n) for p, n in specs]
    with FaultInjector() as fi:
        fi.kill_replica(0, times=10_000)
        done = fleet.run()
    _assert_identity(fleet, done, fids, specs)
    g = fleet.gauges()
    assert fleet.replicas[0].state == "ejected"
    assert g["breaker_open"] == 1
    assert g["requeued"] >= 1 and g["retries"] >= 1
    assert g["failover_ms_p99"] > 0.0


# ---- health model ----------------------------------------------------------

@pytest.mark.fault
def test_wedged_replica_ejected_by_no_progress():
    """ACCEPTANCE PIN: a wedged replica — heartbeats arriving (its
    step() returns promptly), zero progress — is ejected by the
    NO-PROGRESS health check (not the liveness check, not the
    breaker), and its queue drains to the sibling without tripping the
    engine's true-deadlock stall RuntimeError (run() returns
    normally)."""
    prompts = _prompts(3, 4, lo=6, hi=7)
    specs = [(p, 5) for p in prompts]
    # hedging OFF (hedge_delay_s huge): in a warm process the p99-
    # derived hedge fires first and RESCUES the wedged replica's
    # requests before the no-progress clock reaches 5 — fine behavior,
    # but this test pins the EJECTION path specifically
    fleet = ServingFleet(_factory(), num_replicas=2,
                         no_progress_turns=5, retry_backoff_s=0.01,
                         hedge_delay_s=1e9)
    fids = [fleet.submit(p, n) for p, n in specs]
    with FaultInjector() as fi:
        fi.wedge_replica(0, times=10_000)
        done = fleet.run()            # no RuntimeError
        assert fi.fires() >= 5
    _assert_identity(fleet, done, fids, specs)
    g = fleet.gauges()
    assert g["wedge_ejections"] == 1
    assert g["breaker_open"] == 0     # the wedge is NOT a crash
    assert fleet.replicas[0].state == "ejected"


# ---- hedging ---------------------------------------------------------------

@pytest.mark.fault
@pytest.mark.slow
def test_hedge_winner_cancels_loser():
    """A straggler replica's request is duplicated to the sibling
    after the hedge delay; the duplicate wins, the loser is cancelled
    via the PR-10 cancel path, and exactly ONE completion is delivered
    — token-identical to the uncontended stream."""
    prompts = _prompts(4, 1, lo=6, hi=7)
    spec = (prompts[0], 5)
    fleet = ServingFleet(_factory(), num_replicas=2,
                         hedge_delay_s=0.03, retry_backoff_s=0.01)
    with FaultInjector() as fi:
        # replica 0 straggles: every step burns 50 ms and only every
        # 6th advances — both replicas idle at submit, so the router
        # deterministically picks replica 0 first
        fi.slow_replica(0, delay_s=0.05, stride=6)
        fid = fleet.submit(*spec)
        done = fleet.run()
    _assert_identity(fleet, done, [fid], [spec])
    g = fleet.gauges()
    assert g["hedges"] == 1
    assert g["hedge_wins"] == 1       # the duplicate beat the straggler
    assert g["hedge_cancels"] >= 1    # and the loser was cancelled
    assert g["completed"] == 1        # never delivered twice


# ---- draining / elasticity -------------------------------------------------

@pytest.mark.slow
def test_drain_clean_under_generous_deadline():
    """scale_down with headroom: admission stops, in-flight requests
    FINISH on the draining replica (zero evictions), then it
    retires."""
    prompts = _prompts(5, 4, lo=6, hi=7)
    specs = [(p, 5) for p in prompts]
    fleet = ServingFleet(_factory(), num_replicas=2)
    fids = [fleet.submit(p, n) for p, n in specs]
    rid = fleet.scale_down(0, deadline_s=60.0)
    done = fleet.run()
    _assert_identity(fleet, done, fids, specs)
    g = fleet.gauges()
    assert fleet.replicas[rid].state == "retired"
    assert g["drains"] == 1
    assert g["requeued"] == 0         # nothing was evicted


@pytest.mark.slow
def test_drain_deadline_evicts_stragglers_to_sibling():
    """scale_down with an already-expired deadline: the stragglers are
    evicted through the engine's handoff() hook and recomputed on the
    sibling — still token-identical, still zero loss."""
    prompts = _prompts(6, 4, lo=6, hi=7)
    specs = [(p, 5) for p in prompts]
    fleet = ServingFleet(_factory(), num_replicas=2)
    fids = [fleet.submit(p, n) for p, n in specs]
    rid = fleet.scale_down(0, deadline_s=0.0)
    done = fleet.run()
    _assert_identity(fleet, done, fids, specs)
    g = fleet.gauges()
    assert fleet.replicas[rid].state == "retired"
    assert g["drains"] == 1
    assert g["requeued"] >= 1         # stragglers moved over


@pytest.mark.slow
def test_scale_up_warms_before_taking_weight():
    """A scaled-up replica is warmed (programs compiled on a
    sacrificial request) and its gauges reset before it takes router
    weight — warmup latencies never pollute the routing signal."""
    fleet = ServingFleet(_factory(), num_replicas=1)
    rid = fleet.scale_up()
    rep = fleet.replicas[rid]
    assert rep.state == "ready"
    assert rep.engine._compiled         # warmed: programs exist
    assert rep.engine._stats["tokens_emitted"] == 0   # gauges reset
    assert fleet.gauges()["scale_ups"] == 1
    prompts = _prompts(7, 2, lo=6, hi=7)
    specs = [(p, 4) for p in prompts]
    fids = [fleet.submit(p, n) for p, n in specs]
    done = fleet.run()
    _assert_identity(fleet, done, fids, specs)


# ---- shedding / retry-after ------------------------------------------------

@pytest.mark.fault
@pytest.mark.slow
def test_all_breakers_open_sheds_fleet_wide():
    """Every replica dead: outstanding requests complete with the
    typed ReplicaFailed (never silent loss), and a new submission
    raises Overloaded with the configured fleet-wide retry-after."""
    prompts = _prompts(8, 2, lo=6, hi=7)
    fleet = ServingFleet(_factory(), num_replicas=2, max_restarts=0,
                         retry_backoff_s=0.01, max_retries=2,
                         all_open_retry_after_s=0.7)
    fids = [fleet.submit(p, 5) for p in prompts]
    with FaultInjector() as fi:
        fi.kill_replica(0, times=10_000)
        fi.kill_replica(1, times=10_000)
        done = fleet.run()
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(fids)
    for fid in fids:
        assert isinstance(by[fid].error, ReplicaFailed), by[fid].error
        assert by[fid].finish_reason == "failed"
    assert fleet.gauges()["breaker_open"] == 2
    with pytest.raises(Overloaded) as exc:
        fleet.submit(prompts[0], 5)
    assert exc.value.retry_after_s == pytest.approx(0.7)


def test_overloaded_retry_after_is_max_across_replicas():
    """THE ISSUE-11 propagation fix: when every ready replica sheds,
    the fleet's Overloaded carries the MAX of the admission
    controllers' computed retry-afters — not a constant."""
    prompts = _prompts(9, 3, lo=6, hi=7)
    fleet = ServingFleet(_factory(), num_replicas=2, max_queue=1)
    fleet.replicas[0].admission.min_retry_after_s = 0.3
    fleet.replicas[1].admission.min_retry_after_s = 0.7
    fleet.submit(prompts[0], 4)       # fills replica 0's queue bound
    fleet.submit(prompts[1], 4)       # fills replica 1's
    with pytest.raises(Overloaded) as exc:
        fleet.submit(prompts[2], 4)
    assert exc.value.retry_after_s == pytest.approx(0.7)
    assert fleet.gauges()["shed_rejections"] == 1
    assert fleet.gauges()["submitted"] == 2     # sheds never counted


def test_retry_backoff_floor_growth_and_jitter():
    """The fleet's retry schedule: exponential in the attempt number,
    jitter-bounded, capped — and FLOORED by a computed retry-after
    (the Overloaded.retry_after_s backoff-floor contract)."""
    fleet = ServingFleet(_factory(), num_replicas=1,
                         retry_backoff_s=0.05, retry_backoff_cap_s=2.0,
                         retry_jitter=0.25, seed=7)
    for attempt in (1, 2, 3, 4):
        base = 0.05 * 2 ** (attempt - 1)
        for _ in range(20):
            b = fleet._backoff_s(attempt)
            assert base * 0.75 - 1e-9 <= b <= min(2.0, base * 1.25) \
                + 1e-9
    # a computed retry-after outranks the blind schedule entirely
    assert fleet._backoff_s(1, floor_s=5.0) == 5.0
    # the cap bounds the schedule (2^11 * base >> cap), not the floor
    assert fleet._backoff_s(12) == 2.0


@pytest.mark.fault
def test_cancel_while_carried_is_not_resurrected():
    """A request cancelled while waiting out its failover backoff
    (its replica died, it is CARRIED between assignments) completes
    with RequestCancelled — it must never be re-admitted on a sibling
    and delivered as a success (the reap runs before the retry
    firing)."""
    prompts = _prompts(11, 1, lo=6, hi=7)
    fleet = ServingFleet(_factory(), num_replicas=2, max_restarts=0,
                         retry_backoff_s=30.0)   # carry parks for 30s
    with FaultInjector() as fi:
        fi.kill_replica(0, times=10_000)
        fid = fleet.submit(prompts[0], 5)        # routed to replica 0
        out = fleet.step()                       # breaker -> carried
        assert not out and fleet.request(fid) is not None
        assert fleet.cancel(fid)
        done = fleet.step()                      # reap, not reassign
    assert len(done) == 1
    assert isinstance(done[0].error, RequestCancelled), done[0].error
    assert fleet.gauges()["completed"] == 1


@pytest.mark.slow
def test_operator_eject_is_not_a_breaker_trip():
    """fleet.eject() (an operator action, not a failure) fails the
    replica's work over immediately WITHOUT counting a breaker trip or
    burning the salvaged requests' bounded retry budget."""
    prompts = _prompts(12, 3, lo=6, hi=7)
    specs = [(p, 5) for p in prompts]
    fleet = ServingFleet(_factory(), num_replicas=2, max_retries=0)
    fids = [fleet.submit(p, n) for p, n in specs]
    fleet.eject(0)
    done = fleet.run()
    _assert_identity(fleet, done, fids, specs)   # max_retries=0 yet
    g = fleet.gauges()                           # nothing failed
    assert fleet.replicas[0].state == "ejected"
    assert g["breaker_open"] == 0 and g["retries"] == 0
    assert g["requeued"] >= 1


def test_fleet_cancel_and_request_surface():
    """fleet.cancel(fid) completes the request with the typed
    RequestCancelled at the next turn; fleet.request(fid) tracks the
    live handle and then the completion."""
    prompts = _prompts(10, 1, lo=6, hi=7)
    fleet = ServingFleet(_factory(), num_replicas=1)
    fid = fleet.submit(prompts[0], 5)
    assert fleet.request(fid) is not None
    assert fleet.cancel(fid)
    done = fleet.run()
    assert len(done) == 1
    assert isinstance(done[0].error, RequestCancelled)
    assert fleet.request(fid) is done[0]
    assert not fleet.cancel(fid)      # already finished
