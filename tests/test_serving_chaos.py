"""Serving chaos smoke (ISSUE 10) — the ``serving_chaos`` gate in
``tools/run_gates.py`` (mirroring ``elastic_chaos``).

Fast fault-marked smoke: overload past page capacity + a poisoned
request + a mid-step engine kill + a wedged slot, driven through the
AdmissionController + EngineSupervisor stack. The contract asserted
end to end:

- the engine NEVER dies (no stall ``RuntimeError``, no crash escapes
  the supervisor's budget);
- every offered request either completes with tokens or fails with a
  TYPED error (Overloaded at the door counts);
- zero leaked pages (``PADDLE_TPU_SERVING_AUDIT`` is on suite-wide,
  and the free list is checked explicitly).

The randomized breadth sweep stays in the slow tier.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (AdmissionController,
                                  ContinuousBatchingEngine,
                                  EngineSupervisor, Overloaded,
                                  ServingError)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import FaultInjector

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


def _assert_recovered(sup, offered, done):
    """Every offered request completed-or-typed-failed; pool intact."""
    by = {r.request_id: r for r in done}
    for rid in offered:
        assert rid in by, f"request {rid} vanished"
        r = by[rid]
        assert r.finished
        if r.error is not None:
            assert isinstance(r.error, ServingError), r.error
        else:
            assert r.finish_reason in ("eos", "length")
    eng = sup.engine
    # free + prefix-cache-resident = every allocatable page (ISSUE 12)
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1
    assert not eng._deferred_free
    assert all(not p for p in eng.slot_pages)
    assert all(not s for s in eng.slot_shared)


@pytest.mark.fault
def test_overload_poison_and_kill_smoke():
    """THE gate scenario: a workload oversubscribing the page pool
    ~4x with mixed priorities and deadlines, a poisoned request, and
    an injected mid-step engine death — the supervised stack finishes
    every request (tokens or typed error), zero pages leaked, zero
    engine crashes escaping."""
    _, cfg = _model()
    rng = np.random.RandomState(3)
    sup = EngineSupervisor(_factory(), max_restarts=3)
    adm = AdmissionController(sup, max_queue=64)
    offered, shed = [], 0
    # ~4x the pool: 12 pages serve ~2 concurrent; queue 10 requests
    for i in range(10):
        plen = int(rng.randint(4, 12))
        n_new = int(rng.randint(2, 8))
        try:
            offered.append(adm.submit(
                rng.randint(0, cfg.vocab_size,
                            (plen,)).astype(np.int32),
                n_new, priority=int(rng.randint(0, 3)),
                deadline_s=600.0))
        except Overloaded:
            shed += 1
    poison = offered[3]
    with FaultInjector() as fi:
        fi.poison_request(poison, times=2)
        # one mid-step death that ESCAPES containment -> supervisor
        fi.fail_call("paddle_tpu.inference.serving."
                     "ContinuousBatchingEngine._dispatch_step",
                     action="raise", after_calls=4, times=1)
        sup.engine.max_containments = 0   # escapes go to the supervisor
        done = sup.run()
        assert fi.fires() >= 1
    _assert_recovered(sup, offered, done)
    assert shed == 0                       # queue bound was generous
    by = {r.request_id: r for r in done}
    assert by[poison].error is not None    # the poison was isolated
    ok = [r for r in done if r.error is None]
    assert len(ok) >= len(offered) - 2     # innocents survived


@pytest.mark.fault
def test_wedged_slot_recovers_via_supervision():
    """A slot that stops draining (wedge-slot plan) cannot wedge the
    service: either the deadlock-break eviction recomputes it or the
    supervisor replays it on a fresh engine — the request completes
    with its full stream."""
    _, cfg = _model()
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    ref_eng = _factory()()
    ref_eng.add_request(prompt, 5)
    ref = ref_eng.run()[0].tokens
    sup = EngineSupervisor(_factory(), max_restarts=2)
    rid = sup.add_request(prompt, 5)
    with FaultInjector() as fi:
        fi.wedge_slot(0, times=10_000)    # wedged for the whole run
        done = sup.run()
        assert fi.fires() >= 1
    _assert_recovered(sup, [rid], done)
    by = {r.request_id: r for r in done}
    assert by[rid].tokens == ref
    assert sup.restarts >= 1


@pytest.mark.fault
def test_overload_survival_no_stall_4x():
    """Acceptance pin: 4x pool oversubscription with mixed priorities
    and deadlines runs to completion on a BARE engine — the stall
    RuntimeError is unreachable under pure overload."""
    _, cfg = _model()
    rng = np.random.RandomState(9)
    eng = _factory()()
    ids = []
    for i in range(12):                   # ~4x the 12-page pool
        plen = int(rng.randint(3, 10))
        ids.append(eng.add_request(
            rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32),
            int(rng.randint(2, 7)), priority=int(rng.randint(0, 4)),
            deadline_s=600.0))
    done = eng.run()                      # no RuntimeError
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(ids)
    assert all(r.error is None for r in done)
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1


@pytest.mark.fault
@pytest.mark.slow
def test_randomized_chaos_sweep():
    """Slow breadth: randomized workloads x randomized fault choice
    (poison / wedge / mid-step raise / none), all through the
    supervised stack — complete-or-typed-fail + zero leak, every
    seed."""
    _, cfg = _model()
    for seed in range(8):
        rng = np.random.RandomState(100 + seed)
        sup = EngineSupervisor(_factory(), max_restarts=3)
        adm = AdmissionController(sup, max_queue=32)
        offered = []
        for i in range(int(rng.randint(6, 12))):
            plen = int(rng.randint(3, 12))
            try:
                offered.append(adm.submit(
                    rng.randint(0, cfg.vocab_size,
                                (plen,)).astype(np.int32),
                    int(rng.randint(1, 8)),
                    priority=int(rng.randint(0, 3)),
                    ttft_deadline_s=600.0, deadline_s=600.0))
            except Overloaded:
                pass
        fault = rng.choice(["poison", "wedge", "raise", "none"])
        with FaultInjector() as fi:
            if fault == "poison" and offered:
                fi.poison_request(int(rng.choice(offered)), times=2)
            elif fault == "wedge":
                fi.wedge_slot(int(rng.randint(0, 2)), times=10_000)
            elif fault == "raise":
                fi.fail_call(
                    "paddle_tpu.inference.serving."
                    "ContinuousBatchingEngine._dispatch_step",
                    action="raise",
                    after_calls=int(rng.randint(0, 6)), times=1)
            done = sup.run()
        _assert_recovered(sup, offered, done)
