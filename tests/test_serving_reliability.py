"""ISSUE 10: resilient serving under overload — KV-pool preemption &
recompute, request deadlines and cancellation, SLO-aware admission
control, page-accounting audit, and supervised engine recovery.

Contracts pinned here:

- a preempted request's final token stream is IDENTICAL to an
  uncontended run (recompute-style re-prefill rides the chunked-
  prefill parity contract, docs/serving.md);
- cancel/deadline completions free their pages mid-prefill or
  mid-decode and attach the right typed error while survivors keep
  exact token parity with their references;
- the admission controller sheds with ``Overloaded`` + retry-after
  instead of growing a doomed queue;
- the supervisor restarts a dead engine within its budget and replays
  in-flight requests without re-serving delivered prefixes;
- page accounting balances after arbitrary churn
  (``PADDLE_TPU_SERVING_AUDIT`` is on suite-wide via conftest).
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (AdmissionController,
                                  ContinuousBatchingEngine,
                                  DeadlineExceeded, EngineSupervisor,
                                  Overloaded, RequestCancelled,
                                  RequestQuarantined)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

_MODEL = None


def _model():
    """One 1-layer tiny model for the whole module: every engine below
    shares geometry, so XLA's persistent cache dedupes the compiles."""
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


def _build(**kw):
    m, _ = _model()
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 48)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("greedy", True)
    return ContinuousBatchingEngine(m, **kw)


def _ref(prompt, n):
    """Uncontended single-stream reference through the same engine
    geometry (the recompute-parity oracle)."""
    eng = _build(num_slots=1)
    eng.add_request(prompt, n)
    (req,) = eng.run()
    return req.tokens


def _prompts(seed, shapes):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in shapes]


def _assert_balanced(eng):
    # free + prefix-cache-resident = every allocatable page (ISSUE 12)
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1, (
        len(eng._free_pages), eng.prefix_cache_pages, eng.num_pages)
    assert not eng._deferred_free
    assert all(not p for p in eng.slot_pages)
    assert all(not s for s in eng.slot_shared)


# ---------------------------------------------------------------------------
# preemption & recompute
# ---------------------------------------------------------------------------


def test_priority_preemption_recompute_parity():
    """A strictly-higher-priority arrival evicts a running lower-
    priority sequence when the pool cannot serve both; the victim is
    requeued and its FINAL stream must equal the uncontended reference
    (recompute parity), with zero leaked pages and no stall."""
    pA, pB, pH = _prompts(7, (6, 9, 7))
    refA, refB, refH = _ref(pA, 30), _ref(pB, 28), _ref(pH, 20)
    eng = _build()               # 13 pages: 5 + 5 leaves 2 free
    a = eng.add_request(pA, 30)
    b = eng.add_request(pB, 28)
    for _ in range(3):
        eng.step()               # both slots admitted and decoding
    h = eng.add_request(pH, 20, priority=5)   # needs 4 pages > 2 free
    done = eng.run()
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted([a, b, h])
    assert all(r.error is None for r in done)
    assert by[h].tokens == refH
    assert by[a].tokens == refA, (by[a].tokens, refA)
    assert by[b].tokens == refB, (by[b].tokens, refB)
    assert by[a].preemptions + by[b].preemptions >= 1
    g = eng.gauges()
    assert g["preempt_evictions"] >= 1
    assert g["preempt_recompute_tokens"] >= 1
    _assert_balanced(eng)


def test_equal_priority_overload_queues_without_preemption():
    """Pure overload (equal priorities, queue deeper than the pool)
    never preempts and never stalls: requests just wait their turn and
    every stream matches its reference."""
    shapes = [5, 9, 7, 11, 4, 8]
    prompts = _prompts(11, shapes)
    news = [6, 4, 7, 5, 8, 3]
    refs = [_ref(p, n) for p, n in zip(prompts, news)]
    eng = _build()
    ids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
    done = eng.run()
    by = {r.request_id: r for r in done}
    assert [by[i].tokens for i in ids] == refs
    assert eng.gauges()["preempt_evictions"] == 0
    _assert_balanced(eng)


# ---------------------------------------------------------------------------
# deadlines & cancellation
# ---------------------------------------------------------------------------


def test_cancel_mid_decode_unified():
    """cancel() on a decoding request frees its pages at the next
    scheduler turn and completes it with RequestCancelled, keeping the
    tokens already emitted; the surviving stream keeps exact parity."""
    pA, pB = _prompts(13, (6, 9))
    refB = _ref(pB, 5)
    eng = _build()
    c1 = eng.add_request(pA, 30)
    c2 = eng.add_request(pB, 5)
    while not eng.request(c1).tokens:
        eng.step()
    assert eng.cancel(c1)
    assert not eng.cancel(999)           # unknown id
    done = eng.run()
    all_done = {r.request_id: r for r in eng.completed}
    r1 = all_done[c1]
    assert isinstance(r1.error, RequestCancelled)
    assert r1.finish_reason == "cancelled"
    assert r1.tokens and len(r1.tokens) < 30   # partial stream kept
    assert all_done[c2].tokens == refB
    assert any(r.request_id == c2 for r in done + list(eng.completed))
    assert eng.gauges()["requests_cancelled"] == 1
    _assert_balanced(eng)


def test_cancel_mid_prefill():
    """Cancelling while the prompt is still streaming through prefill
    chunks reclaims the pages before a single token exists."""
    (pLong,) = _prompts(17, (30,))
    eng = _build(max_len=64, prefill_chunk=8)
    rid = eng.add_request(pLong, 8)
    eng.step()                            # first prefill chunk only
    req = eng.request(rid)
    assert not req.tokens
    assert eng._prefilling.any() or req.finished is False
    eng.cancel(rid)
    eng.run()
    assert req.finished
    assert isinstance(req.error, RequestCancelled)
    assert req.tokens == []
    _assert_balanced(eng)


def test_ttft_deadline_expires_while_queued():
    """A queued request whose TTFT deadline lapses before admission is
    shed with DeadlineExceeded(kind='ttft') — it never occupies a
    slot, and the request ahead of it is untouched."""
    pA, pB = _prompts(23, (6, 9))
    eng = _build(num_slots=1)
    d1 = eng.add_request(pA, 10)
    d2 = eng.add_request(pB, 5, ttft_deadline_s=1e-4)
    time.sleep(0.005)
    done = eng.run()
    by = {r.request_id: r for r in done}
    err = by[d2].error
    assert isinstance(err, DeadlineExceeded) and err.kind == "ttft"
    assert by[d2].tokens == [] and by[d2].finish_reason == "deadline"
    assert by[d1].error is None and len(by[d1].tokens) == 10
    assert eng.gauges()["deadline_expired"] == 1
    _assert_balanced(eng)


def test_total_deadline_expires_mid_stream():
    """A total deadline expiring mid-decode evicts the slot at the
    next harvest: pages come back, the partial stream is kept, and the
    error is DeadlineExceeded(kind='total')."""
    (pA,) = _prompts(29, (6,))
    eng = _build()
    rid = eng.add_request(pA, 30, deadline_s=3600.0)
    while len(eng.request(rid).tokens) < 2:
        eng.step()
    req = eng.request(rid)
    req.deadline_s = 1e-9                 # already lapsed
    eng.run()
    assert req.finished
    assert isinstance(req.error, DeadlineExceeded)
    assert req.error.kind == "total"
    assert len(req.tokens) >= 2
    _assert_balanced(eng)


# ---------------------------------------------------------------------------
# admission control & load shedding
# ---------------------------------------------------------------------------


def test_admission_queue_bound_sheds_with_retry_after():
    pA, pB, pH = _prompts(31, (5, 6, 7))
    eng = _build()
    adm = AdmissionController(eng, max_queue=2)
    adm.submit(pA, 4)
    adm.submit(pB, 4)
    with pytest.raises(Overloaded) as ei:
        adm.submit(pH, 4)
    assert ei.value.retry_after_s > 0
    assert adm.shed == 1 and adm.accepted == 2
    assert eng.gauges()["shed_rejections"] == 1
    assert eng.metrics.gauge(
        "serving/shed_retry_after_s").value > 0
    done = eng.run()                      # accepted requests unharmed
    assert len(done) == 2
    _assert_balanced(eng)


def test_admission_slo_prediction_sheds_doomed_request():
    """With latency history in the reservoirs and queued work ahead, a
    request whose TTFT deadline is below the prediction is shed at the
    door instead of timing out in a slot."""
    pA, pB = _prompts(37, (6, 8))
    eng = _build()
    adm = AdmissionController(eng, max_queue=32)
    adm.submit(pA, 6)
    eng.run()                             # seeds ttft/itl reservoirs
    assert adm.predicted_ttft_s() is not None
    adm.submit(pB, 8)                     # queued work ahead
    with pytest.raises(Overloaded):
        adm.submit(pA, 4, ttft_deadline_s=1e-7)
    # a realistic deadline still admits
    rid = adm.submit(pA, 4, ttft_deadline_s=3600.0)
    done = eng.run()
    assert {r.request_id for r in done} >= {rid}
    _assert_balanced(eng)


# ---------------------------------------------------------------------------
# containment & supervision
# ---------------------------------------------------------------------------


def test_containment_quarantines_poison_and_recomputes_innocents():
    """A poisoned harvest (FaultInjector poison-request plan) is
    contained: the poison request is quarantined after max_strikes
    implications while the co-scheduled innocent replays to an exact
    reference stream — the engine never dies."""
    from paddle_tpu.testing import FaultInjector
    pP, pI = _prompts(41, (6, 9))
    refI = _ref(pI, 6)
    eng = _build(max_strikes=2)
    rp = eng.add_request(pP, 8)
    ri = eng.add_request(pI, 6)
    with FaultInjector() as fi:
        fi.poison_request(rp, times=2)
        done = eng.run()
    by = {r.request_id: r for r in eng.completed}
    assert isinstance(by[rp].error, RequestQuarantined)
    assert by[rp].finish_reason == "quarantined"
    assert by[ri].error is None
    assert by[ri].tokens == refI, (by[ri].tokens, refI)
    assert eng.gauges()["containments"] >= 1
    assert eng.gauges()["quarantined"] == 1
    assert len(done) == 2
    _assert_balanced(eng)


def test_supervisor_restarts_dead_engine_and_replays():
    """A crash that escapes containment (budget 0) tears the engine
    down; the supervisor rebuilds it, replays the in-flight request
    from prompt + emitted tokens, and the final stream matches the
    uncontended reference. Restart budget is bounded."""
    (pA,) = _prompts(43, (6,))
    refA = _ref(pA, 8)
    calls = {"n": 0}

    def factory():
        eng = _build(max_containments=0)
        orig = eng._harvest_step

        def dying(rec):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("injected engine death")
            return orig(rec)

        eng._harvest_step = dying
        return eng

    sup = EngineSupervisor(factory, max_restarts=3)
    rid = sup.add_request(pA, 8)
    done = sup.run()
    assert sup.restarts >= 1
    by = {r.request_id: r for r in done}
    assert by[rid].tokens == refA
    _assert_balanced(sup.engine)


def test_supervisor_restart_budget_exhausts():
    """An engine that dies on every step propagates the original
    failure once max_restarts is spent — bounded, never infinite."""
    (pA,) = _prompts(47, (5,))

    def factory():
        eng = _build(max_containments=0)

        def dying(rec):
            raise RuntimeError("permanently broken")

        eng._harvest_step = dying
        return eng

    sup = EngineSupervisor(factory, max_restarts=1)
    sup.add_request(pA, 4)
    with pytest.raises(RuntimeError, match="permanently broken"):
        sup.run()
    # exactly ONE rebuild happened; the budget-exceeded terminal
    # attempt does not count as a restart cycle
    assert sup.restarts == 1


# ---------------------------------------------------------------------------
# page accounting
# ---------------------------------------------------------------------------


@pytest.mark.fault
def test_page_leak_fails_audit_loudly():
    """The PADDLE_TPU_SERVING_AUDIT invariant catches an injected
    reclamation bug (leak_pages plan) as an AssertionError — which the
    containment boundary deliberately refuses to swallow."""
    from paddle_tpu.testing import FaultInjector
    (pA,) = _prompts(53, (6,))
    eng = _build()
    eng.add_request(pA, 4)
    with FaultInjector() as fi:
        fi.leak_pages(n=1)
        with pytest.raises(AssertionError, match="page accounting"):
            eng.run()
    # ...and the supervisor must not launder the audit failure into a
    # restart: it propagates through the whole supervised stack
    m, _ = _model()
    sup = EngineSupervisor(
        lambda: ContinuousBatchingEngine(
            m, num_slots=2, page_size=8, max_len=48, decode_chunk=4,
            prefill_chunk=16, greedy=True), max_restarts=3)
    sup.add_request(pA, 4)
    with FaultInjector() as fi:
        fi.leak_pages(n=1)
        with pytest.raises(AssertionError, match="page accounting"):
            sup.run()
    assert sup.restarts == 0


def test_churn_cancel_preempt_zero_leak_fast():
    """Fast churn: priorities, preemptions and mid-flight cancels over
    more requests than the pool can hold at once — zero pages leaked,
    every request completes or typed-fails."""
    _churn(n_requests=24, seed=59)


@pytest.mark.slow
def test_churn_zero_leak_1k_requests():
    """ISSUE-10 satellite: cancellation and preemption leak zero pages
    over 1k churned requests."""
    _churn(n_requests=1000, seed=61)


def _churn(n_requests, seed):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    eng = _build()
    ids = []
    for i in range(n_requests):
        plen = int(rng.randint(3, 12))
        n_new = int(rng.randint(1, 8))
        prio = int(rng.randint(0, 3))
        rid = eng.add_request(
            rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32),
            n_new, priority=prio)
        ids.append(rid)
        if rng.rand() < 0.2:
            eng.cancel(rid)
        if rng.rand() < 0.3:
            eng.step()                    # interleave admission/decode
            if rng.rand() < 0.3 and ids:
                eng.cancel(int(rng.choice(ids)))   # mid-flight cancel
    eng.run()
    by = {r.request_id: r for r in eng.completed}
    assert sorted(by) == sorted(ids)
    for r in by.values():
        assert r.finished
        assert (r.error is None) == (r.finish_reason in
                                     ("eos", "length"))
    _assert_balanced(eng)
