"""Prefix-cache chaos smoke (ISSUE 12) — the ``prefix_cache`` gate in
``tools/run_gates.py`` (mirroring ``serving_chaos``).

Fast fault-marked smoke: a shared-prefix STORM (most requests carry
the same multi-page prefix, so the pool is full of refcounted shared
pages) with mid-run preemptions (high-priority latecomers),
mid-run cancellations, a poisoned request and an injected mid-step
engine death, driven through the AdmissionController +
EngineSupervisor stack with ``PADDLE_TPU_SERVING_AUDIT`` on
(suite-wide). The contract asserted end to end:

- every offered request completes with tokens or fails with a TYPED
  error — a shared page's owner dying never takes its sharers along;
- zero leaked or double-freed pages: free + prefix-cache-resident ==
  every allocatable page, refcounts exact (the extended audit ran
  after every drain/evict inside the run);
- delivered greedy streams are token-identical to a cache-off
  reference engine — sharing plus chaos replay stays transparent;
- the cache actually worked under fire (hits > 0).

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
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("greedy", True)
    return lambda: ContinuousBatchingEngine(m, **kw)


def _specs(cfg, rng, n):
    """The storm: ~70% of requests share a 2-page prefix."""
    shared = rng.randint(0, cfg.vocab_size, (17,)).astype(np.int32)
    out = []
    for i in range(n):
        if rng.rand() < 0.7:
            tail = rng.randint(
                0, cfg.vocab_size,
                (int(rng.randint(0, 5)),)).astype(np.int32)
            prompt = np.concatenate([shared, tail])
        else:
            prompt = rng.randint(
                0, cfg.vocab_size,
                (int(rng.randint(3, 14)),)).astype(np.int32)
        out.append((prompt, int(rng.randint(2, 7)),
                    int(rng.randint(0, 3))))
    return out


def _reference(specs):
    """Cache-off greedy oracle, one request at a time."""
    eng = _factory(prefix_cache=False)()
    refs = []
    for prompt, n_new, _ in specs:
        rid = eng.add_request(prompt, n_new)
        by = {r.request_id: r for r in eng.run()}
        refs.append(by[rid].tokens)
    return refs


def _assert_storm_recovered(sup, offered, done, refs):
    by = {r.request_id: r for r in done}
    for i, rid in enumerate(offered):
        assert rid in by, f"request {rid} vanished"
        r = by[rid]
        assert r.finished
        if r.error is not None:
            # typed failure keeps its delivered tokens — always an
            # exact PREFIX of the greedy stream (replay identity)
            assert isinstance(r.error, ServingError), r.error
            assert r.tokens == refs[i][:len(r.tokens)], (
                rid, r.tokens, refs[i])
        else:
            assert r.tokens == refs[i], (rid, r.tokens, refs[i])
    eng = sup.engine
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1
    assert not eng._deferred_free
    assert all(not p for p in eng.slot_pages)
    assert all(not s for s in eng.slot_shared)
    eng._audit_pages("storm_end")


@pytest.mark.fault
def test_prefix_storm_preempt_cancel_poison_kill():
    """THE gate scenario: shared-prefix storm + mid-run cancellations
    + priority preemptions + a poisoned request + one injected
    mid-step engine death that ESCAPES containment (supervisor
    restart drops the cache and replays) — complete-or-typed-fail,
    token-identity for clean streams, audit green, zero leaks."""
    _, cfg = _model()
    rng = np.random.RandomState(12)
    specs = _specs(cfg, rng, 18)
    refs = _reference(specs)
    sup = EngineSupervisor(_factory(), max_restarts=3)
    adm = AdmissionController(sup, max_queue=64)
    offered, shed = [], 0
    for prompt, n_new, pri in specs:
        try:
            offered.append(adm.submit(prompt, n_new, priority=pri,
                                      deadline_s=600.0))
        except Overloaded:
            shed += 1
    assert shed == 0                         # the bound was generous
    poison = offered[5]
    cancels = {offered[9], offered[14]}
    with FaultInjector() as fi:
        fi.poison_request(poison, times=2)
        fi.fail_call("paddle_tpu.inference.serving."
                     "ContinuousBatchingEngine._dispatch_step",
                     action="raise", after_calls=7, times=1)
        sup.engine.max_containments = 0      # escapes -> supervisor
        done, turn = [], 0
        while sup.has_work() or sup.engine.queue:
            done.extend(sup.step())
            turn += 1
            if turn == 3 or turn == 6:       # mid-run cancellations
                for rid in cancels:
                    sup.cancel(rid)
            assert turn < 5000, "storm made no progress"
        assert fi.fires() >= 1
    _assert_storm_recovered(sup, offered, done, refs)
    # the injected faults actually exercised the recovery machinery:
    # a supervised restart (cache dropped + replay) or a containment
    g = sup.gauges()
    assert sup.restarts >= 1 or g["containments"] >= 1
    assert g["prefix_cache_hits"] >= 1       # the cache worked under fire
    ok = [r for r in done if r.error is None]
    assert len(ok) >= len(offered) - 1 - len(cancels)


@pytest.mark.fault
def test_prefix_storm_overload_no_stall():
    """Pure overload on a SMALL pool full of shared pages: the
    refcount-aware LRU keeps admission fed (evicting only
    unreferenced cache pages), the stall RuntimeError is unreachable,
    and every stream matches its cache-off reference."""
    _, cfg = _model()
    rng = np.random.RandomState(21)
    specs = _specs(cfg, rng, 14)
    refs = _reference(specs)
    # tight pool: ~2 concurrent sequences' worth of pages
    eng = _factory(num_pages=13, max_len=48)()
    offered = [eng.add_request(p, n, priority=pri, deadline_s=600.0)
               for p, n, pri in specs]
    done = eng.run()                         # no RuntimeError
    by = {r.request_id: r for r in done}
    for i, rid in enumerate(offered):
        assert by[rid].error is None
        assert by[rid].tokens == refs[i]
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1
    eng._audit_pages("overload_end")


@pytest.mark.fault
@pytest.mark.slow
def test_randomized_prefix_chaos_sweep():
    """Slow breadth: randomized shared-prefix storms x randomized
    fault choice (poison / mid-step raise / cancel wave / none) — the
    fast smoke's contract, every seed."""
    _, cfg = _model()
    for seed in range(6):
        rng = np.random.RandomState(200 + seed)
        specs = _specs(cfg, rng, int(rng.randint(8, 16)))
        refs = _reference(specs)
        sup = EngineSupervisor(_factory(), max_restarts=3)
        adm = AdmissionController(sup, max_queue=64)
        offered = [adm.submit(p, n, priority=pri, deadline_s=600.0)
                   for p, n, pri in specs]
        fault = rng.choice(["poison", "raise", "cancel", "none"])
        with FaultInjector() as fi:
            if fault == "poison":
                fi.poison_request(int(rng.choice(offered)), times=2)
            elif fault == "raise":
                fi.fail_call(
                    "paddle_tpu.inference.serving."
                    "ContinuousBatchingEngine._dispatch_step",
                    action="raise",
                    after_calls=int(rng.randint(0, 8)), times=1)
            done, turn = [], 0
            while sup.has_work() or sup.engine.queue:
                done.extend(sup.step())
                turn += 1
                if fault == "cancel" and turn == 4:
                    for rid in rng.choice(offered, 2):
                        sup.cancel(int(rid))
                assert turn < 5000, f"seed {seed} made no progress"
        _assert_storm_recovered(sup, offered, done, refs)
