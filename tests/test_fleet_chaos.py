"""Fleet chaos smoke (ISSUE 11) — the ``fleet_chaos`` gate in
``tools/run_gates.py`` (mirroring ``elastic_chaos`` /
``serving_chaos``).

Fast fault-marked smoke: the acceptance scenario — kill 1 of 4
replicas mid-run through the full ServingFleet router. The contract
asserted end to end:

- **zero lost or duplicated completions** — every submitted fleet id
  is delivered exactly once;
- **failover token-identity** — every greedy stream (affected by the
  kill or not) matches its uncontended single-engine run;
- **zero page leaks** — ``PADDLE_TPU_SERVING_AUDIT`` is on
  suite-wide, and every surviving replica's free list is checked
  explicitly.

The randomized kill/wedge/slow sweep stays in the slow tier.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine, ServingFleet
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
    global _REF_ENG
    key = (prompt.tobytes(), int(n_new))
    if key not in _REF_TOKENS:
        if _REF_ENG is None:
            _REF_ENG = _factory()()
        _REF_ENG.add_request(prompt, n_new)
        _REF_TOKENS[key] = _REF_ENG.run()[-1].tokens
    return _REF_TOKENS[key]


def _assert_fleet_clean(fleet, done, fids, specs,
                        require_identity=True):
    """Zero lost/duplicated completions, typed-or-token outcomes,
    token identity for error-free streams, zero leaked pages on every
    surviving replica."""
    assert len(done) == len(fids), "lost or duplicated completions"
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(fids)
    for fid, (prompt, n_new) in zip(fids, specs):
        r = by[fid]
        assert r.finished
        if r.error is None:
            assert r.finish_reason in ("eos", "length")
            if require_identity:
                assert r.tokens == _reference(prompt, n_new), fid
        else:
            from paddle_tpu.inference import ServingError
            assert isinstance(r.error, ServingError), r.error
    for rep in fleet.replicas.values():
        if not rep.live():
            continue            # ejected/retired engines are discarded
        eng = rep.engine
        assert len(eng._free_pages) + eng.prefix_cache_pages \
            == eng.num_pages - 1, rep.id
        assert not eng._deferred_free
        assert all(not p for p in eng.slot_pages)
        assert all(not s for s in eng.slot_shared)


@pytest.mark.fault
def test_kill_one_of_four_replicas_smoke():
    """THE gate scenario (and the acceptance pin): a 4-replica fleet,
    one replica killed mid-run hard enough to trip its breaker — zero
    requests lost, every greedy stream token-identical to the
    uncontended single-engine run, zero pages leaked on the
    survivors."""
    _, cfg = _model()
    rng = np.random.RandomState(11)
    specs = [(rng.randint(0, cfg.vocab_size,
                          (int(rng.randint(3, 10)),)).astype(np.int32),
              int(rng.randint(2, 7))) for _ in range(10)]
    fleet = ServingFleet(_factory(), num_replicas=4, max_restarts=1,
                         retry_backoff_s=0.01)
    fids = [fleet.submit(p, n) for p, n in specs]
    with FaultInjector() as fi:
        fi.kill_replica(1, times=10_000, after_steps=1)
        done = fleet.run()
        assert fi.fires() >= 2      # restart + budget exhaustion
    _assert_fleet_clean(fleet, done, fids, specs)
    by = {r.request_id: r for r in done}
    assert all(by[f].error is None for f in fids)   # zero loss
    g = fleet.gauges()
    assert fleet.replicas[1].state == "ejected"
    assert g["breaker_open"] == 1
    assert g["completed"] == len(fids)


@pytest.mark.fault
@pytest.mark.slow
def test_randomized_kill_wedge_slow_sweep():
    """Slow breadth: randomized workloads x randomized replica fault
    (kill / wedge / slow / none) over a 4-replica fleet — every seed
    must deliver each fleet id exactly once (tokens or typed error),
    leak zero pages, and keep error-free greedy streams
    token-identical."""
    _, cfg = _model()
    for seed in range(6):
        rng = np.random.RandomState(200 + seed)
        specs = [(rng.randint(0, cfg.vocab_size,
                              (int(rng.randint(3, 10)),))
                  .astype(np.int32),
                  int(rng.randint(1, 7)))
                 for _ in range(int(rng.randint(8, 14)))]
        fleet = ServingFleet(_factory(), num_replicas=4,
                             max_restarts=1, retry_backoff_s=0.01,
                             no_progress_turns=6,
                             hedge_delay_s=0.2)
        fids = [fleet.submit(p, n) for p, n in specs]
        fault = rng.choice(["kill", "wedge", "slow", "none"])
        target = int(rng.randint(0, 4))
        with FaultInjector() as fi:
            if fault == "kill":
                fi.kill_replica(target, times=10_000,
                                after_steps=int(rng.randint(0, 4)))
            elif fault == "wedge":
                fi.wedge_replica(target, times=10_000)
            elif fault == "slow":
                fi.slow_replica(target, delay_s=0.01, stride=4)
            done = fleet.run()
        _assert_fleet_clean(fleet, done, fids, specs)
        by = {r.request_id: r for r in done}
        assert all(by[f].error is None for f in fids), \
            (seed, fault, [(f, by[f].error) for f in fids
                           if by[f].error is not None])
