"""Disaggregation chaos (ISSUE 17) — the ``disagg_chaos`` gate's slow
half, against REAL worker processes.

The two acceptance kills, each with the full correctness bar
(exactly-once, greedy token identity vs the colocated in-process
oracle, page audits green over the wire on every surviving worker):

- **prefill worker SIGKILLed mid-transfer** — died with KV pages
  parked for pickup. The payload is lost; the requests are NOT: they
  stayed in the parent shadow via the step reply's ``migrating``
  re-statement, so the respawn replays them from their prompts and
  they migrate again.
- **decode worker SIGKILLed mid-decode** — killed at every step until
  its respawn budget is spent and the breaker opens. Emitted tokens
  salvage through the shadow; with no decode-capable replica left the
  fleet pins ``no_migrate`` and the streams complete COLOCATED on the
  prefill replica (cross-role failover, never a migrate/replay
  livelock).
"""

import os
import signal

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  DisaggServingFleet, ProcReplica)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import FaultInjector

pytestmark = [pytest.mark.disagg, pytest.mark.fault, pytest.mark.slow]

_ENG_KW = dict(num_slots=2, page_size=8, max_len=48, decode_chunk=4,
               prefill_chunk=16, greedy=True)
_SPEC = {"factory": "paddle_tpu.inference.worker:llama_engine",
         "kwargs": dict(model="tiny", num_hidden_layers=1, seed=0,
                        **_ENG_KW)}

_REF = None
_REF_TOKENS = {}


def _reference(prompt, n_new):
    """Colocated greedy oracle: the same tiny model the workers build
    (seed 0), run uncontended in-process."""
    global _REF
    key = (prompt.tobytes(), int(n_new))
    if key not in _REF_TOKENS:
        if _REF is None:
            cfg = LlamaConfig.tiny()
            cfg.tensor_parallel = False
            cfg.scan_layers = False
            cfg.num_hidden_layers = 1
            paddle.seed(0)
            m = LlamaForCausalLM(cfg)
            m.eval()
            _REF = ContinuousBatchingEngine(m, **_ENG_KW)
        _REF.add_request(prompt, n_new)
        _REF_TOKENS[key] = _REF.run()[-1].tokens
    return _REF_TOKENS[key]


def _specs(seed, n):
    cfg = LlamaConfig.tiny()
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size,
                         (int(rng.randint(9, 16)),)).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(n)]


def _fleet(num_prefill, num_decode, **kw):
    return DisaggServingFleet(
        _SPEC, num_prefill=num_prefill, num_decode=num_decode,
        replica_cls=ProcReplica,
        replica_kwargs=dict(hb_timeout_s=5.0,
                            respawn_backoff_s=0.01),
        max_restarts=1, retry_backoff_s=0.01, **kw)


def _assert_exactly_once_and_identical(done, fids, specs):
    assert len(done) == len(fids), "lost or duplicated completions"
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(fids)
    for fid, (prompt, n_new) in zip(fids, specs):
        r = by[fid]
        assert r.finished
        assert r.error is None, (fid, r.error)
        assert r.tokens == _reference(prompt, n_new), fid


def test_kill_prefill_worker_mid_transfer(monkeypatch):
    """SIGKILL the prefill worker at the exact pickup window: KV
    pages are parked worker-side, the take_migrations RPC is about to
    fire. The payload dies with the process; every request replays
    from the shadow after the respawn and the streams stay
    token-identical, exactly-once, with clean audits on both sides."""
    specs = _specs(23, 6)
    fleet = _fleet(1, 1)
    killed = {"n": 0}
    orig = ProcReplica.take_migrations

    def kill_at_pickup(rep):
        if rep.id == 0 and killed["n"] < 1 \
                and getattr(rep, "_migrating", None) and rep.worker_pid:
            killed["n"] += 1
            os.kill(rep.worker_pid, signal.SIGKILL)
        return orig(rep)

    monkeypatch.setattr(ProcReplica, "take_migrations", kill_at_pickup)
    try:
        fids = [fleet.submit(p, n) for p, n in specs]
        done = fleet.run()
        assert killed["n"] == 1, "the mid-transfer window never opened"
        _assert_exactly_once_and_identical(done, fids, specs)
        assert fleet.replicas[0].respawns >= 1
        assert fleet.metrics.counter("disagg/migrations").value >= 1
        g = fleet.gauges()
        assert g["completed"] == len(fids)
        for rep in fleet.replicas.values():
            if rep.live():
                verdict = rep.audit()
                assert verdict["clean"], (rep.id, verdict)
    finally:
        fleet.close()


def test_kill_decode_worker_mid_decode():
    """SIGKILL the decode worker at every step until its respawn
    budget is spent: the breaker opens, emitted tokens salvage off
    the shadow, and with zero decode capacity left the requests pin
    ``no_migrate`` and finish colocated on the prefill replica —
    exactly-once, token-identical, prefill audit clean."""
    specs = _specs(29, 6)
    fleet = _fleet(1, 1)
    try:
        fids = [fleet.submit(p, n) for p, n in specs]
        with FaultInjector() as fi:
            fi.kill_worker(1, times=10_000, after_steps=2)
            done = fleet.run()
            assert fi.fires() >= 2      # respawn + budget exhaustion
        _assert_exactly_once_and_identical(done, fids, specs)
        g = fleet.gauges()
        assert g["completed"] == len(fids)
        assert g["breaker_open"] == 1
        assert fleet.replicas[1].state == "ejected"
        # migrations that raced the kill may have failed over; either
        # way the prefill replica carried the fleet alone afterwards
        rep0 = fleet.replicas[0]
        assert rep0.live()
        verdict = rep0.audit()
        assert verdict["clean"], verdict
    finally:
        fleet.close()
