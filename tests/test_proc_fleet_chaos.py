"""Process-fleet chaos smoke (ISSUE 16) — the ``proc_fleet_chaos``
gate in ``tools/run_gates.py``.

The acceptance scenarios, run against REAL worker processes (``python
-m paddle_tpu.inference.worker`` spawned by :class:`ProcReplica`, not
the hermetic fake in test_proc_replica.py):

- **SIGKILL 1 of 4** — a real worker process is SIGKILLed mid-decode,
  hard enough to spend the respawn budget and trip the breaker. Zero
  requests lost or duplicated, every greedy stream token-identical to
  the uncontended in-process run, and every SURVIVING worker passes
  its page-accounting audit over the wire.
- **SIGSTOP** — a worker stops beating but is not dead. The parent
  must classify it as HUNG via heartbeat timeout (never waitpid),
  dump a flight-recorder bundle, put the stopped process down
  (SIGTERM-with-grace then SIGKILL), and the fleet must eject it via
  the no-progress HEALTH check — ``wedge_ejections``, never the
  breaker.

Both tests boot real JAX worker processes, so they are slow-marked:
tier-1 skips them and the gate runs the full ``proc_fleet`` marker.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  ProcReplica, ServingFleet)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import flight_recorder as frec
from paddle_tpu.testing import FaultInjector

pytestmark = [pytest.mark.proc_fleet, pytest.mark.fault,
              pytest.mark.slow]

_ENG_KW = dict(num_slots=2, page_size=8, max_len=48, decode_chunk=4,
               prefill_chunk=16, greedy=True)
_SPEC = {"factory": "paddle_tpu.inference.worker:llama_engine",
         "kwargs": dict(model="tiny", num_hidden_layers=1, seed=0,
                        **_ENG_KW)}

_REF = None          # (cfg, engine) — one in-process twin per session
_REF_TOKENS = {}


def _reference(prompt, n_new):
    """Greedy token oracle: the SAME model the workers build
    (tiny llama, 1 layer, paddle.seed(0)) run uncontended in-process."""
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
            _REF = (cfg, ContinuousBatchingEngine(m, **_ENG_KW))
        _REF[1].add_request(prompt, n_new)
        _REF_TOKENS[key] = _REF[1].run()[-1].tokens
    return _REF_TOKENS[key]


def _specs(seed, n):
    cfg = LlamaConfig.tiny()
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size,
                         (int(rng.randint(3, 10)),)).astype(np.int32),
             int(rng.randint(2, 7))) for _ in range(n)]


def _fleet(num_replicas, **rep_kw):
    rep_kw.setdefault("hb_timeout_s", 5.0)
    rep_kw.setdefault("respawn_backoff_s", 0.01)
    return ServingFleet(_SPEC, num_replicas=num_replicas,
                        max_restarts=1, retry_backoff_s=0.01,
                        replica_cls=ProcReplica,
                        replica_kwargs=rep_kw)


def _assert_exactly_once_and_identical(done, fids, specs):
    assert len(done) == len(fids), "lost or duplicated completions"
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted(fids)
    for fid, (prompt, n_new) in zip(fids, specs):
        r = by[fid]
        assert r.finished
        assert r.error is None, (fid, r.error)
        assert r.finish_reason in ("eos", "length")
        assert r.tokens == _reference(prompt, n_new), fid


def test_sigkill_one_of_four_workers():
    """THE acceptance pin: 4 process-backed replicas, one worker
    SIGKILLed at every step until its respawn budget is spent — the
    breaker opens, its shadow reroutes, zero streams lost or
    duplicated, every stream token-identical, and each surviving
    worker's page audit comes back clean over the wire."""
    specs = _specs(11, 10)
    fleet = _fleet(4)
    try:
        fids = [fleet.submit(p, n) for p, n in specs]
        with FaultInjector() as fi:
            fi.kill_worker(1, times=10_000, after_steps=1)
            done = fleet.run()
            assert fi.fires() >= 2      # respawn + budget exhaustion
        _assert_exactly_once_and_identical(done, fids, specs)
        g = fleet.gauges()
        assert g["breaker_open"] == 1
        assert g["wedge_ejections"] == 0
        assert g["completed"] == len(fids)
        assert fleet.replicas[1].state == "ejected"
        assert fleet.replicas[1].eject_kind == "breaker"
        kept = fleet.replicas[1]
        assert kept.respawns >= 1       # the budget was really spent
        for rep in fleet.replicas.values():
            if rep.live():
                verdict = rep.audit()
                assert verdict["clean"], (rep.id, verdict)
    finally:
        fleet.close()


def test_sigstop_worker_is_wedge_ejected_with_bundle(tmp_path):
    """A SIGSTOPped worker is alive by waitpid but beats no more: the
    parent must declare it HUNG (flight-recorder bundle + SIGTERM
    grace + SIGKILL) and the fleet must eject it via the no-progress
    health check — ``wedge_ejections == 1`` and the breaker stays
    CLOSED. Streams salvage from the shadow and finish elsewhere,
    exactly-once and token-identical."""
    specs = _specs(16, 6)
    rec = frec.install(bundle_dir=str(tmp_path))
    fleet = _fleet(2, hb_timeout_s=1.0, rpc_deadline_s=0.25)
    try:
        fids = [fleet.submit(p, n) for p, n in specs]
        with FaultInjector() as fi:
            fi.pause_worker(1, after_steps=1)
            done = fleet.run()
            assert fi.fires() == 1
        _assert_exactly_once_and_identical(done, fids, specs)
        g = fleet.gauges()
        assert g["wedge_ejections"] == 1
        assert g["breaker_open"] == 0   # hung is NOT the breaker path
        assert fleet.replicas[1].state == "ejected"
        assert fleet.replicas[1].eject_kind == "wedge"
        assert fleet.replicas[1].respawns == 0   # hung != dead
        # the stopped process was put down, not leaked
        assert fleet.replicas[1]._proc.poll() is not None
        # the post-mortem bundle: dumped, on disk, and it names the
        # hung worker
        assert rec.dumps >= 1
        assert rec.last_bundle_path is not None
        with open(rec.last_bundle_path) as f:
            doc = json.load(f)
        assert "hung" in doc["reason"]
        kinds = [e["kind"] for e in doc["events"]]
        assert "proc_worker_hung" in kinds
        for rep in fleet.replicas.values():
            if rep.live():
                assert rep.audit()["clean"], rep.id
    finally:
        fleet.close()
        frec.uninstall()
