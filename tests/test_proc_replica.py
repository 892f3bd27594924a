"""ProcReplica seam tests (ISSUE 16).

The fast tier runs HERMETIC: a ``_FakeProc`` drives the REAL
``Worker`` protocol loop (serve / reply cache / incremental harvest /
metrics diff — production code, not a stub) in a thread over a real
socketpair, with a tiny deterministic fake engine instead of a model,
via the ``spec["_spawn_fn"]`` seam. That exercises every parent-side
path — admit/step mirroring, shadow salvage + respawn replay, the
restart budget, retransmit dedup, hung-via-heartbeat classification,
corrupt-wire recovery, and the full ServingFleet router over
``replica_cls=ProcReplica`` — in milliseconds, with no process spawn
and no XLA.

The slow tier at the bottom boots a REAL ``python -m
paddle_tpu.inference.worker`` process and pins greedy token identity
against an in-process reference engine (same seed ⇒ same weights ⇒
same stream across the process boundary).
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — backend pinned by conftest
from paddle_tpu.inference import (Overloaded, ProcReplica,
                                  ReplicaFailed, ServingFleet)
from paddle_tpu.inference.serving import ServedRequest
from paddle_tpu.inference.wire import WireClosed, WireTransport, socketpair
from paddle_tpu.inference.worker import Worker, _heartbeat_loop
from paddle_tpu.profiler.metrics import MetricsRegistry
from paddle_tpu.testing import FaultInjector

pytestmark = pytest.mark.proc_fleet


# ---- the hermetic worker ---------------------------------------------------

class _FakeEngine:
    """Deterministic engine stand-in: each step admits queue → slots
    and emits token ``1000 + rid*97 + position`` per running request,
    finishing at ``max_new_tokens``. Page accounting is simulated just
    enough for the audit op."""

    def __init__(self, num_slots=2, page_size=8, max_len=64):
        self.metrics = MetricsRegistry()
        self.num_slots = num_slots
        self.page_size = page_size
        self.max_len = max_len
        self.decode_chunk = 1
        self.num_pages = 9
        self.queue = []
        self.slot_req = [None] * num_slots
        self._free_pages = list(range(self.num_pages - 1))
        self._deferred_free = []
        self.slot_pages = [[] for _ in range(num_slots)]
        self.slot_shared = [[] for _ in range(num_slots)]
        self.prefix_cache_pages = 0
        self.steps = 0

    def requeue(self, req):
        if req.finished:
            return
        self.queue.append(req)

    def step(self):
        self.steps += 1
        self.metrics.counter("serving/unified_steps").inc()
        for i in range(self.num_slots):
            if self.slot_req[i] is None and self.queue:
                self.slot_req[i] = self.queue.pop(0)
        finished = []
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            if r.cancelled:
                r.finished = True
                r.finish_reason = "cancelled"
                r.t_done = time.perf_counter()
                finished.append(r)
                self.slot_req[i] = None
                continue
            if not r.t_first:
                r.t_first = time.perf_counter()
            r.tokens.append(1000 + r.request_id * 97 + len(r.tokens))
            self.metrics.counter("serving/tokens_emitted").inc()
            if len(r.tokens) >= r.max_new_tokens:
                r.finished = True
                r.finish_reason = "length"
                r.t_done = time.perf_counter()
                self.metrics.counter(
                    "serving/requests_completed").inc()
                finished.append(r)
                self.slot_req[i] = None
        return finished

    def cancel(self, rid):
        for r in self.queue + self.slot_req:
            if r is not None and r.request_id == rid \
                    and not r.finished:
                r.cancelled = True
                return True
        return False

    def handoff(self):
        out = [r for r in self.queue if not r.finished]
        out += [r for r in self.slot_req
                if r is not None and not r.finished]
        self.queue = []
        self.slot_req = [None] * self.num_slots
        return out

    def reset_gauges(self):
        pass

    def gauges(self):
        return {"steps": self.steps}


def _expected_tokens(rid, n_new):
    return [1000 + rid * 97 + k for k in range(n_new)]


class _FakeWorker(Worker):
    """Real protocol loop; only ``init`` is replaced (no dotted
    factory — the engine comes from the test)."""

    def __init__(self, transport, engine_factory, proc):
        super().__init__(transport)
        self._engine_factory = engine_factory
        self._proc = proc

    def _handle(self, op, msg):
        while self._proc._paused.is_set() \
                and not self._proc._killed.is_set():
            time.sleep(0.002)            # SIGSTOP: silent, not dead
        if self._proc._killed.is_set():
            raise WireClosed("killed")
        if op == "init":
            self.engine = self._engine_factory()
            eng = self.engine
            return {"pid": self._proc.pid,
                    "geom": {"num_slots": eng.num_slots,
                             "page_size": eng.page_size,
                             "max_len": eng.max_len,
                             "decode_chunk": eng.decode_chunk,
                             "num_pages": eng.num_pages}}
        return super()._handle(op, msg)


class _FakeProc:
    """Process façade over a worker thread: pid/poll/terminate/kill/
    wait, plus pause() to model SIGSTOP (heartbeats and replies stop,
    the 'process' stays alive)."""

    _pid_counter = [900_000_001]

    def __init__(self, engine_factory, hb_interval=0.02):
        self.pid = self._pid_counter[0]
        self._pid_counter[0] += 1
        self.returncode = None
        self._paused = threading.Event()
        self._killed = threading.Event()
        self._stop_hb = threading.Event()
        self.parent_sock, worker_sock = socketpair()
        self._tr = WireTransport(worker_sock, side="worker")
        self.worker = _FakeWorker(self._tr, engine_factory, self)
        self._hb = threading.Thread(
            target=self._hb_loop, args=(hb_interval,), daemon=True)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._hb.start()
        self._thread.start()

    def _hb_loop(self, interval):
        while not self._stop_hb.wait(interval):
            if self._paused.is_set():
                continue
            try:
                self._tr.send({"kind": "hb",
                               "t": time.perf_counter()})
            except Exception:  # noqa: BLE001 — transport torn down
                return

    def _run(self):
        try:
            self.worker.serve()
        except Exception:  # noqa: BLE001 — fatal contract
            self.returncode = 1
        else:
            if self.returncode is None:
                self.returncode = 0
        self._stop_hb.set()
        self._tr.close()

    def pause(self):
        self._paused.set()

    def resume(self):
        self._paused.clear()

    # -- subprocess.Popen façade --------------------------------------

    def poll(self):
        return self.returncode

    def terminate(self):
        self.kill()

    def kill(self):
        if self.returncode is None:
            self.returncode = -9
        self._killed.set()
        self._paused.clear()
        self._stop_hb.set()
        self._tr.close()

    def wait(self, timeout=None):
        self._thread.join(timeout)
        return self.returncode


class _Spawner:
    """``spec["_spawn_fn"]``: builds a fresh _FakeProc per (re)spawn
    and remembers them so tests can kill/pause a specific
    incarnation."""

    def __init__(self, engine_factory=None, hb_interval=0.02):
        self.engine_factory = engine_factory or _FakeEngine
        self.hb_interval = hb_interval
        self.procs = []

    def __call__(self, replica):
        p = _FakeProc(self.engine_factory, self.hb_interval)
        self.procs.append(p)
        return p, p.parent_sock

    def spec(self):
        return {"_spawn_fn": self}


def _replica(spawner=None, **kw):
    spawner = spawner or _Spawner()
    kw.setdefault("rpc_deadline_s", 0.1)
    kw.setdefault("hb_timeout_s", 0.25)
    kw.setdefault("term_grace_s", 0.05)
    kw.setdefault("respawn_backoff_s", 0.001)
    rep = ProcReplica(0, spawner.spec(), **kw)
    return rep, spawner


def _submit(rep, rid, n_new=4, prompt_len=3):
    req = ServedRequest(rid, np.arange(prompt_len, dtype=np.int32),
                        n_new, None)
    req.t_arrive = time.perf_counter()
    rep.admission.admit(req)
    return req


def _run(rep, reqs, max_steps=200):
    done = []
    for _ in range(max_steps):
        done.extend(rep.step())
        if all(r.finished for r in reqs):
            return done
    raise AssertionError("requests did not complete")


# ---- happy path ------------------------------------------------------------

def test_admit_step_mirror_and_complete():
    rep, sp = _replica()
    try:
        reqs = [_submit(rep, i, n_new=3 + i) for i in range(3)]
        done = _run(rep, reqs)
        assert sorted(r.request_id for r in done) == [0, 1, 2]
        for r in reqs:
            # the PARENT's objects carry the tokens (the shadow
            # mirror), exactly the deterministic stream
            assert r.tokens == _expected_tokens(r.request_id,
                                                r.max_new_tokens)
            assert r.finish_reason == "length"
            assert r.t_first and r.t_done
        # occupancy restated from the worker's truth
        assert rep.engine.queue == []
        assert all(s is None for s in rep.engine.slot_req)
        assert not rep.engine.has_work()
        # worker-side registry diff landed in the shadow registry
        reg = rep.engine.metrics
        assert reg.counter("serving/tokens_emitted").value \
            == sum(r.max_new_tokens for r in reqs)
        assert rep.engine.gauges().get("steps", 0) > 0
        assert rep.respawns == 0
    finally:
        rep.close()


def test_clock_offset_maps_worker_times():
    rep, sp = _replica()
    try:
        req = _submit(rep, 0, n_new=2)
        t0 = time.perf_counter()
        _run(rep, [req])
        t1 = time.perf_counter()
        # worker timestamps arrive translated into the parent's
        # perf_counter domain (same process here, so the offset is
        # ~0 and the times must bracket)
        assert t0 - 0.5 <= req.t_first <= t1 + 0.5
        assert t0 - 0.5 <= req.t_done <= t1 + 0.5
    finally:
        rep.close()


def test_audit_roundtrip():
    rep, sp = _replica()
    try:
        v = rep.audit()
        assert v["clean"] is True
        assert v["free"] == 8
    finally:
        rep.close()


def test_cancel_rpc():
    rep, sp = _replica()
    try:
        reqs = [_submit(rep, i, n_new=8) for i in range(2)]
        rep.step()
        rep.supervisor.cancel(1)
        done = _run(rep, reqs)
        by = {r.request_id: r for r in done}
        assert by[1].finish_reason == "cancelled"
        assert by[0].tokens == _expected_tokens(0, 8)
    finally:
        rep.close()


# ---- dead: salvage from shadow + respawn replay ----------------------------

def test_worker_death_respawns_and_replays_continuously():
    rep, sp = _replica(max_restarts=2)
    try:
        reqs = [_submit(rep, i, n_new=6) for i in range(3)]
        for _ in range(2):
            rep.step()
        mid = [list(r.tokens) for r in reqs]
        assert any(mid), "no progress before the kill"
        sp.procs[-1].kill()              # the corpse answers nothing
        done = _run(rep, reqs)
        assert rep.respawns == 1
        assert len(sp.procs) == 2
        # exactly-once, and the stream CONTINUED where the shadow had
        # it: full deterministic token identity after replay
        assert sorted(r.request_id for r in done) == [0, 1, 2]
        for r in reqs:
            assert r.tokens == _expected_tokens(r.request_id, 6), \
                (r.request_id, mid)
            assert any(h.get("kind") == "respawn" for h in r.hops)
        reg = rep.engine.metrics
        assert reg.counter("proc/respawns").value == 1
        assert reg.counter("proc/spawns").value == 2
    finally:
        rep.close()


def test_respawn_budget_exhausted_raises_for_breaker():
    rep, sp = _replica(max_restarts=0)
    try:
        _submit(rep, 0, n_new=4)
        sp.procs[-1].kill()
        with pytest.raises(ReplicaFailed):
            rep.step()
        assert rep.respawns == 0          # budget checked BEFORE spend
    finally:
        rep.close()


def test_admit_to_dead_worker_respawns_then_admits():
    rep, sp = _replica(max_restarts=1)
    try:
        sp.procs[-1].kill()
        req = _submit(rep, 0, n_new=3)    # admit rides the respawn
        assert rep.respawns == 1
        done = _run(rep, [req])
        assert done[0].tokens == _expected_tokens(0, 3)
    finally:
        rep.close()


def test_death_mid_replay_loses_no_salvage():
    """A respawned worker that dies PARTWAY through the replay must
    not shrink the salvage set: the next lap (and a budget-spent
    raise) must still carry every unfinished request, not just the
    ones re-admitted before the second death."""
    rep, sp = _replica(max_restarts=3)
    try:
        reqs = [_submit(rep, i, n_new=4) for i in range(3)]
        rep.step()                        # 2 in slots, 1 queued
        orig = rep._rpc_checked
        state = {"armed": False, "admits": 0}

        def wrapper(op, payload, **kw):
            if op == "admit" and state["armed"]:
                state["admits"] += 1
                if state["admits"] == 2:
                    state["armed"] = False
                    sp.procs[-1].kill()   # die mid-replay, after req 1
            return orig(op, payload, **kw)

        rep._rpc_checked = wrapper
        state["armed"] = True
        sp.procs[-1].kill()               # first death → replay lap 1
        done = _run(rep, reqs)
        assert rep.respawns == 2
        assert sorted(r.request_id for r in done) == [0, 1, 2]
        for r in reqs:
            assert r.tokens == _expected_tokens(r.request_id, 4)
    finally:
        rep.close()


# ---- hung: heartbeat classification (wedge, not breaker) -------------------

def test_paused_worker_is_hung_not_dead():
    rep, sp = _replica(hb_timeout_s=0.15)
    try:
        reqs = [_submit(rep, 0, n_new=8)]
        rep.step()
        sp.procs[-1].pause()             # SIGSTOP shape: alive, silent
        out = rep.step()                 # classifies hung, returns []
        assert out == []
        assert rep.wedged(25)            # fleet ejects via HEALTH
        reg = rep.engine.metrics
        assert reg.counter("proc/heartbeat_misses").value == 1
        assert rep.respawns == 0         # hung is NOT the respawn path
        # the hung corpse was SIGKILLed (fake: returncode set)
        assert sp.procs[-1].poll() is not None
        del reqs
    finally:
        rep.close()


def test_slow_reply_with_heartbeats_is_not_hung():
    # the deadlines that must NOT fire (heartbeat silence 10 s, hard
    # deadline 60 s) stand three orders above the soft deadline that
    # must (20 ms), and the worker beats every 50 ms: slower than the
    # parent's 20 ms receive poll, so a poll runs dry between two beats
    # and the soft deadline is looked at
    rep, sp = _replica(_Spawner(hb_interval=0.05), rpc_deadline_s=0.02,
                       rpc_retries=2, rpc_hard_deadline_s=60.0,
                       hb_timeout_s=10.0)
    try:
        retries = rep.engine.metrics.counter("proc/rpc_retries")
        # hold every step's reply until the parent HAS retransmitted
        # it (deduped by the worker's reply cache) and then heard a
        # heartbeat — the events themselves, not a sleep that stands
        # for them: the RPC lands late, no hung declaration
        orig = _FakeWorker._handle
        held = []

        def until(cond, give_up):
            while not cond() and time.perf_counter() < give_up:
                time.sleep(0.002)
            return cond()

        def slow(self, op, msg):
            if op == "step":
                n0, give_up = retries.value, time.perf_counter() + 8.0
                retried = until(lambda: retries.value > n0, give_up)
                beat = rep.last_beat
                held.append(retried and until(
                    lambda: rep.last_beat > beat, give_up))
            return orig(self, op, msg)

        _FakeWorker._handle = slow
        try:
            reqs = [_submit(rep, 0, n_new=2)]
            done = _run(rep, reqs, max_steps=20)
        finally:
            _FakeWorker._handle = orig
        assert done[0].tokens == _expected_tokens(0, 2)
        assert not rep._hung
        assert held and all(held)
        assert retries.value >= len(held)
    finally:
        rep.close()


# ---- lossy: FaultInjector wire plans ---------------------------------------

def test_dropped_rpc_frame_retransmits_exactly_once():
    rep, sp = _replica(rpc_deadline_s=0.05)
    try:
        req = _submit(rep, 0, n_new=5)
        with FaultInjector() as fi:
            fi.drop_frame(0, times=2, direction="tx")
            done = _run(rep, [req])
            assert fi.fires() == 2
        # the dropped step RPCs were retransmitted and applied ONCE:
        # token stream is exact (a double-applied step would overshoot
        # or duplicate positions)
        assert done[0].tokens == _expected_tokens(0, 5)
        assert rep.engine.metrics.counter(
            "proc/rpc_retries").value >= 2
        assert rep.respawns == 0
    finally:
        rep.close()


def test_corrupt_rx_frame_typed_error_then_recovery():
    rep, sp = _replica(rpc_deadline_s=0.05)
    try:
        req = _submit(rep, 0, n_new=5)
        with FaultInjector() as fi:
            fi.corrupt_frame(0, times=3, direction="rx")
            done = _run(rep, [req])
            assert fi.fires() == 3
        assert done[0].tokens == _expected_tokens(0, 5)
        assert rep.engine.metrics.counter("wire/errors").value >= 1
        assert rep.respawns == 0          # lossy ≠ dead
        assert not rep._hung              # lossy ≠ hung
    finally:
        rep.close()


def test_delayed_frames_only_slow_things_down():
    rep, sp = _replica(rpc_deadline_s=0.05)
    try:
        req = _submit(rep, 0, n_new=3)
        with FaultInjector() as fi:
            fi.delay_frame(0, delay_s=0.08, times=2, direction="rx")
            done = _run(rep, [req])
        assert done[0].tokens == _expected_tokens(0, 3)
        assert rep.respawns == 0 and not rep._hung
    finally:
        rep.close()


# ---- the fleet router over ProcReplica -------------------------------------

def test_fleet_router_over_proc_replicas_failover():
    """The hermetic acceptance shape: a 2-replica process-backed
    fleet, one worker killed hard enough to spend its budget — the
    router fails the shadow over to the sibling, exactly-once, token
    streams deterministic, breaker accounted."""
    spawners = {0: _Spawner(), 1: _Spawner()}
    fleet = ServingFleet(
        lambda: None, num_replicas=0, retry_backoff_s=0.001,
        replica_cls=ProcReplica,
        replica_kwargs=dict(rpc_deadline_s=0.1, hb_timeout_s=0.3,
                            term_grace_s=0.05,
                            respawn_backoff_s=0.001, max_queue=64))
    # hand-add replicas so each gets its own spawner identity
    for i in (0, 1):
        fleet._add_replica(spawners[i].spec())
    assert sorted(fleet.replicas) == [0, 1]
    fids = [fleet.submit(np.arange(3, dtype=np.int32), 4)
            for _ in range(8)]

    # kill replica 1's worker at EVERY step (the fi.kill_worker
    # shape, deterministic): each incarnation dies, the budget (2)
    # spends, the breaker opens, everything lands on replica 0
    rep1 = fleet.replicas[1]
    orig_step = rep1._step_rpc

    def dying_step():
        spawners[1].procs[-1].kill()
        return orig_step()

    rep1._step_rpc = dying_step
    done = fleet.run()
    assert sorted(r.request_id for r in done) == sorted(fids)
    by = {r.request_id: r for r in done}
    for fid in fids:
        assert by[fid].error is None
        assert by[fid].finish_reason == "length"
    g = fleet.gauges()
    assert g["completed"] == len(fids)
    assert fleet.replicas[1].state == "ejected"
    assert fleet.replicas[1].eject_kind == "breaker"
    assert g["breaker_open"] == 1
    # survivor audit across the seam
    assert fleet.replicas[0].audit()["clean"]
    fleet.close()
    # close() reaped every incarnation
    for sp in spawners.values():
        assert all(p.poll() is not None for p in sp.procs)


def test_fleet_ejects_hung_proc_replica_via_health_not_breaker():
    spawners = {0: _Spawner(), 1: _Spawner()}
    fleet = ServingFleet(
        lambda: None, num_replicas=0, retry_backoff_s=0.001,
        no_progress_turns=5, replica_cls=ProcReplica,
        replica_kwargs=dict(rpc_deadline_s=0.1, hb_timeout_s=0.15,
                            term_grace_s=0.05,
                            respawn_backoff_s=0.001))
    for i in (0, 1):
        fleet._add_replica(spawners[i].spec())
    fids = [fleet.submit(np.arange(3, dtype=np.int32), 4)
            for _ in range(6)]
    # let work spread, then freeze replica 1's worker (SIGSTOP shape)
    fleet.step()
    spawners[1].procs[-1].pause()
    done = fleet.run()
    assert sorted(r.request_id for r in done) == sorted(fids)
    assert all(r.error is None for r in done)
    g = fleet.gauges()
    assert g["wedge_ejections"] == 1
    assert g["breaker_open"] == 0        # heartbeat path, NOT breaker
    assert fleet.replicas[1].eject_kind == "wedge"
    fleet.close()


@pytest.mark.parametrize("kill_mid_drain", [False, True])
def test_scale_down_drain_handoff_exactly_once(kill_mid_drain):
    """ISSUE-19 satellite: drain-based ``scale_down`` composes with
    process-backed replicas. The drain deadline fires ``handoff()``
    over the crc-framed wire; with ``kill_mid_drain`` the worker is
    SIGKILLed between drain-begin and the handoff rpc, so the salvage
    comes from the parent-side shadow (or a respawn replay) instead.
    Either way: every request completes exactly once, token streams
    stay deterministic, the replica RETIRES (never ejects), and the
    survivor's page audit is green."""
    spawners = {0: _Spawner(), 1: _Spawner()}
    fleet = ServingFleet(
        lambda: None, num_replicas=0, retry_backoff_s=0.001,
        replica_cls=ProcReplica,
        replica_kwargs=dict(rpc_deadline_s=0.1, hb_timeout_s=0.3,
                            term_grace_s=0.05,
                            respawn_backoff_s=0.001, max_queue=64))
    for i in (0, 1):
        fleet._add_replica(spawners[i].spec())
    fids = [fleet.submit(np.arange(3, dtype=np.int32), 6)
            for _ in range(8)]
    fleet.step()                      # work spreads, tokens flow
    assert fleet.replicas[1].has_work()
    fleet.scale_down(replica_id=1, deadline_s=0.0)
    if kill_mid_drain:
        spawners[1].procs[-1].kill()
    done = fleet.run()
    # exactly-once: no lost, no duplicated completions
    assert sorted(r.request_id for r in done) == sorted(fids)
    by = {r.request_id: r for r in done}
    for fid in fids:
        assert by[fid].error is None, by[fid].error
        assert by[fid].tokens == _expected_tokens(fid, 6), fid
    assert fleet.replicas[1].state == "retired"
    assert fleet.gauges()["breaker_open"] == 0
    assert fleet.metrics.counter("fleet/drains").value == 1
    assert fleet.replicas[0].audit()["clean"]
    fleet.close()
    for sp in spawners.values():
        assert all(p.poll() is not None for p in sp.procs)


# ---- real process (slow tier) ----------------------------------------------

@pytest.mark.slow
def test_real_worker_token_identity_and_sigkill_respawn():
    """One REAL worker process: greedy streams across the process
    boundary are token-identical to an in-process engine, and a real
    SIGKILL mid-decode salvages from the shadow, respawns, and
    finishes the same streams exactly-once."""
    import os
    import signal as _sig

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    eng_kw = dict(num_slots=2, page_size=8, max_len=48,
                  decode_chunk=4, prefill_chunk=16, greedy=True)
    spec = {"factory": "paddle_tpu.inference.worker:llama_engine",
            "kwargs": dict(model="tiny", num_hidden_layers=1, seed=0,
                           **eng_kw)}

    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 1
    paddle.seed(0)
    ref_model = LlamaForCausalLM(cfg)
    ref_model.eval()
    ref_eng = ContinuousBatchingEngine(ref_model, **eng_kw)
    rng = np.random.RandomState(5)
    specs = [(rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32),
              5) for _ in range(4)]
    ref_tokens = {}
    for i, (p, n) in enumerate(specs):
        ref_eng.add_request(p, n)
    for r in ref_eng.run():
        ref_tokens[r.request_id] = r.tokens

    rep = ProcReplica(0, spec, max_restarts=2, hb_timeout_s=5.0,
                      respawn_backoff_s=0.01)
    try:
        reqs = []
        for i, (p, n) in enumerate(specs):
            req = ServedRequest(i, p, n, None)
            req.t_arrive = time.perf_counter()
            rep.admission.admit(req)
            reqs.append(req)
        # a few real steps (harvest — short streams can finish before
        # the kill), then a REAL SIGKILL mid-decode
        done = []
        for _ in range(2):
            done.extend(rep.step())
        pid = rep.worker_pid
        os.kill(pid, _sig.SIGKILL)
        for _ in range(400):
            done.extend(rep.step())
            if all(r.finished for r in reqs):
                break
        assert all(r.finished for r in reqs)
        assert rep.respawns >= 1
        assert rep.worker_pid != pid
        assert sorted(r.request_id for r in done) == [0, 1, 2, 3]
        for r in reqs:
            assert r.error is None
            assert r.tokens == ref_tokens[r.request_id], r.request_id
        assert rep.audit()["clean"]
        reg = rep.engine.metrics
        assert reg.counter("proc/respawns").value >= 1
        assert reg.counter("proc/spawns").value >= 2
        assert reg.histogram("proc/rpc_ms").count > 0
        assert reg.gauge("proc/worker_rss_bytes").value > 0
    finally:
        rep.close()
