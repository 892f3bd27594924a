"""ISSUE-9: the stall flight recorder — ring semantics, watchdog
no-progress dumps, atomic bundles under fault injection, and the
acceptance scenarios: a FaultInjector-induced stall and a
SIGKILL-shaped crash each leave a COMPLETE, atomically-written debug
bundle (ring events + all-thread stacks + metrics snapshot)."""

import errno
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from paddle_tpu.profiler import flight_recorder as fr
from paddle_tpu.profiler import metrics
from paddle_tpu.profiler.flight_recorder import (BUNDLE_NAME,
                                                 BUNDLE_SCHEMA,
                                                 FlightRecorder,
                                                 Watchdog)
from paddle_tpu.testing import FaultInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _uninstalled():
    """Every test starts and ends with no process-wide recorder."""
    fr.uninstall()
    yield
    fr.uninstall()


def _load_bundle(path):
    doc = json.loads(open(path, encoding="utf-8").read())
    assert doc["schema"] == BUNDLE_SCHEMA
    for key in ("reason", "ts", "pid", "restart_round", "events",
                "threads", "metrics"):
        assert key in doc, key
    return doc


# ---- ring semantics -------------------------------------------------------

def test_ring_keeps_last_capacity_events_in_order():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("turn", i=i)
    evs = rec.events()
    assert len(evs) == 8
    assert [e["i"] for e in evs] == list(range(12, 20))   # newest 8
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    assert all(e["kind"] == "turn" for e in evs)


def test_record_event_noop_until_installed():
    assert fr.record_event("turn", x=1) is None      # no recorder: free
    rec = fr.install(capacity=16)
    before = metrics.get_registry().counter("obs/ring_events").value
    fr.record_event("turn", x=1)
    assert len(rec.events()) == 1
    assert metrics.get_registry().counter("obs/ring_events").value \
        == before + 1


def test_concurrent_recording_wait_free():
    rec = FlightRecorder(capacity=128)
    n_threads, per = 6, 2000
    start = threading.Barrier(n_threads)

    def worker(k):
        start.wait()
        for i in range(per):
            rec.record("turn", k=k, i=i)

    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = rec.events()
    assert len(evs) == 128
    # the ring's tail is the newest 128 sequence numbers, gap-free
    seqs = [e["seq"] for e in evs]
    assert seqs == list(range(n_threads * per - 128, n_threads * per))


# ---- bundles --------------------------------------------------------------

def test_dump_bundle_contents(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.counter("t/frc").inc(7)
    rec = FlightRecorder(capacity=16, bundle_dir=str(tmp_path),
                         registry=reg)
    rec.record("checkpoint_phase", phase="stage")
    rec.record("collective", op="process_allgather")
    path = rec.dump("unit test")
    assert path == os.path.join(str(tmp_path), BUNDLE_NAME)
    doc = _load_bundle(path)
    assert doc["reason"] == "unit test"
    assert [e["kind"] for e in doc["events"]] == ["checkpoint_phase",
                                                 "collective"]
    assert doc["metrics"]["t/frc"] == 7
    # every live thread's stack is present, this one included
    assert any("MainThread" in k for k in doc["threads"])
    assert any("test_dump_bundle_contents" in line
               for frames in doc["threads"].values()
               for line in frames)
    assert reg.counter("obs/bundle_dumps").value == 1


def test_dump_without_destination_is_none():
    rec = FlightRecorder(capacity=4)
    assert rec.dump("nowhere") is None


def test_incident_bundle_survives_periodic_overwrite(tmp_path):
    """A stall/crash post-mortem must not be destroyed by the next
    periodic persist: incidents are preserved under their own names,
    pruned to keep_incidents."""
    rec = FlightRecorder(capacity=8, bundle_dir=str(tmp_path),
                         keep_incidents=2)
    rec.record("sched_turn", seq=1)
    rec.dump("stall: wedged")
    rec.record("heartbeat")
    rec.dump("periodic")                   # overwrites BUNDLE_NAME...
    latest = _load_bundle(os.path.join(str(tmp_path), BUNDLE_NAME))
    assert latest["reason"] == "periodic"
    incidents = sorted(f for f in os.listdir(str(tmp_path))
                       if f.startswith("flight_incident_"))
    assert len(incidents) == 1             # ...but the stall survives
    doc = _load_bundle(os.path.join(str(tmp_path), incidents[0]))
    assert doc["reason"] == "stall: wedged"
    # pruning: only the newest keep_incidents incident files remain
    for i in range(4):
        rec.dump(f"crash: boom {i}")
    incidents = [f for f in os.listdir(str(tmp_path))
                 if f.startswith("flight_incident_")]
    assert len(incidents) == 2


def test_watchdog_ignores_foreign_beats(tmp_path):
    """Owner-token scoping: a healthy component's beats must not mask
    another component's stalled armed region."""
    rec = FlightRecorder(capacity=8, bundle_dir=str(tmp_path))
    wd = Watchdog(rec, timeout_s=0.25, poll_s=0.05)
    try:
        stale = wd.arm("first region")
        owner = wd.arm("serving run loop")   # takes ownership
        deadline = time.time() + 5.0
        bundle = os.path.join(str(tmp_path), BUNDLE_NAME)
        while time.time() < deadline and not os.path.exists(bundle):
            wd.beat(stale)                   # foreign beats: ignored
            time.sleep(0.02)
        assert os.path.exists(bundle), \
            "foreign beats masked the owner's stall"
        assert "serving run loop" in _load_bundle(bundle)["reason"]
        wd.disarm(stale)                     # foreign disarm: ignored
        assert wd._armed.is_set()
        wd.disarm(owner)
        assert not wd._armed.is_set()
    finally:
        wd.stop()


def test_pre_install_arm_token_is_inert(tmp_path):
    """A component that armed while NO watchdog was installed holds an
    inert token; if a watchdog appears and another component arms it,
    the first component's beats/disarms must read as foreign — a None
    fallthrough would mask (or tear down) the real armed region."""
    stale = fr.arm("armed before any watchdog existed")
    assert stale is not None
    rec = FlightRecorder(capacity=8, bundle_dir=str(tmp_path))
    wd = Watchdog(rec, timeout_s=0.25, poll_s=0.05)
    try:
        wd.arm("serving run loop")
        deadline = time.time() + 5.0
        bundle = os.path.join(str(tmp_path), BUNDLE_NAME)
        while time.time() < deadline and not os.path.exists(bundle):
            wd.beat(stale)                   # inert: must not mask
            wd.disarm(stale)                 # inert: must not disarm
            time.sleep(0.02)
        assert os.path.exists(bundle), \
            "pre-install token masked the real region's stall"
        assert "serving run loop" in _load_bundle(bundle)["reason"]
        assert wd._armed.is_set()
    finally:
        wd.stop()


def test_reinstall_rebinds_live_watchdog_recorder(tmp_path):
    """install() without a watchdog arg must point an already-running
    watchdog at the NEW recorder — a stall dump snapshotting the old,
    no-longer-fed ring would be a post-mortem missing its events."""
    fr.install(capacity=8, bundle_dir=str(tmp_path / "old"),
               watchdog_timeout_s=30.0)
    wd = fr.get_watchdog()
    rec2 = fr.install(capacity=8, bundle_dir=str(tmp_path / "new"))
    assert wd is fr.get_watchdog() and wd.recorder is rec2


@pytest.mark.fault
def test_dump_fault_never_leaves_torn_bundle(tmp_path):
    """ENOSPC mid-dump: the previous complete bundle survives intact,
    no .tmp litter, and a retry wins — the bundle on disk is ALWAYS a
    complete JSON document."""
    rec = FlightRecorder(capacity=16, bundle_dir=str(tmp_path))
    rec.record("turn", i=1)
    p = rec.dump("first")
    rec.record("turn", i=2)
    with FaultInjector() as fi:
        fi.fail_write(BUNDLE_NAME, errno_=errno.ENOSPC)
        with pytest.raises(OSError):
            rec.dump("second")
    doc = _load_bundle(p)                    # old bundle intact
    assert doc["reason"] == "first"
    assert not os.path.exists(p + ".tmp")
    rec.dump("third")
    assert _load_bundle(p)["reason"] == "third"


# ---- watchdog / stall -----------------------------------------------------

@pytest.mark.fault
def test_watchdog_dumps_on_no_progress(tmp_path):
    """The stall scenario: an armed region stops beating (here: a
    FaultInjector pause wedges the 'scheduler' thread on a read) and
    the watchdog dumps a bundle whose thread stacks show the wedge."""
    rec = fr.install(capacity=32, bundle_dir=str(tmp_path))
    wd = Watchdog(rec, timeout_s=0.3, poll_s=0.05)
    try:
        trigger = tmp_path / "wedge.bin"
        trigger.write_bytes(b"x" * 16)
        fi = FaultInjector().install()
        try:
            fi.pause("wedge.bin", op="open",
                     marker=str(tmp_path / "wedged"))

            def stuck_scheduler():
                fr.record_event("sched_turn", seq=1)
                open(str(trigger), "rb")     # pauses forever

            t = threading.Thread(target=stuck_scheduler,
                                 name="stuck-scheduler", daemon=True)
            wd.arm("serving run loop")
            t.start()
            deadline = time.time() + 10.0
            bundle = os.path.join(str(tmp_path), BUNDLE_NAME)
            while time.time() < deadline and not os.path.exists(bundle):
                time.sleep(0.05)
            assert os.path.exists(bundle), "watchdog never dumped"
            doc = _load_bundle(bundle)
            assert "stall" in doc["reason"]
            assert "serving run loop" in doc["reason"]
            assert any(e["kind"] == "sched_turn" for e in doc["events"])
            assert any("stuck-scheduler" in k for k in doc["threads"])
            assert wd.stall_dumps == 1
        finally:
            fi.uninstall()
    finally:
        wd.stop()


def test_watchdog_does_not_dump_while_beating(tmp_path):
    rec = FlightRecorder(capacity=8, bundle_dir=str(tmp_path))
    wd = Watchdog(rec, timeout_s=0.3, poll_s=0.05)
    try:
        wd.arm("busy loop")
        for _ in range(10):
            wd.beat()
            time.sleep(0.05)
        wd.disarm()
        time.sleep(0.5)                      # disarmed: gap is fine
        assert not os.path.exists(
            os.path.join(str(tmp_path), BUNDLE_NAME))
        assert wd.stall_dumps == 0
    finally:
        wd.stop()


def test_engine_stall_raises_and_dumps(tmp_path):
    """The serving engine's stall guard dumps the bundle before
    raising: the pool-exhaustion post-mortem is an artifact, not just
    an exception string."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 1
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    # audit=False: this test DELIBERATELY corrupts page accounting to
    # reach the stall diagnostic; the audit would (correctly) fail
    # first otherwise (test_serving_reliability pins that behavior)
    eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=8, greedy=True,
                                   audit=False)
    eng.add_request(np.arange(5, dtype=np.int32), 4)
    eng._free_pages.clear()
    fr.install(capacity=32, bundle_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="stalled"):
        eng.run()
    doc = _load_bundle(os.path.join(str(tmp_path), BUNDLE_NAME))
    assert "stalled" in doc["reason"]
    assert any(e["kind"] == "serving_stall" for e in doc["events"])


# ---- crash hook -----------------------------------------------------------

def test_crash_hook_dumps_on_uncaught_exception(tmp_path):
    rec = fr.install(capacity=8, bundle_dir=str(tmp_path))
    rec.record("turn", i=1)
    fr.install_crash_hook()
    prev = sys.excepthook
    try:
        try:
            raise ValueError("boom in turn 1")
        except ValueError:
            ei = sys.exc_info()
        sys.excepthook(*ei)                 # what the interpreter does
    finally:
        sys.excepthook = prev
    doc = _load_bundle(os.path.join(str(tmp_path), BUNDLE_NAME))
    assert doc["reason"] == "crash: ValueError: boom in turn 1"


# ---- the SIGKILL-shaped acceptance scenarios (subprocess) -----------------

_CRASH_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu.profiler import flight_recorder as fr, metrics
from paddle_tpu.testing import FaultInjector

bundle_dir = sys.argv[1]
# persist_every=1: every record refreshes the on-disk bundle, so death
# at ANY instant leaves a complete recent bundle
rec = fr.install(capacity=64, bundle_dir=bundle_dir, persist_every=1)
metrics.get_registry().counter("obs/ring_events")  # snapshot non-empty
for i in range(10):
    fr.record_event("sched_turn", seq=i, mode="child")
fi = FaultInjector().install()
fi.crash("trigger.bin", op="open")        # os._exit(41): SIGKILL-shaped
fr.record_event("checkpoint_phase", phase="stage")
open(os.path.join(bundle_dir, "trigger.bin"), "w")   # dies HERE
fr.record_event("never", seq=-1)          # unreachable
print("NOT REACHED")
"""

_SIGKILL_CHILD = """
import os, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu.profiler import flight_recorder as fr

bundle_dir, marker = sys.argv[1], sys.argv[2]
rec = fr.install(capacity=64, bundle_dir=bundle_dir, persist_every=1)
for i in range(5):
    fr.record_event("sched_turn", seq=i, mode="sigkill_child")
open(marker, "w").write("ready")          # parent SIGKILLs after this
while True:
    time.sleep(0.2)
    fr.record_event("heartbeat")
"""


@pytest.mark.fault
def test_faultinjector_crash_leaves_complete_bundle(tmp_path):
    """Acceptance: an abrupt crash (FaultInjector os._exit(41) — no
    atexit, no flush, indistinguishable from SIGKILL) at an exact
    checkpoint-phase op leaves a complete, parseable bundle from the
    periodic persistence, including the phase event recorded moments
    before death."""
    script = tmp_path / "child.py"
    script.write_text(_CRASH_CHILD.format(repo=REPO))
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert proc.returncode == 41, proc.stderr
    assert "NOT REACHED" not in proc.stdout
    doc = _load_bundle(os.path.join(str(tmp_path), BUNDLE_NAME))
    kinds = [e["kind"] for e in doc["events"]]
    assert "checkpoint_phase" in kinds     # the pre-death breadcrumb
    assert "never" not in kinds
    assert sum(1 for k in kinds if k == "sched_turn") == 10
    assert doc["metrics"]["obs/ring_events"] >= 10
    assert doc["threads"]                  # stacks captured at persist


@pytest.mark.fault
@pytest.mark.slow
def test_real_sigkill_leaves_complete_bundle(tmp_path):
    """Acceptance (breadth): a REAL SIGKILL — no signal handler runs —
    still leaves the last periodically-persisted bundle, complete and
    parseable."""
    script = tmp_path / "child.py"
    marker = tmp_path / "ready"
    script.write_text(_SIGKILL_CHILD.format(repo=REPO))
    proc = subprocess.Popen(
        [sys.executable, str(script), str(tmp_path), str(marker)],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    try:
        deadline = time.time() + 300
        while time.time() < deadline and not marker.exists():
            time.sleep(0.1)
        assert marker.exists(), "child never became ready"
        time.sleep(0.5)                    # let a heartbeat persist
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    doc = _load_bundle(os.path.join(str(tmp_path), BUNDLE_NAME))
    kinds = [e["kind"] for e in doc["events"]]
    assert "sched_turn" in kinds
