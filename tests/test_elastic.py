"""Elastic launch: fault detection, heartbeat watchdog, checkpoint-restart
(SURVEY.md §5; test pattern = reference's subprocess-kill simulation).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import (
    ElasticManager, ElasticStatus, latest_checkpoint, checkpoint_step,
    latest_valid_checkpoint, start_heartbeat, stop_heartbeat)

LAUNCH = [sys.executable, "-m", "paddle_tpu.distributed.launch"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=os.path.dirname(os.path.dirname(
               os.path.abspath(__file__))))


# --------------------------------------------------------------------------
# manager unit behavior
# --------------------------------------------------------------------------

def test_heartbeat_and_watch(tmp_path):
    d = str(tmp_path)
    # the timeout that must NOT fire is 600 beat intervals (a loaded
    # machine may starve a beat thread for a second, not for thirty);
    # the gap that must fire is made, not slept through
    mgr = ElasticManager(2, directory=d, timeout=30.0)
    status, missing = mgr.watch()
    assert status is ElasticStatus.INCOMPLETE and missing == [0, 1]
    start_heartbeat(0, directory=d, interval=0.05)
    status, missing = mgr.watch()
    assert status is ElasticStatus.INCOMPLETE and missing == [1]
    start_heartbeat(1, directory=d, interval=0.05)  # replaces thread 0...
    assert mgr.wait_all_registered(timeout=5.0)
    status, stale = mgr.watch()
    assert status is ElasticStatus.HEALTHY
    # rank 0's thread was replaced by rank 1's, so nothing refreshes its
    # beat: age it past the timeout, then see rank 1 beat twice more (a
    # rank-0 thread still alive would have beaten in that time too)
    old = time.time() - 60.0
    os.utime(os.path.join(d, "heartbeat.0"), (old, old))
    beat1 = os.path.join(d, "heartbeat.1")
    seen, last, give_up = 0, os.path.getmtime(beat1), time.time() + 20.0
    while seen < 2 and time.time() < give_up:
        time.sleep(0.01)
        now = os.path.getmtime(beat1)
        seen, last = seen + (now != last), now
    assert seen == 2
    status, stale = mgr.watch()
    assert status is ElasticStatus.STALE and stale == [0]
    stop_heartbeat()
    mgr.reset()
    assert mgr.watch()[0] is ElasticStatus.INCOMPLETE


def test_heartbeat_store_backend():
    from paddle_tpu.native import TCPStore
    store = TCPStore("127.0.0.1", 29877, is_master=True, world_size=1)
    try:
        mgr = ElasticManager(1, store=store, timeout=5.0)
        assert mgr.watch()[0] is ElasticStatus.INCOMPLETE
        from paddle_tpu.distributed.fleet.elastic.manager import _beat_once
        _beat_once(0, store=store)
        assert mgr.watch()[0] is ElasticStatus.HEALTHY
        mgr.reset()
        assert mgr.watch()[0] is ElasticStatus.INCOMPLETE
    finally:
        store.close()


def test_watch_ignores_exited_ranks(tmp_path):
    """A rank that exited cleanly stops heartbeating but must not be
    treated as stale (launcher passes it in ignore=)."""
    d = str(tmp_path)
    mgr = ElasticManager(2, directory=d, timeout=0.3)
    from paddle_tpu.distributed.fleet.elastic.manager import _beat_once
    _beat_once(0, directory=d)
    _beat_once(1, directory=d)
    time.sleep(0.5)
    _beat_once(1, directory=d)  # rank 1 still alive; rank 0 exited
    assert mgr.watch()[0] is ElasticStatus.STALE
    status, bad = mgr.watch(ignore={0})
    assert status is ElasticStatus.HEALTHY, bad


def test_start_heartbeat_rank_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_ELASTIC_HEARTBEAT_RANK", "3")
    monkeypatch.setenv("PADDLE_ELASTIC_HEARTBEAT_DIR", str(tmp_path))
    assert start_heartbeat(interval=0.2)
    try:
        assert os.path.exists(tmp_path / "heartbeat.3")
    finally:
        stop_heartbeat()


def test_latest_checkpoint(tmp_path):
    assert latest_checkpoint(str(tmp_path / "nope")) is None
    for s in (10, 200, 30):
        os.makedirs(tmp_path / f"step_{s}")
    os.makedirs(tmp_path / "step_999.tmp")  # in-progress: ignored
    os.makedirs(tmp_path / "step_998.tmp-abc12")  # staging: ignored
    os.makedirs(tmp_path / "unrelated")
    best = latest_checkpoint(str(tmp_path))
    assert os.path.basename(best) == "step_200"
    assert checkpoint_step(best) == 200
    assert checkpoint_step("/x/unrelated") == -1


def test_latest_valid_checkpoint_skips_torn_saves(tmp_path):
    """Elastic restart must resume from the last COMMITTED step:
    name-based discovery would hand back the torn step_20, validated
    discovery skips it."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as ckpt

    sd = {"w": paddle.to_tensor(np.ones(4, np.float32))}
    ckpt.save_state_dict(sd, str(tmp_path / "step_10"))
    ckpt.save_state_dict(sd, str(tmp_path / "step_20"))
    os.remove(tmp_path / "step_20" / "COMMITTED")  # torn by a crash
    os.makedirs(tmp_path / "step_30.tmp-dead")     # mid-save staging
    assert os.path.basename(
        latest_checkpoint(str(tmp_path))) == "step_20"
    best = latest_valid_checkpoint(str(tmp_path))
    assert os.path.basename(best) == "step_10"
    assert latest_valid_checkpoint(str(tmp_path / "nope")) is None


# --------------------------------------------------------------------------
# launcher integration (subprocess-kill simulation)
# --------------------------------------------------------------------------

CRASH_ONCE = """
import os, sys
marker = sys.argv[1]
if not os.path.exists(marker):
    open(marker, "w").write("x")
    sys.exit(1)          # first run: fail -> launcher must relaunch
open(marker + ".done", "w").write("ok")
"""


def test_launcher_restarts_after_crash(tmp_path):
    script = tmp_path / "crash_once.py"
    script.write_text(CRASH_ONCE)
    marker = str(tmp_path / "marker")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "2", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(marker + ".done")
    assert "relaunching (1/2)" in r.stderr


def test_launcher_exhausts_restarts(tmp_path):
    script = tmp_path / "always_fail.py"
    script.write_text("import sys; sys.exit(3)")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "1", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"), str(script)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "restarts exhausted" in r.stderr


HANG_ONCE = """
import os, sys, time
from paddle_tpu.distributed.fleet.elastic import start_heartbeat
marker = sys.argv[1]
rank = int(os.environ.get("PADDLE_ELASTIC_HEARTBEAT_RANK", "0"))
start_heartbeat(rank, interval=0.1)
if not os.path.exists(marker):
    open(marker, "w").write("x")
    from paddle_tpu.distributed.fleet.elastic import stop_heartbeat
    stop_heartbeat()     # heartbeat stops but the process hangs
    time.sleep(300)
open(marker + ".done", "w").write("ok")
"""


@pytest.mark.slow
def test_launcher_detects_hung_worker(tmp_path):
    """A worker that stops heartbeating (but does not exit) must be
    killed and relaunched — the watchdog path."""
    script = tmp_path / "hang_once.py"
    script.write_text(HANG_ONCE)
    marker = str(tmp_path / "marker")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "1", "--elastic_timeout", "0",
                  "--heartbeat_timeout", "2.0",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert os.path.exists(marker + ".done")
    assert "stale heartbeats" in r.stderr


RESUME_PROBE = """
import os, sys
with open(sys.argv[1], "w") as f:
    f.write(os.environ.get("PADDLE_RESUME_CHECKPOINT", "NONE") + "\\n")
    f.write(os.environ.get("PADDLE_RESUME_STEP", "NONE"))
"""


def test_launcher_exports_validated_resume_env(tmp_path):
    """--checkpoint_dir: each launch round points workers at the newest
    COMMITTED checkpoint via PADDLE_RESUME_CHECKPOINT, skipping a save
    torn by the previous crash."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as ckpt

    root = tmp_path / "ckpts"
    sd = {"w": paddle.to_tensor(np.ones(4, np.float32))}
    ckpt.save_state_dict(sd, str(root / "step_7"))
    ckpt.save_state_dict(sd, str(root / "step_9"))
    os.remove(root / "step_9" / "COMMITTED")  # torn: must be skipped

    script = tmp_path / "probe.py"
    script.write_text(RESUME_PROBE)
    out = tmp_path / "probe.out"
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--elastic_timeout", "0",
                  "--checkpoint_dir", str(root),
                  "--log_dir", str(tmp_path / "log"),
                  str(script), str(out)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "resuming from" in r.stdout
    got_path, got_step = out.read_text().splitlines()
    assert os.path.basename(got_path) == "step_7"
    assert got_step == "7"


def test_launcher_resume_env_absent_without_checkpoints(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(RESUME_PROBE)
    out = tmp_path / "probe.out"
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--elastic_timeout", "0",
                  "--checkpoint_dir", str(tmp_path / "empty"),
                  "--log_dir", str(tmp_path / "log"),
                  str(script), str(out)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert out.read_text().splitlines()[0] == "NONE"


# --------------------------------------------------------------------------
# preemption + elastic shrink (the fault-tolerance launcher paths)
# --------------------------------------------------------------------------

PREEMPT_ONCE = """
import os, sys
from paddle_tpu.distributed.fleet.elastic.preempt import \\
    PREEMPTED_EXIT_CODE
marker = sys.argv[1]
if not os.path.exists(marker):
    open(marker, "w").write("x")
    sys.exit(PREEMPTED_EXIT_CODE)   # clean preemption, not a crash
open(marker + ".done", "w").write(
    os.environ.get("PADDLE_RESTART_ROUND", "?"))
"""


def test_preempted_exit_does_not_burn_crash_budget(tmp_path):
    """A worker exiting with PREEMPTED_EXIT_CODE (emergency checkpoint
    committed) relaunches on the preempt budget — --max_restarts 0
    must NOT stop it, and the round counter reaches the workers."""
    script = tmp_path / "preempt_once.py"
    script.write_text(PREEMPT_ONCE)
    marker = str(tmp_path / "marker")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "clean preemption" in r.stderr
    assert "preempt 1/16" in r.stderr
    assert open(marker + ".done").read() == "1"


def test_preempt_restart_budget_exhausted(tmp_path):
    """Preemptions have their own bound: a worker that is preempted
    every round must eventually fail loudly, not tight-loop."""
    script = tmp_path / "always_preempt.py"
    script.write_text(
        "import sys\n"
        "from paddle_tpu.distributed.fleet.elastic.preempt import \\\n"
        "    PREEMPTED_EXIT_CODE\n"
        "sys.exit(PREEMPTED_EXIT_CODE)\n")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--max_preempt_restarts", "2",
                  "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"), str(script)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "preempt restarts exhausted" in r.stderr


UNCAUGHT_PREEMPTED = """
import sys
from paddle_tpu.distributed.fleet.elastic import (Preempted,
                                                  PreemptionGuard)
import os
marker = sys.argv[1]
if not os.path.exists(marker):
    open(marker, "w").write("x")
    PreemptionGuard().install()      # chains the Preempted excepthook
    raise Preempted("preempted mid-run", checkpoint="/ck", epoch=1,
                    step=2)          # NOT caught by the trainer
open(marker + ".done", "w").write("ok")
"""


def test_uncaught_preempted_exits_with_preempt_code(tmp_path):
    """The documented contract without trainer boilerplate: letting
    Preempted propagate must exit PREEMPTED_EXIT_CODE (launcher books
    a clean preemption), not a generic 1 (a crash)."""
    script = tmp_path / "uncaught.py"
    script.write_text(UNCAUGHT_PREEMPTED)
    marker = str(tmp_path / "marker")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "clean preemption" in r.stderr
    assert os.path.exists(marker + ".done")


PARTIAL_PREEMPT = """
import os, sys, time
from paddle_tpu.distributed.fleet.elastic.preempt import \\
    PREEMPTED_EXIT_CODE
marker, out = sys.argv[1], sys.argv[2]
rank = int(os.environ["PADDLE_TRAINER_ID"])
if not os.path.exists(marker):
    if rank == 0:
        open(marker, "w").write("x")
        sys.exit(PREEMPTED_EXIT_CODE)   # rank 0 alone is preempted
    time.sleep(300)   # rank 1 would block at its next collective
with open(out + f".{rank}", "w") as f:
    f.write("ok")
"""


def test_partial_preemption_ends_the_round(tmp_path):
    """One rank preempted while its peer keeps running: the round must
    end (the peer would block forever at its next collective, still
    heartbeating) — survivors are terminated with the grace window and
    the job relaunches as a preemption."""
    script = tmp_path / "partial.py"
    script.write_text(PARTIAL_PREEMPT)
    marker = str(tmp_path / "marker")
    out = str(tmp_path / "out")
    r = subprocess.run(
        LAUNCH + ["--nproc_per_node", "2", "--max_restarts", "0",
                  "--elastic_timeout", "0", "--grace", "5",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker, out],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "clean preemption" in r.stderr
    assert os.path.exists(out + ".0") and os.path.exists(out + ".1")


def test_min_nproc_ignored_multinode(tmp_path):
    """Per-launcher shrinking is uncoordinated across nodes: with
    --nnodes > 1 it must be refused loudly, not silently misaddress
    global ranks."""
    script = tmp_path / "ok.py"
    script.write_text("pass\n")
    r = subprocess.run(
        LAUNCH + ["--nnodes", "2", "--rank", "0",
                  "--min_nproc_per_node", "1", "--max_restarts", "0",
                  "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"), str(script)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "single-node only" in r.stderr


SHRINK_PROBE = """
import os, sys, time
world = int(os.environ["PADDLE_TRAINERS_NUM"])
rank = int(os.environ["PADDLE_TRAINER_ID"])
out = sys.argv[1]
if world == 2:
    if rank == 1:
        sys.exit(9)          # rank 1's host dies
    time.sleep(60)           # survivor keeps running until terminated
with open(out, "w") as f:    # reduced world completes the job
    f.write(f"world={world}")
"""


def test_run_round_counts_all_simultaneous_failures(tmp_path):
    """A shrinking relaunch must see EVERY rank lost in the round, not
    just the first one scanned — undercounting respawns onto missing
    capacity and burns the restart budget crashing again."""
    import argparse
    from paddle_tpu.distributed.launch.main import _run_round

    class FakeProc:
        def __init__(self, ret):
            self.ret = ret

        def poll(self):
            return self.ret

    class FakeLog:
        def flush(self):
            pass

        def close(self):
            pass

    args = argparse.Namespace(log_dir=str(tmp_path / "log"),
                              heartbeat_timeout=0.0)
    procs = [(FakeProc(9), FakeLog()), (FakeProc(None), FakeLog()),
             (FakeProc(7), FakeLog())]
    outcome, bad = _run_round(procs, args, None, {"flag": False})
    assert outcome == "failed"
    assert bad == [0, 2]


def test_relaunch_shrinks_to_surviving_world(tmp_path):
    """--min_nproc_per_node: a crashed rank's slot is treated as lost
    capacity; the next round respawns with the surviving world size
    and the job completes on the reduced fleet."""
    script = tmp_path / "shrink_probe.py"
    script.write_text(SHRINK_PROBE)
    out = str(tmp_path / "out")
    r = subprocess.run(
        LAUNCH + ["--nproc_per_node", "2", "--min_nproc_per_node", "1",
                  "--max_restarts", "1", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), out],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "shrinking nproc_per_node 2 -> 1" in r.stderr
    assert open(out).read() == "world=1"


TERM_FORWARD = """
import os, signal, sys, time
from paddle_tpu.distributed.fleet.elastic.preempt import \\
    PreemptionGuard, PREEMPTED_EXIT_CODE
marker = sys.argv[1]
guard = PreemptionGuard().install()
open(marker, "w").write("started")
for _ in range(600):
    if guard.requested():
        open(marker + ".term", "w").write("got SIGTERM")
        sys.exit(PREEMPTED_EXIT_CODE)
    time.sleep(0.1)
sys.exit(3)
"""


def test_launcher_forwards_sigterm_with_grace(tmp_path):
    """Preempting the LAUNCHER must fan out to workers: each gets the
    grace window to emergency-checkpoint, then the launcher exits with
    the preempted code instead of relaunching."""
    script = tmp_path / "term_forward.py"
    script.write_text(TERM_FORWARD)
    marker = str(tmp_path / "marker")
    proc = subprocess.Popen(
        LAUNCH + ["--max_restarts", "3", "--elastic_timeout", "0",
                  "--grace", "20",
                  "--log_dir", str(tmp_path / "log"),
                  str(script), marker],
        env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        deadline = time.time() + 60
        while not os.path.exists(marker):
            assert proc.poll() is None, proc.communicate()
            assert time.time() < deadline, "worker never started"
            time.sleep(0.05)
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    from paddle_tpu.distributed.fleet.elastic import PREEMPTED_EXIT_CODE
    assert proc.returncode == PREEMPTED_EXIT_CODE, err
    assert "forwarding to workers" in err.replace("\n", " ")
    assert os.path.exists(marker + ".term"), \
        "worker never observed the forwarded SIGTERM"


def test_launcher_dumps_failed_worker_log(tmp_path):
    """Observability: the failing rank's log tail must surface on the
    launcher's stderr (no hunting for workerlog files)."""
    script = tmp_path / "noisy_fail.py"
    script.write_text(
        "print('useful diagnostic line A')\n"
        "print('useful diagnostic line B')\n"
        "raise RuntimeError('worker exploded: cuda_oom_equivalent')\n")
    r = subprocess.run(
        LAUNCH + ["--max_restarts", "0", "--elastic_timeout", "0",
                  "--log_dir", str(tmp_path / "log"), str(script)],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "exited rc=1" in r.stderr
    assert "worker exploded: cuda_oom_equivalent" in r.stderr
    assert "[rank 0]" in r.stderr
    # the per-rank log file itself also exists
    assert (tmp_path / "log" / "workerlog.0").exists()
