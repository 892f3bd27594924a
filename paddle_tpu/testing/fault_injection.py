"""Deterministic filesystem fault injection.

Robustness claims ("a save killed mid-write never yields a loadable
checkpoint") are only worth anything if a test can *produce* the fault
on demand. ``FaultInjector`` patches ``builtins.open`` (which numpy's
``np.save``/``np.load`` also route through) plus ``os.replace`` /
``os.rename``, and fires registered :class:`FaultPlan`\\ s when an
operation touches a matching path:

- ``action="raise"`` — raise ``OSError(errno)`` (ENOSPC, EIO, ...),
  optionally after ``after_bytes`` of a write landed (a partial write
  followed by the error, the torn-write shape).
- ``action="truncate"`` — write only ``after_bytes`` bytes but report
  full success: the silent short write that only checksums catch.
- ``action="crash"`` — ``os._exit(41)``: abrupt process death at an
  exact operation, indistinguishable from SIGKILL to an observer (no
  atexit, no buffer flush, no cleanup).
- ``action="pause"`` — touch ``marker`` then sleep forever, so a
  parent test process can deliver a *real* SIGKILL at a known point
  (e.g. between shard write and commit).
- ``action="sigterm"`` — deliver a real SIGTERM to this process at the
  matching operation, then let the operation PROCEED: the preemption
  shape (the signal is asynchronous; work continues until the loop's
  next step boundary polls its ``PreemptionGuard``). The
  :meth:`FaultInjector.preempt` helper arms it.

Beyond filesystem ops, **call-site plans** (:meth:`FaultInjector
.fail_call` / :meth:`crash_call`) patch a dotted callable — e.g. the
optimizer step or a collective — and fire after ``after_calls``
invocations. That is how a chaos test kills a worker *mid-step* or
*mid-collective* at a chosen, randomizable point::

    fi.crash_call("paddle_tpu.distributed.communication.all_reduce")
    fi.crash_call("paddle_tpu.optimizer.optimizer.Optimizer.step",
                  after_calls=k)     # SIGKILL-equivalent at step k

Plans match by substring of the path and fire deterministically: each
plan fires at most ``times`` times, in registration order. Use as a
context manager so ``builtins.open`` is always restored::

    with FaultInjector() as fi:
        fi.fail("w.r0.s0.npy", op="write", errno_=errno.ENOSPC)
        save_state_dict(sd, path)     # first write ENOSPCs, retry wins
        assert fi.fires() == 1
"""

from __future__ import annotations

import builtins
import errno as _errno
import importlib
import os
import signal as _signal
import threading
import time

__all__ = ["FaultInjector", "FaultPlan"]


class FaultPlan:
    """One armed fault: fires when ``op`` touches a path containing
    ``match``, at most ``times`` times."""

    def __init__(self, match, op="write", errno_=_errno.EIO, times=1,
                 after_bytes=0, action="raise", marker=None,
                 after_calls=0):
        if op not in ("open", "write", "read", "rename", "call"):
            raise ValueError(f"unknown fault op {op!r}")
        if action not in ("raise", "truncate", "crash", "pause",
                          "sigterm"):
            raise ValueError(f"unknown fault action {action!r}")
        self.match = match
        self.op = op
        self.errno = errno_
        self.times = int(times)
        self.after_bytes = int(after_bytes)
        self.after_calls = int(after_calls)
        self.action = action
        self.marker = marker
        self.fired = 0
        self.calls = 0

    def __repr__(self):
        return (f"FaultPlan({self.match!r}, op={self.op}, "
                f"action={self.action}, fired={self.fired}/{self.times})")


class _FaultFile:
    """File proxy that consults the injector on write()/read()."""

    def __init__(self, f, path, injector):
        self._f = f
        self._path = path
        self._inj = injector
        self._written = 0
        self._truncated = False

    def write(self, data):
        if self._truncated:
            return len(data)  # silently dropped tail of a short write
        plan = self._inj._take(self._path, "write",
                               pending=self._written + len(data))
        if plan is not None:
            if plan.action == "sigterm":
                # preemption notice mid-write: signal, then the write
                # itself PROCEEDS untouched (the signal is async)
                self._inj._act(plan, self._path)
            else:
                keep = max(0, plan.after_bytes - self._written)
                if keep:
                    self._f.write(data[:keep])
                    self._written += keep
                if plan.action == "truncate":
                    self._truncated = True
                    return len(data)  # lie: report full success
                self._inj._act(plan, self._path)  # raise/crash/pause
        n = self._f.write(data)
        self._written += len(data)
        return n

    def read(self, *args):
        plan = self._inj._take(self._path, "read")
        if plan is not None:
            self._inj._act(plan, self._path)
        return self._f.read(*args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()
        return False

    def __iter__(self):
        return iter(self._f)

    def __getattr__(self, name):
        return getattr(self._f, name)


class FaultInjector:
    """Installable fault plan registry (see module docstring)."""

    def __init__(self):
        self.plans = []
        self._lock = threading.Lock()
        self._installed = False
        self._real_open = None
        self._real_replace = None
        self._real_rename = None
        self._call_targets = []   # (dotted_name, plan) awaiting patch
        self._patched_calls = []  # (owner, attr, original)
        # serving-side plans need ARGUMENT access (which request rides
        # the harvested program, which slot is draining), so they carry
        # their own wrapper factory instead of the blind call patch
        self._custom_targets = []  # (dotted_name, plan, make_patched)
        # process-level plans (ISSUE 16)
        self._wire_hooks = []        # hooks awaiting install
        self._active_wire_hooks = []  # hooks currently registered
        self._paused_pids = set()    # SIGSTOP'd workers owed a SIGCONT

    # -- arming ------------------------------------------------------------

    def fail(self, match, op="write", errno_=_errno.EIO, times=1,
             after_bytes=0, action="raise", marker=None):
        plan = FaultPlan(match, op=op, errno_=errno_, times=times,
                         after_bytes=after_bytes, action=action,
                         marker=marker)
        self.plans.append(plan)
        return plan

    def fail_write(self, match, errno_=_errno.ENOSPC, times=1,
                   after_bytes=0):
        """Nth write to a matching path raises OSError(errno_) after
        ``after_bytes`` bytes actually landed (partial write)."""
        return self.fail(match, op="write", errno_=errno_, times=times,
                         after_bytes=after_bytes)

    def fail_read(self, match, errno_=_errno.EIO, times=1):
        return self.fail(match, op="read", errno_=errno_, times=times)

    def truncate_write(self, match, after_bytes):
        """Silent short write: only ``after_bytes`` land, success is
        reported — detectable only by size/checksum validation."""
        return self.fail(match, op="write", after_bytes=after_bytes,
                         action="truncate")

    def crash(self, match, op="open", after_bytes=0):
        """os._exit(41) when ``op`` touches a matching path."""
        return self.fail(match, op=op, action="crash",
                         after_bytes=after_bytes)

    def pause(self, match, op="open", marker=None):
        """Touch ``marker`` then sleep forever at the matching
        operation so the test harness can SIGKILL this process at an
        exact point."""
        return self.fail(match, op=op, action="pause", marker=marker)

    def preempt(self, match, op="open", times=1):
        """Deliver a real SIGTERM to this process when ``op`` touches a
        matching path, then let the operation proceed — the SIGTERM-
        with-grace-window preemption scenario: an installed
        ``PreemptionGuard`` records the signal and the training loop
        drains at its next step boundary."""
        return self.fail(match, op=op, action="sigterm", times=times)

    def fail_call(self, target, action="raise", errno_=_errno.EIO,
                  times=1, after_calls=0):
        """Arm a fault on a dotted CALLABLE instead of a file path:
        ``target`` names a module-level function or class method (e.g.
        ``"paddle_tpu.distributed.communication.all_reduce"``); the
        plan fires once more than ``after_calls`` invocations have
        happened, then the chosen action runs *before* the original
        callable — ``"crash"`` is a worker killed mid-collective /
        mid-step, ``"raise"`` an injected failure unwinding through
        it, ``"sigterm"`` a preemption notice landing inside it.
        Patched on :meth:`install`, restored on :meth:`uninstall`."""
        plan = FaultPlan(target, op="call", errno_=errno_, times=times,
                         action=action, after_calls=after_calls)
        self.plans.append(plan)
        self._call_targets.append((target, plan))
        if self._installed:
            self._patch_call(target, plan)
        return plan

    def crash_call(self, target, after_calls=0, times=1):
        """``os._exit(41)`` (SIGKILL-equivalent) inside the named
        callable — kill a worker mid-step / mid-collective at an
        exact, randomizable point."""
        return self.fail_call(target, action="crash", times=times,
                              after_calls=after_calls)

    def fires(self):
        """Total number of times any plan fired."""
        return sum(p.fired for p in self.plans)

    # -- serving-side plans (ISSUE 10) -------------------------------------
    # Chaos shapes for the continuous-batching engine: a poisoned
    # request, a slot that stops draining, a page-reclamation leak.
    # Each is a call plan on an engine method whose wrapper inspects
    # the call's arguments, so the fault is attributable (fires only
    # when the chosen request/slot is involved).

    _SERVING = "paddle_tpu.inference.serving.ContinuousBatchingEngine."

    def _custom(self, target, plan, make_patched):
        self._custom_targets.append((target, plan, make_patched))
        if self._installed:
            self._patch_custom(target, plan, make_patched)

    def _claim(self, plan):
        """Claim one firing of ``plan`` if it is still live."""
        with self._lock:
            if plan.fired >= plan.times:
                return False
            plan.fired += 1
            return True

    def poison_request(self, request_id, times=1):
        """Poison-request plan: harvesting a compiled serving step
        RAISES ``FloatingPointError`` (the NaN-sampler-output shape
        materializing at the packed fetch) whenever the chosen request
        rides the harvested program. The engine's containment boundary
        must quarantine the poison and recompute its co-scheduled
        innocents — never die."""
        plan = FaultPlan(f"poison_request:{request_id}", op="call",
                         action="raise", times=times)
        self.plans.append(plan)
        rid = int(request_id)
        injector = self

        def make(original, plan_):
            def patched(eng, rec, *a, **kw):
                snap = rec[1]   # the harvest record carries the
                                # slot->request snapshot at index 1
                if any(r is not None and r.request_id == rid
                       for r in snap) and injector._claim(plan_):
                    raise FloatingPointError(
                        f"fault injected: NaN sampler output "
                        f"(poison request {rid})")
                return original(eng, rec, *a, **kw)
            return patched

        self._custom(self._SERVING + "_harvest_step", plan, make)
        return plan

    def wedge_slot(self, slot, times=1):
        """Wedge-slot plan: the drain pass SKIPS the chosen slot for
        ``times`` passes — the stream sits finished-but-undrained,
        holding its pages (the stuck-slot shape the deadlock-break
        eviction and the EngineSupervisor exist for)."""
        plan = FaultPlan(f"wedge_slot:{slot}", op="call",
                         action="raise", times=times)
        self.plans.append(plan)
        slot_i = int(slot)
        injector = self

        def make(original, plan_):
            def patched(eng, *a, **kw):
                if not (slot_i < eng.num_slots
                        and eng.slot_req[slot_i] is not None
                        and injector._claim(plan_)):
                    return original(eng, *a, **kw)
                # emits-inflight makes the drain defer exactly this
                # slot, without touching any device state
                eng._emits_inflight[slot_i] += 1
                try:
                    return original(eng, *a, **kw)
                finally:
                    eng._emits_inflight[slot_i] -= 1
            return patched

        self._custom(self._SERVING + "_drain", plan, make)
        return plan

    # -- replica-level plans (ISSUE 11) ------------------------------------
    # Fleet chaos shapes: a replica that dies, one that wedges, one
    # that merely straggles. Each matches the engine's
    # ``_fleet_replica_id`` tag (set by ServingFleet — re-applied on
    # every supervised rebuild — or settable by hand on a bare engine),
    # so one plan targets exactly one replica of the shared class.

    def kill_replica(self, replica_id, times=1, after_steps=0):
        """Replica death, supervisor-visible: the chosen replica's
        ``step()`` raises ``RuntimeError`` BEFORE any scheduler work
        runs — the whole turn dies, exactly what a crashed worker
        looks like from the driver. The replica's EngineSupervisor
        salvages + restarts; once its budget is spent the fleet opens
        the circuit breaker and fails the queue over to siblings.
        ``after_steps`` counts only the chosen replica's steps."""
        plan = FaultPlan(f"kill_replica:{replica_id}", op="call",
                         action="raise", times=times,
                         after_calls=after_steps)
        self.plans.append(plan)
        rid = int(replica_id)
        injector = self

        def make(original, plan_):
            def patched(eng, *a, **kw):
                if getattr(eng, "_fleet_replica_id", None) == rid:
                    live = injector._take_call(plan_)
                    if live is not None:
                        raise RuntimeError(
                            f"fault injected: replica {rid} died "
                            f"mid-step")
                return original(eng, *a, **kw)
            return patched

        self._custom(self._SERVING + "step", plan, make)
        return plan

    def wedge_replica(self, replica_id, times=10_000):
        """Wedged replica: ``step()`` returns promptly having done
        NOTHING — the scheduler turn is skipped wholesale, so the
        replica still heartbeats (the step returns; liveness is fine)
        but never makes progress. Must be caught by the fleet's
        NO-PROGRESS health check, not the liveness check, and without
        tripping the engine's true-deadlock stall diagnostic (which
        lives only in ``run()``)."""
        plan = FaultPlan(f"wedge_replica:{replica_id}", op="call",
                         action="raise", times=times)
        self.plans.append(plan)
        rid = int(replica_id)
        injector = self

        def make(original, plan_):
            def patched(eng, *a, **kw):
                if getattr(eng, "_fleet_replica_id", None) == rid \
                        and injector._claim(plan_):
                    return []      # a turn that does nothing
                return original(eng, *a, **kw)
            return patched

        self._custom(self._SERVING + "step", plan, make)
        return plan

    def slow_replica(self, replica_id, delay_s=0.05, stride=4,
                     times=10_000):
        """Straggler replica: inflated step latency — every matching
        ``step()`` burns ``delay_s`` of wall clock, and only every
        ``stride``-th actually advances the scheduler (in the fleet's
        cooperative round-robin a slow worker completes fewer turns
        per unit time; this models that without threads). Progress
        continues — just slowly — so the no-progress health check must
        NOT fire; hedged dispatch is what this shape exercises."""
        plan = FaultPlan(f"slow_replica:{replica_id}", op="call",
                         action="raise", times=times)
        self.plans.append(plan)
        rid = int(replica_id)
        delay = float(delay_s)
        stride_n = max(1, int(stride))
        injector = self

        def make(original, plan_):
            def patched(eng, *a, **kw):
                if getattr(eng, "_fleet_replica_id", None) == rid \
                        and injector._claim(plan_):
                    time.sleep(delay)
                    if plan_.fired % stride_n:
                        return []  # the slice elapsed, no turn ran
                return original(eng, *a, **kw)
            return patched

        self._custom(self._SERVING + "step", plan, make)
        return plan

    def leak_pages(self, n=1, times=1):
        """Page-leak plan: the engine's page-reclamation path silently
        DROPS the first ``n`` pages it would have returned to the pool
        — the reclamation-bug shape the PADDLE_TPU_SERVING_AUDIT
        invariant exists to catch loudly."""
        plan = FaultPlan("leak_pages", op="call", action="raise",
                         times=times)
        self.plans.append(plan)
        n_drop = int(n)
        injector = self

        def make(original, plan_):
            def patched(eng, pages, *a, **kw):
                if pages and injector._claim(plan_):
                    pages = list(pages)[n_drop:]
                return original(eng, pages, *a, **kw)
            return patched

        self._custom(self._SERVING + "_release_pages", plan, make)
        return plan

    # -- process-level plans (ISSUE 16) ------------------------------------
    # Real-process fault shapes for ProcReplica workers: a worker
    # killed with an actual SIGKILL, one frozen with SIGSTOP, and a
    # lossy wire (dropped / delayed / corrupted frames) injected at
    # the parent transport's fault-hook seam. Matched by replica id
    # like the replica-level plans above.

    _PROC = "paddle_tpu.inference.proc_replica.ProcReplica."

    def kill_worker(self, replica_id, times=1, after_steps=0):
        """Real worker death: deliver an actual SIGKILL to the chosen
        replica's worker process right before a matching step RPC —
        the parent sees waitpid/EOF, salvages from its parent-side
        shadow, and respawns under the restart budget (past it, the
        breaker opens). ``after_steps`` counts only the chosen
        replica's step RPCs."""
        plan = FaultPlan(f"kill_worker:{replica_id}", op="call",
                         action="raise", times=times,
                         after_calls=after_steps)
        self.plans.append(plan)
        rid = int(replica_id)
        injector = self

        def make(original, plan_):
            def patched(rep, *a, **kw):
                if rep.id == rid:
                    live = injector._take_call(plan_)
                    if live is not None and rep.worker_pid:
                        try:
                            os.kill(rep.worker_pid, _signal.SIGKILL)
                        except (ProcessLookupError, OSError):
                            pass
                return original(rep, *a, **kw)
            return patched

        self._custom(self._PROC + "_step_rpc", plan, make)
        return plan

    def pause_worker(self, replica_id, times=1, after_steps=0):
        """Hung worker: SIGSTOP the chosen replica's worker process.
        Heartbeats stop but the process is NOT dead, so the parent
        must classify it as hung via heartbeat timeout (SIGTERM with
        grace, then SIGKILL; wedge ejection — never the breaker). Any
        pid still stopped gets a SIGCONT on :meth:`uninstall` so
        nothing outlives the test."""
        plan = FaultPlan(f"pause_worker:{replica_id}", op="call",
                         action="raise", times=times,
                         after_calls=after_steps)
        self.plans.append(plan)
        rid = int(replica_id)
        injector = self

        def make(original, plan_):
            def patched(rep, *a, **kw):
                if rep.id == rid:
                    live = injector._take_call(plan_)
                    if live is not None and rep.worker_pid:
                        try:
                            os.kill(rep.worker_pid, _signal.SIGSTOP)
                            injector._paused_pids.add(rep.worker_pid)
                        except (ProcessLookupError, OSError):
                            pass
                return original(rep, *a, **kw)
            return patched

        self._custom(self._PROC + "_step_rpc", plan, make)
        return plan

    def _add_wire_hook(self, hook):
        from paddle_tpu.inference import wire as _wire
        _wire.add_fault_hook(hook)
        self._active_wire_hooks.append(hook)

    def _wire_plan(self, kind, replica_id, times, direction,
                   after_frames, act):
        if direction not in ("rx", "tx"):
            raise ValueError(f"unknown wire direction {direction!r}")
        plan = FaultPlan(f"{kind}:{replica_id}", op="call",
                         action="raise", times=times,
                         after_calls=after_frames)
        self.plans.append(plan)
        rid = int(replica_id)
        injector = self

        def hook(hook_rid, hook_dir, data):
            if hook_rid != rid or hook_dir != direction:
                return data
            live = injector._take_call(plan)
            if live is None:
                return data
            return act(data)

        self._wire_hooks.append(hook)
        if self._installed:
            self._add_wire_hook(hook)
        return plan

    def drop_frame(self, replica_id, times=1, direction="rx",
                   after_frames=0):
        """Lossy wire: the matching transport chunk vanishes — a sent
        frame never leaves (``direction="tx"``) or a received chunk
        never arrives (``"rx"``). The RPC layer's deadline + bounded
        retransmit must absorb it; the worker's reply cache keeps the
        retransmit exactly-once."""
        return self._wire_plan("drop_frame", replica_id, times,
                               direction, after_frames,
                               lambda data: None)

    def delay_frame(self, replica_id, delay_s=0.05, times=1,
                    direction="rx", after_frames=0):
        """Slow wire: the matching chunk is held for ``delay_s``
        before delivery — exercises the RPC deadline/backoff path
        without losing any bytes."""
        delay = float(delay_s)

        def act(data):
            time.sleep(delay)
            return data

        return self._wire_plan("delay_frame", replica_id, times,
                               direction, after_frames, act)

    def corrupt_frame(self, replica_id, times=1, direction="rx",
                      after_frames=0):
        """Corrupt wire: one byte in the middle of the matching chunk
        is bit-flipped — the decoder must surface a typed
        ``WireError`` (bad magic / CRC mismatch), resync, and the RPC
        layer must retransmit; never a hang, never a half-applied
        message."""
        def act(data):
            if not data:
                return data
            buf = bytearray(data)
            buf[len(buf) // 2] ^= 0xFF
            return bytes(buf)

        return self._wire_plan("corrupt_frame", replica_id, times,
                               direction, after_frames, act)

    # -- plan matching / actions -------------------------------------------

    def _take(self, path, op, pending=None):
        """Claim the first live plan matching (path, op); for writes,
        only once the byte threshold is actually reached."""
        with self._lock:
            for plan in self.plans:
                if plan.fired >= plan.times or plan.op != op:
                    continue
                if plan.match not in path:
                    continue
                if (op == "write" and pending is not None
                        and pending <= plan.after_bytes):
                    continue  # threshold not reached yet this write
                plan.fired += 1
                return plan
        return None

    def _act(self, plan, path):
        if plan.action == "crash":
            os._exit(41)
        if plan.action == "sigterm":
            # real signal to self; the caller PROCEEDS with the
            # operation — preemption is asynchronous by nature
            os.kill(os.getpid(), _signal.SIGTERM)
            return
        if plan.action == "pause":
            if plan.marker:
                with self._real_open(plan.marker, "w") as m:
                    m.write(path)
            while True:
                time.sleep(60)
        raise OSError(plan.errno,
                      f"fault injected ({plan.op} -> {plan.action})", path)

    def _take_call(self, plan):
        """Claim a call plan: fires once the invocation count passes
        ``after_calls`` (counted across install lifetime)."""
        with self._lock:
            plan.calls += 1
            if plan.fired >= plan.times:
                return None
            if plan.calls <= plan.after_calls:
                return None
            plan.fired += 1
            return plan

    # -- patching ----------------------------------------------------------

    @staticmethod
    def _resolve_owner(dotted):
        """(owner, attr) for a dotted target: the longest importable
        module prefix, then a getattr chain (supports Class.method)."""
        parts = dotted.split(".")
        mod = None
        rest = None
        for i in range(len(parts) - 1, 0, -1):
            try:
                mod = importlib.import_module(".".join(parts[:i]))
                rest = parts[i:]
                break
            except ImportError:
                continue
        if mod is None or not rest:
            raise ValueError(f"cannot resolve fault target {dotted!r}")
        owner = mod
        for p in rest[:-1]:
            owner = getattr(owner, p)
        if not hasattr(owner, rest[-1]):
            raise ValueError(
                f"fault target {dotted!r}: {owner!r} has no "
                f"attribute {rest[-1]!r}")
        return owner, rest[-1]

    def _patch_call(self, target, plan):
        injector = self

        def make(original, plan_):
            def patched(*a, **kw):
                live = injector._take_call(plan_)
                if live is not None:
                    injector._act(live, target)  # crash/raise/sigterm
                return original(*a, **kw)
            return patched

        self._patch_custom(target, plan, make)

    def _patch_custom(self, target, plan, make_patched):
        """The one patch/restore skeleton every call plan rides —
        blind plans (_patch_call) and argument-aware serving plans
        alike, so install/uninstall bookkeeping lives in one place."""
        owner, attr = self._resolve_owner(target)
        original = getattr(owner, attr)
        patched = make_patched(original, plan)
        patched.__name__ = getattr(original, "__name__", attr)
        setattr(owner, attr, patched)
        self._patched_calls.append((owner, attr, original))

    def _open(self, file, mode="r", *args, **kwargs):
        path = None
        if isinstance(file, (str, bytes, os.PathLike)):
            path = os.fsdecode(os.fspath(file))
        if path is not None:
            plan = self._take(path, "open")
            if plan is not None:
                self._act(plan, path)
        f = self._real_open(file, mode, *args, **kwargs)
        if path is not None and any(
                p.op in ("write", "read") and p.fired < p.times
                and p.match in path for p in self.plans):
            return _FaultFile(f, path, self)
        return f

    def _rename_like(self, real):
        def patched(src, dst, **kwargs):
            for p in (os.fspath(src), os.fspath(dst)):
                sp = os.fsdecode(p) if isinstance(p, bytes) else str(p)
                plan = self._take(sp, "rename")
                if plan is not None:
                    self._act(plan, sp)
            return real(src, dst, **kwargs)
        return patched

    def install(self):
        if self._installed:
            return self
        self._real_open = builtins.open
        self._real_replace = os.replace
        self._real_rename = os.rename
        builtins.open = self._open
        os.replace = self._rename_like(self._real_replace)
        os.rename = self._rename_like(self._real_rename)
        self._installed = True
        for target, plan in self._call_targets:
            self._patch_call(target, plan)
        for target, plan, make in self._custom_targets:
            self._patch_custom(target, plan, make)
        for hook in self._wire_hooks:
            if hook not in self._active_wire_hooks:
                self._add_wire_hook(hook)
        return self

    def uninstall(self):
        if not self._installed:
            return
        builtins.open = self._real_open
        os.replace = self._real_replace
        os.rename = self._real_rename
        while self._patched_calls:
            owner, attr, original = self._patched_calls.pop()
            setattr(owner, attr, original)
        if self._active_wire_hooks:
            from paddle_tpu.inference import wire as _wire
            while self._active_wire_hooks:
                _wire.remove_fault_hook(self._active_wire_hooks.pop())
        while self._paused_pids:
            pid = self._paused_pids.pop()
            try:
                os.kill(pid, _signal.SIGCONT)
            except (ProcessLookupError, OSError):
                pass
        self._installed = False

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()
        return False
