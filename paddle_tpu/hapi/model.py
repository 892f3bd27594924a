"""High-level ``paddle.Model`` API (python/paddle/hapi/model.py parity,
UNVERIFIED): prepare/fit/evaluate/predict/save/load.

Training hot path: ``fit`` runs a to_static-COMPILED train step by
default — forward, loss, backward and the optimizer update lower into
one XLA program with the persistable state (params + optimizer slots)
donated, fed by a background device-prefetch stage
(``io.DevicePrefetcher``) and a non-blocking loss window: up to
``steps_in_flight`` dispatched steps stay un-fetched, loss scalars
resolve only at ``log_freq``/epoch boundaries, so the host loop stays
dispatch-ahead of the device (the GSPMD-style host-overlap discipline;
docs/data_pipeline.md). The eager ``train_batch`` loop remains as
``fit(compiled=False)`` — the parity oracle and the fallback for
un-traceable user code (to_static itself also falls back per-signature
on genuine graph breaks, so ``compiled=True`` is always safe)."""

from __future__ import annotations

import collections
import time

import numpy as np

from ..framework.core import Tensor, no_grad
from ..framework.io import save as save_obj, load as load_obj
from ..io import DataLoader, DevicePrefetcher
from ..profiler import flight_recorder as _frec
from ..profiler import metrics as _pmetrics
from ..profiler import trace as _trace
from ..profiler.goodput import GoodputLedger
from ..tuner.surface import TunableSurface, register_surface
from ..utils import monitor

__all__ = ["Model"]

#: process-wide registry: fit-pipeline gauges + elastic/restart
#: accounting flow through it (updates mirror into the structured
#: tracer while tracing is enabled, so chrome exports keep carrying
#: them — docs/observability.md)
_REG = _pmetrics.get_registry()

_pmetrics.declare("hapi/input_wait_ms", "gauge",
                  "prefetcher starvation: ms the fit loop waited on "
                  "input this epoch")
_pmetrics.declare("hapi/steps_in_flight", "gauge",
                  "dispatched-but-unfetched compiled steps at last "
                  "dispatch")
_pmetrics.declare("hapi/h2d_bytes", "gauge",
                  "bytes device-placed by the input pipeline this "
                  "epoch")
_pmetrics.declare("hapi/avg_step_ms", "gauge",
                  "per-epoch mean train-step wall time (epoch summary)")
_pmetrics.declare("elastic/preempt_requested", "counter",
                  "preemption signals that reached the fit loop")
_pmetrics.declare("elastic/emergency_save_ms", "gauge",
                  "wall time of the bounded-time emergency checkpoint")
_pmetrics.declare("elastic/emergency_step", "gauge",
                  "epoch-relative step the emergency checkpoint "
                  "captured")
_pmetrics.declare("restart/round", "gauge",
                  "the launcher's PADDLE_RESTART_ROUND at resume")
_pmetrics.declare("restart/resume_epoch", "gauge",
                  "epoch training resumed at")
_pmetrics.declare("restart/resume_step", "gauge",
                  "first step consumed after a mid-epoch resume (0 = "
                  "epoch start)")


#: fit's pipeline knobs registered as a tunable surface (next to the
#: knob, like the serving chunk ladder): prefetch_depth = batches the
#: DevicePrefetcher places ahead of the consumer; steps_in_flight =
#: dispatched-but-unfetched compiled steps before backpressure.
#: ``bench.py --autotune`` sweeps this grid; fit consults the tuning
#: cache when both knobs are left None (arg > cache > default).
register_surface(TunableSurface(
    name="fit_pipeline",
    params=("prefetch_depth", "steps_in_flight"),
    default={"prefetch_depth": 2, "steps_in_flight": 2},
    candidates=lambda shape: [
        {"prefetch_depth": p, "steps_in_flight": s}
        for p in (1, 2, 4) for s in (1, 2, 4)],
    describe="hapi.Model.fit device-prefetch depth and in-flight "
             "compiled-step window"))


def _persist_ledger(ledger):
    """Best-effort goodput-ledger persist: an ENOSPC on the bookkeeping
    file must never mask an in-flight Preempted (the exit-75 launcher
    contract), skip fit's finally-block cleanup, or fail a training run
    that otherwise succeeded."""
    try:
        ledger.persist()
    except OSError as e:
        import warnings
        warnings.warn(f"goodput ledger persist failed ({e!r}); "
                      "continuing without on-disk goodput continuity")


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._optimizer = None
        self._loss = None
        self._scaler = None
        self._metrics = []
        self._compiled_train_step = None
        self._compiled_eval_step = None
        self._fit_pipeline = None
        self._resume_mid_step = None
        self._goodput = None

    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, scaler=None):
        self._optimizer = optimizer
        self._loss = loss
        # optional GradScaler: train steps route the update through
        # scale/unscale/update, and its device scalars (scale +
        # good/bad counters) ride every checkpoint — an elastic resume
        # restores dynamic-loss-scaling state exactly
        self._scaler = scaler
        # the compiled steps close over optimizer/loss/amp — re-prepare
        # must rebuild them
        self._compiled_train_step = None
        self._compiled_eval_step = None
        if metrics is not None:
            self._metrics = metrics if isinstance(metrics, (list, tuple)) \
                else [metrics]
        # AMP integration (upstream: amp_configs='O1'/'O2' or a dict):
        # O1 = bf16 autocast around fwd/loss; O2 additionally keeps fp32
        # master weights via GradScaler-less bf16-native flow (TPU bf16
        # needs no loss scaling)
        self._amp_level = None
        if amp_configs is not None:
            if isinstance(amp_configs, str):
                self._amp_level = amp_configs.upper()
            else:
                self._amp_level = str(amp_configs.get("level",
                                                      "O1")).upper()
            if self._amp_level not in ("O0", "O1", "O2"):
                raise ValueError(
                    f"amp_configs level must be O0/O1/O2, got "
                    f"{self._amp_level}")
            if self._amp_level == "O0":
                self._amp_level = None
            elif self._amp_level == "O2":
                from ..amp import decorate
                out = decorate(models=self.network,
                               optimizers=self._optimizer, level="O2")
                self.network = out[0] if isinstance(out, (list, tuple)) \
                    else out

    def _compute_loss(self, outputs, labels):
        if callable(self._loss):
            return self._loss(outputs, labels)
        raise RuntimeError("prepare(loss=...) first")

    def _fused_network_loss(self):
        """True when the compiled steps route labels INTO the network and
        take its labeled loss — the fused head + cross-entropy tail
        (ops/fused_ce.py, no [N, V] logits) — in place of the criterion
        over logits: when the criterion certifies that the two are the
        same loss (``fuses_with_network_loss``, e.g.
        LlamaPretrainingCriterion). The eager ``train_batch`` loop runs
        the criterion over logits: the parity oracle."""
        return getattr(self._loss, "fuses_with_network_loss", False)

    def _backward_and_step(self, loss):
        """Backward + optimizer update, through the GradScaler when one
        was prepared (scale → backward → unscale/step/update, the
        dynamic-loss-scaling flow; its counters are traced device math,
        so the compiled fit loop keeps them live)."""
        scaler = self._scaler
        if scaler is not None and scaler.is_enable():
            scaler.scale(loss).backward()
            scaler.step(self._optimizer)
        else:
            loss.backward()
            self._optimizer.step()
        self._optimizer.clear_grad()

    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        if getattr(self, "_amp_level", None):
            from ..amp import auto_cast
            with auto_cast(enable=True,
                           level=self._amp_level):
                outputs = self.network(*inputs)
                loss = self._compute_loss(outputs, labels)
        else:
            outputs = self.network(*inputs)
            loss = self._compute_loss(outputs, labels)
        if update:
            self._backward_and_step(loss)
        else:
            loss.backward()
        return [float(loss.item())]

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        with no_grad():
            outputs = self.network(*inputs)
            loss = self._compute_loss(outputs, labels)
        return [float(loss.item())]

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        with no_grad():
            out = self.network(*inputs)
        return out

    # ---- compiled steps (the fit hot path) -------------------------------

    def _static_train_step(self):
        """The jitted train step: forward + loss + backward + optimizer
        update functionalized into ONE compiled program via the
        to_static machinery, which donates the params and optimizer
        slots the step reassigns, so XLA updates state in place instead
        of allocating a fresh copy per step. Returns the loss TENSOR —
        no host fetch; the fit loop resolves values at log boundaries.
        ``train_batch`` stays the eager parity oracle."""
        sf = getattr(self, "_compiled_train_step", None)
        if sf is None:
            def train_step(*args):
                *xs, y = args
                self.network.train()

                def fwd_loss():
                    if self._fused_network_loss():
                        # labeled forward: the network's fused head + CE
                        # tail, (None, loss)
                        return self.network(*xs, labels=y)[1]
                    return self._compute_loss(self.network(*xs), y)

                if getattr(self, "_amp_level", None):
                    from ..amp import auto_cast
                    with auto_cast(enable=True, level=self._amp_level):
                        loss = fwd_loss()
                else:
                    loss = fwd_loss()
                self._backward_and_step(loss)
                return loss

            from ..jit.to_static_api import StaticFunction
            sf = StaticFunction(train_step)
            self._compiled_train_step = sf
        return sf

    def _static_eval_step(self):
        sf = getattr(self, "_compiled_eval_step", None)
        if sf is None:
            def eval_step(*args):
                *xs, y = args
                self.network.eval()
                with no_grad():
                    if self._fused_network_loss():
                        loss = self.network(*xs, labels=y)[1]
                    else:
                        loss = self._compute_loss(self.network(*xs), y)
                return loss

            from ..jit.to_static_api import StaticFunction
            sf = StaticFunction(eval_step)
            self._compiled_eval_step = sf
        return sf

    def _resolve_fit_pipeline(self, batch_size, prefetch_depth,
                              steps_in_flight) -> dict:
        """Pipeline-knob resolution, the serving-engine precedence:
        explicit fit() arg > tuning-cache entry > surface default."""
        cfg = {"prefetch_depth": prefetch_depth,
               "steps_in_flight": steps_in_flight}
        if any(v is None for v in cfg.values()):
            from ..tuner.surface import get_surface
            base = dict(get_surface("fit_pipeline").default)
            try:
                from .. import tuner
                hit = tuner.lookup("fit_pipeline",
                                   {"bs": int(batch_size or 0)},
                                   dtype="-")
            except Exception:
                hit = None
            if hit:
                base.update(hit)
            for k, v in cfg.items():
                if v is None:
                    cfg[k] = base.get(k, 2)
        cfg = {k: int(v) for k, v in cfg.items()}
        bad = {k: v for k, v in cfg.items() if v < 1}
        if bad:
            # 0 must not silently mean 1 — the fully synchronous,
            # unpipelined path is fit(compiled=False)
            raise ValueError(
                f"fit pipeline knobs must be >= 1, got {bad}; use "
                "compiled=False for the synchronous eager loop")
        self._fit_pipeline = cfg    # introspection (tests, bench)
        return cfg

    # ---- epoch loops -----------------------------------------------------

    def _fit_epoch_compiled(self, loader, step_fn, epoch, log_freq,
                            verbose, pipeline, device_sharding,
                            explicit_depth=False, guard=None,
                            skip_to=0):
        """One epoch at compiled-step speed: device-prefetched input,
        up to ``steps_in_flight`` dispatched steps un-fetched, loss
        scalars resolved only at log/epoch boundaries. ``guard`` is
        polled at each step boundary — on a preemption signal the loop
        stops dispatching, drains the in-flight loss window, and
        reports back so fit can emergency-checkpoint within the grace
        bound. ``skip_to`` fast-forwards a mid-epoch resume past the
        steps the preempted run already consumed (they are iterated but
        never dispatched). Returns (losses, prefetcher,
        host_dispatch_seconds, last_step, preempted)."""
        it = iter(loader)
        host_skipped = 0
        if isinstance(it, DevicePrefetcher):
            # the loader was built with prefetch_to_device= — use ITS
            # prefetch stage (a second wrapper would double-place every
            # batch, double-count h2d_bytes, and undo the loader's own
            # device_sharding)
            pf = it
            ignored = []
            if device_sharding is not None and \
                    pf.sharding != device_sharding:
                ignored.append("device_sharding")
            if explicit_depth and pf.depth != pipeline["prefetch_depth"]:
                ignored.append("prefetch_depth")
            if ignored:
                import warnings
                warnings.warn(
                    f"fit({'/'.join(ignored)}=...) ignored: the "
                    "DataLoader was built with prefetch_to_device= "
                    "and its own prefetch config wins — set these on "
                    "the DataLoader instead")
        else:
            # mid-epoch resume: skip consumed batches on the HOST
            # iterator, before the prefetch stage ever device-places
            # them (a restart should not pay H2D for batches it will
            # discard, nor inflate the h2d_bytes/input_wait gauges)
            for _ in range(skip_to):
                try:
                    next(it)
                except StopIteration:
                    break
            pf = DevicePrefetcher(it, depth=pipeline["prefetch_depth"],
                                  sharding=device_sharding)
            host_skipped = skip_to
        in_flight = pipeline["steps_in_flight"]
        pending: collections.deque = collections.deque()
        losses: list[float] = []
        host_s = 0.0

        def resolve_pending():
            # the ONLY host←device value fetches of the epoch
            while pending:
                _s, t = pending.popleft()
                v = float(np.asarray(t._data))
                losses.append(v)
                monitor.emit_step_metrics(epoch=epoch, loss=v)
            _REG.gauge("hapi/input_wait_ms").set(
                round(pf.input_wait_s * 1e3, 3), epoch=epoch)

        last_step = skip_to - 1
        preempted = False
        _wd_token = _frec.arm("fit compiled epoch")
        try:
            for step, batch in enumerate(pf, start=host_skipped):
                # step-boundary progress for the watchdog (owner-token
                # scoped so these beats never mask another component)
                _frec.beat(_wd_token)
                if guard is not None and guard.requested():
                    # step boundary: stop dispatching; the drain below
                    # resolves every in-flight step before the
                    # emergency checkpoint snapshots state
                    preempted = True
                    break
                if step < skip_to:
                    # mid-epoch resume behind a loader-owned prefetch
                    # stage (already device-placed): discard-iterate
                    continue
                batch = batch if isinstance(batch, (list, tuple)) \
                    else (batch,)
                t0 = time.perf_counter()
                with _trace.trace_span("hapi/train_batch", cat="train",
                                       epoch=epoch, step=step,
                                       mode="compiled"):
                    loss_t = step_fn(*batch)
                host_s += time.perf_counter() - t0
                last_step = step
                pending.append((step, loss_t))
                in_flight_now = min(len(pending), in_flight)
                _REG.gauge("hapi/steps_in_flight").set(in_flight_now)
                if len(pending) > in_flight:
                    # backpressure: block on the readiness (not the
                    # value) of the step in_flight behind the newest —
                    # at most in_flight UNFINISHED steps stay queued,
                    # however long resolution is deferred. (pending[0]
                    # would be a no-op once the first step completes.)
                    _trace.block_on(pending[-in_flight - 1][1]._data)
                if step % log_freq == 0:
                    resolve_pending()
                    if verbose:
                        print(f"epoch {epoch} step {step}: "
                              f"loss {losses[-1]:.5f}")
            resolve_pending()
        finally:
            _frec.disarm(_wd_token)
            pf.close()
        _REG.gauge("hapi/h2d_bytes").set(pf.h2d_bytes, epoch=epoch)
        return losses, pf, host_s, last_step, preempted

    def _fit_epoch_eager(self, loader, epoch, log_freq, verbose,
                         guard=None, skip_to=0):
        """The eager parity-oracle loop (per-step host sync); same
        preemption/skip contract as the compiled loop."""
        losses: list[float] = []
        last_step = skip_to - 1
        preempted = False
        for step, batch in enumerate(loader):
            if guard is not None and guard.requested():
                preempted = True
                break
            if step < skip_to:
                continue  # host batches only: no device cost to skip
            *xs, y = batch if isinstance(batch, (list, tuple)) \
                else (batch,)
            with _trace.trace_span("hapi/train_batch", cat="train",
                                   epoch=epoch, step=step):
                loss = self.train_batch(xs, y)
            last_step = step
            losses.append(loss[0])
            monitor.emit_step_metrics(epoch=epoch, loss=loss[0])
            if verbose and step % log_freq == 0:
                print(f"epoch {epoch} step {step}: loss {loss[0]:.5f}")
        return losses, last_step, preempted

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1,
            verbose=2, drop_last=False, shuffle=True, num_workers=0,
            callbacks=None, resume=None, keep_last_n=None,
            legacy_save=True, compiled=True,
            prefetch_depth=None, steps_in_flight=None,
            device_sharding=None, preemptible=None):
        """Train. ``save_dir`` writes a committed ``step_N``
        distributed checkpoint per epoch (``keep_last_n`` bounds its
        retention) plus — unless ``legacy_save=False`` — the upstream
        ``epoch_N.pdparams`` files. ``resume=True`` restarts from the
        newest *committed* checkpoint — ``PADDLE_RESUME_CHECKPOINT``
        if the elastic launcher exported one, else the newest valid
        ``step_N`` under ``save_dir`` — skipping any save torn by a
        crash; ``resume=<path>`` loads that checkpoint explicitly.
        Checkpoints are topology-aware: a resume may run on a
        different mesh (dp/mp resized either way) and each tensor is
        resharded on load, optimizer slots and device step/scale
        scalars included.

        **Preemption** (``preemptible``, default: on whenever
        ``save_dir`` is set): a SIGTERM observed at a step boundary
        drains the in-flight loss window, writes a bounded-time
        emergency checkpoint (``PADDLE_PREEMPT_GRACE_S`` caps the
        commit barrier) recording the mid-epoch step, and raises
        :class:`~paddle_tpu.distributed.fleet.elastic.Preempted`; the
        elastic launcher classifies the resulting EX_TEMPFAIL exit as
        a clean preemption and relaunches without burning the crash
        budget. A mid-epoch resume fast-forwards the loader past the
        consumed steps — with a deterministic batch order (seeded or
        ``shuffle=False``) the loss trajectory continues exactly.
        Pass a ``PreemptionGuard`` instance to share one across loops,
        or ``False`` to opt out.

        Hot-path knobs (module docstring, docs/data_pipeline.md):
        ``compiled=True`` runs the jitted train step; ``prefetch_depth``
        / ``steps_in_flight`` override the pipeline depths (default:
        tuning cache, then 2/2); ``device_sharding`` (a jax Sharding,
        e.g. NamedSharding over a dp mesh axis) device-places each
        global batch sharded across the mesh."""
        import os as _os
        loader = train_data if isinstance(train_data, DataLoader) else \
            DataLoader(train_data, batch_size=batch_size, shuffle=shuffle,
                       drop_last=drop_last, num_workers=num_workers)
        # goodput ledger: end-to-end wall-time partition (productive
        # compiled steps vs input-wait / saves / restarts / recompiles
        # — docs/observability.md). In-memory always; persisted next to
        # the checkpoints so restart rounds accumulate into ONE ledger
        # and a preempted run still reports honest end-to-end goodput.
        # load=resume: a deliberately fresh fit into a reused save_dir
        # must not inherit (and book days of "restart" loss against) a
        # previous run's ledger; elastic relaunches pass resume=True
        ledger = GoodputLedger(
            path=f"{save_dir}/goodput.json" if save_dir else None,
            load=bool(resume))
        self._goodput = ledger
        # register as the process's CURRENT ledger so the /statusz
        # goodput section (profiler/exposition.py, ISSUE 13) reads the
        # live run without a handle threaded through the stack
        from ..profiler import goodput as _goodput_mod
        _goodput_mod.set_current(ledger)
        start_epoch = 0
        resume_skip = 0  # steps already consumed in start_epoch
        if resume:
            ckpt_path = resume if isinstance(resume, str) else None
            if ckpt_path is None:
                ckpt_path = _os.environ.get("PADDLE_RESUME_CHECKPOINT")
            if ckpt_path is None and save_dir is not None:
                from ..distributed.checkpoint import \
                    latest_valid_checkpoint
                ckpt_path = latest_valid_checkpoint(save_dir)
            if ckpt_path:
                # resume restore (validated load + cross-mesh reshard)
                # is lost time the ledger books against "reshard"
                with ledger.measure("reshard"):
                    epoch_done = self.load_checkpoint(ckpt_path)
                mid = self._resume_mid_step
                if mid is None:
                    start_epoch = epoch_done + 1
                else:
                    # emergency checkpoint mid-epoch: redo THIS epoch
                    # from the step after the last one consumed
                    start_epoch = epoch_done
                    resume_skip = int(mid) + 1
                _REG.gauge("restart/round").set(
                    int(_os.environ.get("PADDLE_RESTART_ROUND", "0")))
                _REG.gauge("restart/resume_epoch").set(start_epoch)
                _REG.gauge("restart/resume_step").set(resume_skip)
                _frec.record_event("resume", epoch=start_epoch,
                                   step=resume_skip,
                                   checkpoint=str(ckpt_path))
                if verbose:
                    mid_msg = f" step {resume_skip}" if resume_skip \
                        else ""
                    print(f"resuming from {ckpt_path} "
                          f"(epoch {start_epoch}{mid_msg})")
        # cache keying must see the REAL batch size when the caller
        # handed us a pre-built DataLoader (batch_size stays at its
        # default of 1 in that case)
        eff_bs = batch_size
        if isinstance(train_data, DataLoader):
            sampler = getattr(loader, "batch_sampler", None)
            eff_bs = getattr(sampler, "batch_size", None) \
                or getattr(loader, "batch_size", None) or batch_size
        pipeline = self._resolve_fit_pipeline(eff_bs, prefetch_depth,
                                              steps_in_flight)
        # preemptible: False = off, a PreemptionGuard = use that one,
        # None (default) = on when save_dir is set, True = on (needs
        # save_dir for the emergency checkpoint)
        guard = None
        own_guard = False
        if preemptible is True and save_dir is None:
            raise ValueError(
                "fit(preemptible=True) needs save_dir=: an emergency "
                "checkpoint has nowhere to commit")
        if preemptible is not None and not isinstance(preemptible, bool):
            guard = preemptible
            guard.install()
        elif preemptible is not False and save_dir is not None:
            from ..distributed.fleet.elastic import PreemptionGuard
            guard = PreemptionGuard().install()
            own_guard = True
        try:
            step_fn = self._static_train_step() if compiled else None
            for epoch in range(start_epoch, epochs):
                epoch_t0 = time.perf_counter()
                skip_to = resume_skip if epoch == start_epoch else 0
                extra = {}
                if compiled:
                    runs0 = (step_fn.n_compiled_runs,
                             step_fn.n_eager_runs)
                    comp_s0 = step_fn.build_seconds()
                    losses, pf, host_s, last_step, preempted = \
                        self._fit_epoch_compiled(
                            loader, step_fn, epoch, log_freq, verbose,
                            pipeline, device_sharding,
                            explicit_depth=prefetch_depth is not None,
                            guard=guard, skip_to=skip_to)
                    # host-vs-device attribution: host_dispatch_ms is
                    # the python/dispatch cost of the epoch; the rest
                    # of epoch_s is device compute + input wait. Run
                    # counters are cumulative on the StaticFunction —
                    # report the per-epoch delta.
                    extra = {"input_wait_ms":
                                 round(pf.input_wait_s * 1e3, 3),
                             "h2d_mb": round(pf.h2d_bytes / 1e6, 3),
                             "host_dispatch_ms": round(host_s * 1e3, 3),
                             "compiled_steps":
                                 step_fn.n_compiled_runs - runs0[0],
                             "eager_steps":
                                 step_fn.n_eager_runs - runs0[1]}
                    ledger.add("input_wait", pf.input_wait_s)
                    # discovery and the first compiled call's trace,
                    # lower and compile (jit/* counters, its fn)
                    ledger.add("recompile",
                               step_fn.build_seconds() - comp_s0)
                else:
                    losses, last_step, preempted = self._fit_epoch_eager(
                        loader, epoch, log_freq, verbose,
                        guard=guard, skip_to=skip_to)
                # per-epoch perf summary through the trace layer (INFO
                # log + gauges; profiler subsystem) — avg step time is
                # the number every perf regression shows up in first
                summary = _trace.epoch_summary(
                    epoch, steps=len(losses),
                    seconds=time.perf_counter() - epoch_t0,
                    mean_loss=round(float(np.mean(losses)), 6)
                    if losses else None,
                    goodput_frac=ledger.summary()["goodput_frac"],
                    **extra)
                self._last_epoch_summary = summary
                if preempted:
                    ck = self._emergency_checkpoint(
                        save_dir, epoch, last_step, keep_last_n, guard)
                    _persist_ledger(ledger)
                    from ..distributed.fleet.elastic import Preempted
                    raise Preempted(
                        f"preempted at epoch {epoch} step {last_step}; "
                        f"emergency checkpoint committed at {ck}",
                        checkpoint=ck, epoch=epoch, step=last_step)
                if verbose:
                    print(f"epoch {epoch} done: {summary['steps']} "
                          f"steps in {summary['epoch_s']:.2f}s "
                          f"(avg {summary['avg_step_ms']:.1f} ms/step)")
                if save_dir is not None and epoch % save_freq == 0:
                    with ledger.measure("checkpoint_save"):
                        if legacy_save:
                            self.save(f"{save_dir}/epoch_{epoch}")
                        self.save_checkpoint(f"{save_dir}/step_{epoch}",
                                             epoch=epoch,
                                             keep_last_n=keep_last_n)
                    _persist_ledger(ledger)
                if eval_data is not None and epoch % eval_freq == 0:
                    self.evaluate(eval_data, batch_size=batch_size,
                                  verbose=verbose, compiled=compiled)
        finally:
            # freeze the wall clock at end-of-run: the ledger stays on
            # self._goodput, and a summary()/bench_keys() read hours
            # later must not book the idle gap as productive time
            ledger.close()
            _persist_ledger(ledger)
            if own_guard:
                guard.uninstall()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, compiled=True):
        loader = eval_data if isinstance(eval_data, DataLoader) else \
            DataLoader(eval_data, batch_size=batch_size,
                       num_workers=num_workers)
        losses = []
        if compiled:
            step_fn = self._static_eval_step()
            in_flight = (self._fit_pipeline
                         or {"steps_in_flight": 2})["steps_in_flight"]
            pending = []
            for batch in loader:
                batch = batch if isinstance(batch, (list, tuple)) \
                    else (batch,)
                pending.append(step_fn(*batch))
                if len(pending) > in_flight:
                    # same backpressure as fit: bound the device queue
                    # by the READINESS of the step in_flight back —
                    # values still resolve only once at the end
                    _trace.block_on(pending[-in_flight - 1]._data)
            losses = [float(np.asarray(t._data)) for t in pending]
        else:
            for batch in loader:
                *xs, y = batch if isinstance(batch, (list, tuple)) \
                    else (batch,)
                losses.append(self.eval_batch(xs, y)[0])
        result = {"loss": [float(np.mean(losses))]}
        if verbose:
            print(f"Eval loss: {result['loss'][0]:.5f}")
        return result

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = test_data if isinstance(test_data, DataLoader) else \
            DataLoader(test_data, batch_size=batch_size)
        outs = []
        for batch in loader:
            xs = batch if isinstance(batch, (list, tuple)) else (batch,)
            outs.append(self.predict_batch(list(xs)))
        return outs

    def save(self, path, training=True):
        save_obj(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            save_obj(self._optimizer.state_dict(), path + ".pdopt")

    def _checkpoint_state(self, epoch=None, mid_epoch_step=None):
        """The full resumable-state dict: model + optimizer (slots AND
        the device ``@step`` scalar) + GradScaler scale/counters +
        epoch/step markers."""
        state = {"model": self.network.state_dict()}
        if self._optimizer is not None:
            state["optimizer"] = self._optimizer.state_dict()
        if self._scaler is not None:
            state["scaler"] = self._scaler.state_dict()
        if epoch is not None:
            state["epoch"] = int(epoch)
        if mid_epoch_step is not None:
            state["mid_epoch_step"] = int(mid_epoch_step)
        return state

    def save_checkpoint(self, path, epoch=None, keep_last_n=None,
                        mid_epoch_step=None, barrier_timeout=None):
        """Atomic (commit-protocol) checkpoint of model + optimizer +
        scaler + epoch: the directory either appears fully committed or
        not at all, so a crash mid-save can never corrupt the resume
        point. ``mid_epoch_step`` marks an emergency (preemption)
        checkpoint taken inside an epoch; resume redoes the epoch from
        the following step. ``barrier_timeout`` bounds the commit
        barrier (the preemption grace window)."""
        from ..distributed import checkpoint as dckpt
        dckpt.save_state_dict(
            self._checkpoint_state(epoch, mid_epoch_step), path,
            keep_last_n=keep_last_n, barrier_timeout=barrier_timeout)

    def _emergency_checkpoint(self, save_dir, epoch, step, keep_last_n,
                              guard):
        """Bounded-time preemption checkpoint at a step boundary: the
        in-flight window is already drained, so device state is exactly
        post-step ``step`` of ``epoch``. Returns the committed path
        (None when fit has no save_dir to commit into)."""
        _REG.counter("elastic/preempt_requested").inc()
        _frec.record_event("preempt_requested", epoch=epoch, step=step)
        if save_dir is None:
            return None
        t0 = time.perf_counter()
        path = f"{save_dir}/step_{epoch}"
        bound = guard.remaining() if guard is not None else None
        if bound is not None and not np.isfinite(bound):
            bound = None
        self.save_checkpoint(path, epoch=epoch, keep_last_n=keep_last_n,
                             mid_epoch_step=step, barrier_timeout=bound)
        elapsed = time.perf_counter() - t0
        ledger = getattr(self, "_goodput", None)
        if ledger is not None:
            ledger.add("emergency_save", elapsed)
        _REG.gauge("elastic/emergency_save_ms").set(
            round(elapsed * 1e3, 3))
        _REG.gauge("elastic/emergency_step").set(int(step), epoch=epoch)
        return path

    def load_checkpoint(self, path):
        """Validated load of a committed checkpoint (checksums verified;
        torn/corrupt dirs raise), resharding every tensor — params,
        optimizer slots, device step/scale scalars — onto the CURRENT
        mesh layout. Returns the epoch recorded at save time, or -1;
        an emergency checkpoint's mid-epoch step lands in
        ``self._resume_mid_step`` (None otherwise)."""
        from ..distributed import checkpoint as dckpt
        target = {"model": self.network.state_dict()}
        dckpt.load_state_dict(target, path)
        if self._optimizer is not None:
            # read (not in-place load): optimizer slots are created
            # lazily, so a fresh process has no target tensors yet —
            # set_state_dict stashes state until the slots materialize
            flat = dckpt.read_state_dict(path, prefix="optimizer")
            opt_state = {}
            for k, v in flat.items():
                # the optimizer state dict has exactly one nested
                # level (LR_Scheduler); other keys are flat slot names
                # that may themselves contain dots
                if k.startswith("LR_Scheduler."):
                    opt_state.setdefault("LR_Scheduler", {})[
                        k[len("LR_Scheduler."):]] = v
                else:
                    opt_state[k] = v
            if opt_state:
                self._optimizer.set_state_dict(opt_state)
        vals = dckpt.load_values(path)
        if self._scaler is not None and isinstance(
                vals.get("scaler"), dict):
            self._scaler.load_state_dict(vals["scaler"])
        mid = vals.get("mid_epoch_step")
        self._resume_mid_step = int(mid) if mid is not None else None
        return int(vals.get("epoch", -1))

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        self.network.set_state_dict(load_obj(path + ".pdparams"))
        import os
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(load_obj(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        n_params = sum(p.size for p in self.network.parameters())
        print(f"Total params: {n_params}")
        return {"total_params": n_params}
