"""ISSUE 5 — the compiled-step fit loop: hapi.Model.fit at
compiled-step speed with buffer donation, device-prefetch input and
non-blocking loss fetch.

Covers: compiled-vs-eager loss parity (the eager loop is the oracle),
bit-for-bit equivalence of deferred (non-blocking) vs per-step loss
resolution, the host-overhead drop vs the eager loop, wall-clock ≈
max(data, compute) overlap with a throttled dataset and a sleep-padded
compiled step, DevicePrefetcher semantics (sharded placement, error
propagation, stats), the fit_pipeline tuner surface, and the compiled
step advancing optimizer/scaler device state correctly."""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi import Model
from paddle_tpu.io import DevicePrefetcher, TensorDataset
from paddle_tpu.utils import monitor


def _dataset(n=16, in_dim=4, seed=0):
    x = np.random.RandomState(seed).randn(n, in_dim).astype("float32")
    y = np.random.RandomState(seed + 1).randn(n, 1).astype("float32")
    return TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])


def _model(seed=0, lr=0.05):
    paddle.seed(seed)
    net = nn.Linear(4, 1)
    m = Model(net)
    m.prepare(paddle.optimizer.Adam(lr, parameters=net.parameters()),
              nn.MSELoss())
    return m


def _fit_losses(m, ds, **kw):
    """Run fit and return the per-step losses the monitor hooks saw."""
    rec = []
    remove = monitor.register_step_metrics_hook(
        lambda ms: rec.append(ms["loss"]))
    try:
        m.fit(ds, batch_size=4, verbose=0, shuffle=False, **kw)
    finally:
        remove()
    return rec


class TestCompiledFitParity:
    def test_compiled_matches_eager_oracle(self):
        """fit(compiled=True) trains to the same losses as the eager
        tape loop (to_static parity tolerance: XLA fuses the update
        math the eager path dispatches op-by-op)."""
        ref = _fit_losses(_model(3), _dataset(), epochs=2,
                          compiled=False)
        got = _fit_losses(_model(3), _dataset(), epochs=2,
                          compiled=True)
        assert len(ref) == len(got) == 8
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)

    def test_step_ran_compiled_not_eager(self):
        m = _model(0)
        _fit_losses(m, _dataset(), epochs=2, compiled=True)
        sf = m._compiled_train_step
        # one discovery (eager) per signature, everything else compiled
        assert sf.n_compiled_runs >= 6
        assert sf.n_eager_runs <= 2

    def test_nonblocking_resolution_is_bit_for_bit(self):
        """Deferred loss resolution (large in-flight window, resolve
        only at log boundaries) returns bit-identical floats to
        per-step synchronous resolution of the same compiled step."""
        deferred = _fit_losses(_model(7), _dataset(), epochs=2,
                               compiled=True, steps_in_flight=4,
                               log_freq=1000)
        synced = _fit_losses(_model(7), _dataset(), epochs=2,
                             compiled=True, steps_in_flight=1,
                             log_freq=1)
        assert deferred == synced        # exact, not allclose

    def test_optimizer_step_count_advances_under_compiled_steps(self):
        m = _model(0)
        _fit_losses(m, _dataset(), epochs=2, compiled=True)
        # 4 batches/epoch x 2 epochs; a python-int counter would read 1
        # (the discovery run only)
        assert m._optimizer._step_count == 8

    def test_donation_invalidates_old_state_buffers(self):
        """fit's compiled step gets to_static's rule: the state it
        reassigns is aliased into the program, so the pre-step param
        buffer must be dead afterwards (proof the donation actually
        engaged, not silently dropped)."""
        m = _model(0)
        ds = _dataset()
        _fit_losses(m, ds, epochs=1, compiled=True)
        p = next(iter(m.network.parameters()))
        old = p._data
        _fit_losses(m, ds, epochs=1, compiled=True)
        with pytest.raises(RuntimeError):
            np.asarray(old) + 1   # donated buffer: deleted
        # the live tensor is fine
        assert np.isfinite(p.numpy()).all()

    def test_compiled_evaluate_matches_eager(self):
        m = _model(1)
        ds = _dataset()
        r1 = m.evaluate(ds, batch_size=4, verbose=0, compiled=True)
        r2 = m.evaluate(ds, batch_size=4, verbose=0, compiled=False)
        np.testing.assert_allclose(r1["loss"], r2["loss"], rtol=1e-6)


class TestGraphBreakFallback:
    def test_unguardable_loss_falls_back_with_prefetch_running(self):
        """A loss with a float() graph break: fit must warn, run the
        signature eagerly/segmented, and still train — WITH the
        device-prefetch thread live. Regression: segment mode used to
        be process-global, so the fallback's lazy-op recording captured
        the prefetch thread's collate ops mid-flight and corrupted
        batch shapes (flaky 'all input arrays must have the same
        shape'). The recorder is now thread-local."""
        import warnings

        paddle.seed(0)
        net = nn.Linear(4, 1)
        m = Model(net)

        def breaky_loss(out, y):
            loss = ((out - y) ** 2).mean()
            if float(loss) > 1e30:     # unguardable concretization
                loss = loss * 2.0
            return loss

        m.prepare(paddle.optimizer.SGD(0.05,
                                       parameters=net.parameters()),
                  breaky_loss)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            m.fit(_dataset(n=32), batch_size=4, epochs=2, verbose=0,
                  shuffle=False, compiled=True)
        assert any("graph break" in str(x.message) for x in w)
        s = m._last_epoch_summary
        assert s["steps"] == 8 and np.isfinite(s["mean_loss"])


class TestHostOverhead:
    def test_compiled_fit_step_cheaper_than_eager(self):
        """The acceptance bar: fit-loop host overhead per step drops
        measurably vs the eager loop (one jitted call + deferred fetch
        vs per-op tape dispatch + a float() sync every step)."""
        ds = _dataset(n=64)
        m = _model(0)
        m.fit(ds, batch_size=4, epochs=2, verbose=0, shuffle=False,
              compiled=True, log_freq=1000)
        compiled_ms = m._last_epoch_summary["avg_step_ms"]
        m2 = _model(0)
        m2.fit(ds, batch_size=4, epochs=2, verbose=0, shuffle=False,
               compiled=False)
        eager_ms = m2._last_epoch_summary["avg_step_ms"]
        # generous margin for a loaded 1-core CI box; the real ratio is
        # ~10-25x on this model
        assert compiled_ms < eager_ms * 0.7, (compiled_ms, eager_ms)

    def test_epoch_summary_carries_pipeline_attribution(self):
        m = _model(0)
        m.fit(_dataset(), batch_size=4, epochs=1, verbose=0,
              shuffle=False, compiled=True)
        s = m._last_epoch_summary
        for key in ("input_wait_ms", "h2d_mb", "host_dispatch_ms",
                    "compiled_steps", "eager_steps"):
            assert key in s, key
        assert s["compiled_steps"] + s["eager_steps"] >= s["steps"]


class _ThrottledDataset(paddle.io.Dataset):
    """Synthetic dataset sleeping per item — the input side of the
    overlap test."""

    def __init__(self, n, item_sleep_s):
        self.n = n
        self.sleep = item_sleep_s

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        time.sleep(self.sleep)
        x = np.full((4,), float(i), dtype=np.float32)
        return x, x[:1]


def _sleepy_loss(pad_s):
    """MSE whose VALUE routes through a host callback that sleeps —
    inside the compiled program, so every compiled-step execution is
    padded by ``pad_s`` (the compute side of the overlap test)."""
    import jax

    from paddle_tpu.framework.core import apply

    def _cb(x):
        time.sleep(pad_s)
        return x

    def _pad(arr):
        return jax.pure_callback(
            _cb, jax.ShapeDtypeStruct(arr.shape, arr.dtype), arr)

    def loss_fn(out, y):
        mse = ((out - y) ** 2).mean()
        return apply(_pad, mse, differentiable=False, name="sleep_pad")

    return loss_fn


class TestOverlap:
    def test_fit_wall_is_max_not_sum(self):
        """With a throttled dataset (sleep per item) and a sleep-padded
        compiled step, fit wall-clock ≈ max(data, compute) — the
        prefetch thread hides input time behind the step."""
        n, bs = 24, 2
        item_s, pad_s = 0.008, 0.020
        data_s = n * item_s                      # 0.192 s/epoch
        compute_s = (n // bs) * pad_s            # 0.240 s/epoch
        paddle.seed(0)
        net = nn.Linear(4, 1)
        m = Model(net)
        m.prepare(paddle.optimizer.SGD(0.1, parameters=net.parameters()),
                  _sleepy_loss(pad_s))
        ds = _ThrottledDataset(n, item_s)
        # epoch 0 warms (trace + compile); epoch 1 is the measurement
        m.fit(ds, batch_size=bs, epochs=2, verbose=0, shuffle=False,
              compiled=True, log_freq=1000, prefetch_depth=2,
              steps_in_flight=2)
        wall = m._last_epoch_summary["epoch_s"]
        serial = data_s + compute_s              # 0.432 s
        assert wall < serial * 0.85, (wall, serial)
        assert wall > max(data_s, compute_s) * 0.9, (wall, compute_s)

    def test_input_wait_gauge_sees_input_bound_pipeline(self):
        """When data is the bottleneck, input_wait_ms must say so."""
        paddle.seed(0)
        net = nn.Linear(4, 1)
        m = Model(net)
        m.prepare(paddle.optimizer.SGD(0.1, parameters=net.parameters()),
                  nn.MSELoss())
        m.fit(_ThrottledDataset(12, 0.01), batch_size=2, epochs=1,
              verbose=0, shuffle=False, compiled=True)
        assert m._last_epoch_summary["input_wait_ms"] > 20.0


class TestDevicePrefetcher:
    def test_batches_and_stats(self):
        batches = [[paddle.to_tensor(np.full((2, 3), i, "float32")),
                    paddle.to_tensor(np.full((2, 1), i, "float32"))]
                   for i in range(5)]
        pf = DevicePrefetcher(iter(batches), depth=2)
        out = list(pf)
        assert len(out) == 5 and pf.batches == 5
        for i, b in enumerate(out):
            np.testing.assert_array_equal(b[0].numpy(),
                                          np.full((2, 3), i, "float32"))
        assert pf.h2d_bytes == 5 * (2 * 3 + 2 * 1) * 4

    def test_sharded_placement_no_host_gather(self):
        """sharding-aware placement: a GLOBAL numpy batch lands split
        across a dp mesh straight from host memory."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devs = jax.devices()
        assert len(devs) >= 8   # conftest forces 8 virtual cpu devices
        mesh = Mesh(np.array(devs[:8]), ("dp",))
        sh = NamedSharding(mesh, PartitionSpec("dp"))
        global_batch = np.arange(64, dtype=np.float32).reshape(16, 4)
        pf = DevicePrefetcher(iter([[global_batch]]), depth=1,
                              sharding=sh)
        (t,) = next(pf)
        assert t._data.sharding == sh
        assert len(t._data.addressable_shards) == 8
        assert t._data.addressable_shards[0].data.shape == (2, 4)
        np.testing.assert_array_equal(np.asarray(t._data), global_batch)

    def test_exhausted_iterator_keeps_raising_stopiteration(self):
        pf = DevicePrefetcher(
            iter([[paddle.to_tensor(np.zeros((2,), "float32"))]]),
            depth=1)
        assert len(list(pf)) == 1
        with pytest.raises(StopIteration):   # must not deadlock
            next(pf)
        with pytest.raises(StopIteration):
            next(pf)

    def test_closed_iterator_raises_not_blocks(self):
        pf = DevicePrefetcher(
            iter([[paddle.to_tensor(np.zeros((2,), "float32"))]] * 4),
            depth=1)
        next(pf)
        pf.close()
        with pytest.raises(StopIteration):   # must not deadlock
            next(pf)

    def test_producer_error_propagates(self):
        def gen():
            yield [paddle.to_tensor(np.zeros((2, 2), "float32"))]
            raise ValueError("boom in producer")

        pf = DevicePrefetcher(gen(), depth=2)
        next(pf)
        with pytest.raises(ValueError, match="boom in producer"):
            next(pf)

    def test_namedtuple_batches_place(self):
        import collections
        B = collections.namedtuple("B", ["x", "y"])
        pf = DevicePrefetcher(
            iter([B(np.ones((2, 2), np.float32),
                    np.zeros((2, 1), np.float32))]), depth=1)
        b = next(pf)
        assert isinstance(b, B)
        np.testing.assert_array_equal(b.x.numpy(), np.ones((2, 2)))

    def test_fit_reuses_loader_prefetcher_no_double_wrap(self):
        """A loader built with prefetch_to_device= supplies the
        prefetch stage; fit must ride it (not re-place every batch
        through a second wrapper)."""
        loader = paddle.io.DataLoader(_dataset(), batch_size=4,
                                      shuffle=False,
                                      prefetch_to_device=2)
        ref = _fit_losses(_model(3), _dataset(), epochs=1,
                          compiled=True)
        m = _model(3)
        rec = []
        remove = monitor.register_step_metrics_hook(
            lambda ms: rec.append(ms["loss"]))
        try:
            m.fit(loader, epochs=1, verbose=0)
        finally:
            remove()
        np.testing.assert_allclose(rec, ref, rtol=1e-6)
        assert m._last_epoch_summary["h2d_mb"] >= 0

    def test_dataloader_prefetch_to_device_arg(self):
        loader = paddle.io.DataLoader(_dataset(8), batch_size=4,
                                      shuffle=False,
                                      prefetch_to_device=2)
        it = iter(loader)
        assert isinstance(it, DevicePrefetcher)
        assert len(list(it)) == 2


def _adamw_step(seed=0):
    """A plain ``to_static`` AdamW step over a small MLP whose first
    layer is frozen: state the step reads and never writes."""
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net[0].weight.stop_gradient = True
    net[0].bias.stop_gradient = True
    trained = list(net[2].parameters())
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=trained,
        grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def step(x, y, *held):
        loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.random.RandomState(0).randn(16, 8)
                         .astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1).randn(16, 4)
                         .astype("float32"))
    return net, opt, step, x, y


def _donated_by(fn, *args):
    from paddle_tpu.profiler.metrics import get_registry
    c = get_registry().counter("jit/donated_inputs")
    before = c.value
    out = fn(*args)
    return out, c.value - before


def _latest_graph(fn):
    entry = list(fn._graphs.values())[-1]
    return entry.by_key[entry.latest_key]


class TestStepDonatesWhatItReassigns:
    """A guard-free compiled step gives its program the buffers of the
    state it reassigns, and nothing else (ISSUE 26)."""

    def test_written_buffer_is_consumed_read_only_is_not(self):
        net, _, step, x, y = _adamw_step()
        fn = paddle.jit.to_static(step)
        fn(x, y)                                  # discovery: eager
        written, read_only = net[2].weight, net[0].weight
        old_w, old_r = written._data, read_only._data
        fn(x, y)
        assert old_w.is_deleted() and written._data is not old_w
        assert read_only._data is old_r           # not even returned
        np.asarray(old_r)
        assert np.isfinite(written.numpy()).all()

    def test_parameter_passed_as_argument_stays_the_callers(self):
        """A written parameter that is ALSO a call argument is left out
        of the donated group (JAX refuses ``f(donate(a), a)``), and
        only that one."""
        net, _, step, x, y = _adamw_step()
        w = net[2].weight
        fn = paddle.jit.to_static(step)
        _, n = _donated_by(fn, x, y, w)           # discovery: eager
        assert n == 0
        old_w, old_b = w._data, net[2].bias._data
        for _ in range(3):
            _, n = _donated_by(fn, x, y, w)
        graph = _latest_graph(fn)
        assert n == len(graph.written) - 1
        assert not old_w.is_deleted() and old_b.is_deleted()
        assert w._data is not old_w               # returned fresh

    def test_two_state_tensors_sharing_a_buffer(self):
        """... is given away by neither (JAX refuses a buffer that one
        flattened argument donates and another reads): both come back
        fresh, and from then on each owns its own."""
        import jax.numpy as jnp
        _, opt, step, x, y = _adamw_step()
        fn = paddle.jit.to_static(step)
        fn(x, y)
        pair = [t for t in opt.state_dict().values()
                if isinstance(t, paddle.Tensor) and t.shape == [4]][:2]
        shared = jnp.zeros((4,), jnp.float32)
        for t in pair:
            t.set_data(shared)
        _, n = _donated_by(fn, x, y)
        graph = _latest_graph(fn)
        assert n == len(graph.written) - 2
        assert not shared.is_deleted()
        assert pair[0]._data is not pair[1]._data
        loss, n = _donated_by(fn, x, y)
        assert n == len(graph.written) and np.isfinite(float(loss))

    def test_losses_equal_the_undonated_steps_bit_for_bit(self):
        """Donation changes where the new state is written, not one bit
        of it: the same step with every parameter and optimizer slot
        ALSO handed in as an (unused) argument keeps them all, and reads
        the same losses exactly. Against the eager loop the compiled
        program differs in the last digit with or without donation (XLA
        fuses the update), so that comparison has a tolerance."""
        _, _, step, x, y = _adamw_step()
        eager = [float(step(x, y)) for _ in range(6)]
        _, _, step, x, y = _adamw_step()
        fn = paddle.jit.to_static(step)
        donated = [float(fn(x, y)) for _ in range(6)]
        net, opt, step, x, y = _adamw_step()
        fn = paddle.jit.to_static(step)
        first = float(fn(x, y))                   # slots exist after it
        held = list(net[2].parameters()) + [
            t for t in opt.state_dict().values()
            if isinstance(t, paddle.Tensor)]
        kept, n_donated = [first], []
        for _ in range(5):
            loss, n = _donated_by(fn, x, y, *held)
            kept.append(float(loss))
            n_donated.append(n)
        assert donated == kept                    # exact, not allclose
        graph = _latest_graph(fn)
        assert n_donated[-1] == len(graph.written) - len(held)
        np.testing.assert_allclose(donated, eager, rtol=1e-6)

    def test_detach_aliases_and_clone_snapshots(self):
        net, _, step, x, y = _adamw_step()
        fn = paddle.jit.to_static(step)
        fn(x, y)
        w = net[2].weight
        alias, snap = w.detach(), w.clone()
        # (not w.numpy(): on the CPU that is a view of the buffer, and
        # a buffer with a view on it is copied instead of given away)
        host = snap.numpy()
        fn(x, y)
        with pytest.raises(RuntimeError):
            alias.numpy()                         # as Paddle: aliases
        np.testing.assert_array_equal(snap.numpy(), host)
        assert not np.array_equal(w.numpy(), host)

    def test_set_state_dict_copies_from_live_state(self):
        """A target network synced from the online one, and an optimizer
        loaded from a live one, own their buffers: the online step's
        donation must not delete them."""
        net, opt, step, x, y = _adamw_step()
        target, opt2 = _adamw_step(seed=1)[:2]
        fn = paddle.jit.to_static(step)
        fn(x, y)
        target.set_state_dict(net.state_dict())
        opt2.set_state_dict(opt.state_dict())
        want = {k: v.numpy() for k, v in net.state_dict().items()}
        for _ in range(2):
            fn(x, y)
        for k, v in target.state_dict().items():
            np.testing.assert_array_equal(v.numpy(), want[k])
        for v in opt2._pending_state.values():
            if isinstance(v, paddle.Tensor):
                v.numpy()

    def test_rng_state_saved_before_a_compiled_call_restores(self):
        """The generator's key is written state; a saved copy of it is
        the caller's, before and after it was set back."""
        paddle.seed(3)
        drop = nn.Dropout(0.5)
        fn = paddle.jit.to_static(lambda x: drop(x))
        x = paddle.to_tensor(np.ones((64,), "float32"))
        fn(x)                                     # discovery
        saved = paddle.get_rng_state()
        _, n = _donated_by(fn, x)
        assert n == 1                             # the key
        first = fn(x).numpy()
        outs = []
        for _ in range(2):
            paddle.set_rng_state(saved)
            fn(x)
            outs.append(fn(x).numpy())
        np.testing.assert_array_equal(outs[0], first)
        np.testing.assert_array_equal(outs[1], first)

    def test_model_average_survives_compiled_steps(self):
        net, _, step, x, y = _adamw_step()
        ma = paddle.incubate.ModelAverage(
            parameters=list(net[2].parameters()))
        fn = paddle.jit.to_static(step)
        seen = []
        for _ in range(4):
            fn(x, y)
            ma.step()
            seen.append(net[2].weight.numpy())
        with ma.apply():
            np.testing.assert_allclose(net[2].weight.numpy(),
                                       np.mean(seen, 0), rtol=1e-6)


class TestFitPipelineSurface:
    def test_surface_registered_with_default(self):
        from paddle_tpu.tuner import get_surface
        s = get_surface("fit_pipeline")
        assert s.default == {"prefetch_depth": 2, "steps_in_flight": 2}
        grid = s.grid({"bs": 8})
        assert grid[0] == s.default and len(grid) >= 4

    def test_fit_consults_tuning_cache(self):
        """knob resolution: explicit arg > cache > default (the
        serving-engine precedence)."""
        from paddle_tpu import tuner
        key = tuner.make_key("fit_pipeline", "bs4", "-",
                             tuner.backend_signature())
        tuner.get_cache().put(
            key, {"prefetch_depth": 4, "steps_in_flight": 3},
            median_ms=1.0, representative=False, source="search")
        try:
            m = _model(0)
            m.fit(_dataset(), batch_size=4, epochs=1, verbose=0,
                  shuffle=False)
            assert m._fit_pipeline == {"prefetch_depth": 4,
                                       "steps_in_flight": 3}
            # explicit arg wins over the cache
            m2 = _model(0)
            m2.fit(_dataset(), batch_size=4, epochs=1, verbose=0,
                   shuffle=False, prefetch_depth=1)
            assert m2._fit_pipeline == {"prefetch_depth": 1,
                                        "steps_in_flight": 3}
        finally:
            tuner.get_cache().discard(key)

    def test_default_when_cache_empty(self):
        m = _model(0)
        m.fit(_dataset(), batch_size=4, epochs=1, verbose=0,
              shuffle=False)
        assert m._fit_pipeline == {"prefetch_depth": 2,
                                   "steps_in_flight": 2}


class TestScalerInCompiledStep:
    def test_compiled_step_reads_live_loss_scale(self):
        """GradScaler's scale lives in device state: a compiled step
        traced at scale S must use the CURRENT scale after update()
        changes it — no re-trace, no stale constant."""
        from paddle_tpu.amp import GradScaler

        scaler = GradScaler(init_loss_scaling=4.0,
                            use_dynamic_loss_scaling=False)
        x = paddle.to_tensor(np.ones((2, 2), "float32"))

        @paddle.jit.to_static
        def scaled(x):
            return scaler.scale(x * 1.0)

        np.testing.assert_allclose(scaled(x).numpy(), 4.0 * np.ones((2, 2)))
        np.testing.assert_allclose(scaled(x).numpy(), 4.0 * np.ones((2, 2)))
        scaler.set_init_loss_scaling(16.0)
        # same compiled program, fresh scale read from state
        np.testing.assert_allclose(scaled(x).numpy(),
                                   16.0 * np.ones((2, 2)))

    def test_scale_preserves_low_precision_dtype(self):
        """fp16 loss in, fp16 scaled loss out — the device-state scale
        must not promote the mixed-precision graph to float32."""
        from paddle_tpu.amp import GradScaler
        import jax.numpy as jnp

        scaler = GradScaler(init_loss_scaling=4.0)
        loss = paddle.to_tensor(np.ones((2,), np.float16))
        scaled = scaler.scale(loss)
        assert scaled.dtype == jnp.float16
        np.testing.assert_allclose(scaled.numpy(),
                                   np.full((2,), 4.0, np.float16))

    def test_scale_grows_across_compiled_replays(self):
        """Dynamic growth must happen on COMPILED replays too: the
        good-step counter and the grow/shrink decision are traced
        device math, not python counters that only run on the trace.
        Regression: the scale used to freeze after the first compile."""
        from paddle_tpu.amp import GradScaler

        paddle.seed(0)
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        scaler = GradScaler(init_loss_scaling=2.0, incr_ratio=2.0,
                            incr_every_n_steps=3)

        def step(x, y):
            loss = ((net(x) - y) ** 2).mean()
            scaler.scale(loss).backward()
            scaler.step(opt)
            opt.clear_grad()
            return loss

        sf = paddle.jit.to_static(step)
        x = paddle.to_tensor(np.full((4, 2), 0.1, "float32"))
        y = paddle.to_tensor(np.zeros((4, 1), "float32"))
        for _ in range(6):          # 1 discovery + 5 compiled replays
            sf(x, y)
        assert sf.n_compiled_runs >= 4
        # two growth events (after steps 3 and 6): 2.0 -> 4.0 -> 8.0
        assert scaler.get_loss_scaling() == 8.0

    def test_scaler_train_step_skips_on_overflow(self):
        """unscale_'s found-inf check is a guarded branch under
        to_static: an inf gradient discards the compiled run and
        re-runs eagerly with correct skip semantics."""
        from paddle_tpu.amp import GradScaler

        paddle.seed(0)
        net = nn.Linear(2, 1)
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        scaler = GradScaler(init_loss_scaling=2.0)

        def step(x, y):
            loss = ((net(x) - y) ** 2).mean()
            scaler.scale(loss).backward()
            scaler.step(opt)
            opt.clear_grad()    # the documented compiled-step pattern
            return loss

        sf = paddle.jit.to_static(step)
        x = paddle.to_tensor(np.ones((4, 2), "float32"))
        y = paddle.to_tensor(np.zeros((4, 1), "float32"))
        w0 = net.weight.numpy().copy()
        sf(x, y)
        assert not np.allclose(net.weight.numpy(), w0)  # stepped
        w1 = net.weight.numpy().copy()
        bad = paddle.to_tensor(np.full((4, 2), np.inf, "float32"))
        sf(bad, y)                      # overflow: step skipped
        np.testing.assert_array_equal(net.weight.numpy(), w1)
        assert scaler.get_loss_scaling() < 2.0   # dynamic backoff
