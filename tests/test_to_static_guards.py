"""to_static guarded specialization (the SOT role, SURVEY.md §3.5):
data-dependent python control flow on scalars stays COMPILED via
discovery-recorded branch decisions replayed as constants + runtime
guards; unguardable float pulls break the graph with a warning; .grad
reads after a compiled step warn (documented divergence)."""

import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def _pos():
    return paddle.to_tensor(np.array([1.0, 2.0], "float32"))


def _neg():
    return paddle.to_tensor(np.array([-1.0, -2.0], "float32"))


class TestGuardedSpecialization:
    def test_scalar_branch_compiles(self):
        calls = {"n": 0}

        @paddle.jit.to_static
        def f(x):
            calls["n"] += 1          # python side effect: traces only
            y = x * 2
            if y.sum() > 0:          # Tensor.__bool__ -> guarded
                return y + 1
            return y - 1

        x = _pos()
        np.testing.assert_allclose(f(x).numpy(), [3.0, 5.0])   # discovery
        np.testing.assert_allclose(f(x).numpy(), [3.0, 5.0])   # compiled
        np.testing.assert_allclose(f(x).numpy(), [3.0, 5.0])
        # compiled runs don't re-execute python: discovery + one trace
        assert calls["n"] == 2
        assert not f._fallback_sigs
        (entry,) = f._graphs.values()
        assert len(entry.by_key) == 1

    def test_branch_flip_respecializes_correctly(self):
        @paddle.jit.to_static
        def f(x):
            y = x * 2
            if y.sum() > 0:
                return y + 1
            return y - 1

        pos, neg = _pos(), _neg()
        f(pos)
        f(pos)                                   # compiled spec A
        np.testing.assert_allclose(f(neg).numpy(), [-3.0, -5.0])  # flip
        np.testing.assert_allclose(f(neg).numpy(), [-3.0, -5.0])  # spec B
        np.testing.assert_allclose(f(pos).numpy(), [3.0, 5.0])    # flip
        np.testing.assert_allclose(f(pos).numpy(), [3.0, 5.0])    # cached A
        (entry,) = f._graphs.values()
        assert len(entry.by_key) == 2            # one per branch pattern
        assert not f._fallback_sigs

    def test_int_concretization_guarded(self):
        @paddle.jit.to_static
        def f(x, idx):
            k = int(idx)             # device int -> baked + guarded
            return x * k

        x = _pos()
        two = paddle.to_tensor(np.int64(2))
        three = paddle.to_tensor(np.int64(3))
        np.testing.assert_allclose(f(x, two).numpy(), [2.0, 4.0])
        np.testing.assert_allclose(f(x, two).numpy(), [2.0, 4.0])
        np.testing.assert_allclose(f(x, three).numpy(), [3.0, 6.0])
        assert not f._fallback_sigs

    def test_float_pull_breaks_graph_with_warning(self):
        @paddle.jit.to_static
        def g(x):
            return x * float(x.sum())   # fed back into tensors: unguardable

        x = _pos()
        with pytest.warns(UserWarning, match="graph break"):
            out = g(x)
        np.testing.assert_allclose(out.numpy(), [3.0, 6.0])
        np.testing.assert_allclose(g(x).numpy(), [3.0, 6.0])  # eager
        assert len(g._fallback_sigs) == 1

    def test_float_branch_breaks_graph(self):
        @paddle.jit.to_static
        def g(x):
            s = x.sum().item()
            if s > 0:                   # branching on the read: unguardable
                return x + 1
            return x - 1

        with pytest.warns(UserWarning, match="graph break"):
            out = g(_pos())
        np.testing.assert_allclose(out.numpy(), [2.0, 3.0])
        assert len(g._fallback_sigs) == 1

    def test_observed_float_logging_stays_compiled(self):
        """SOT-style partial capture: loss.item() used only for logging /
        returning does NOT break the graph — the matmuls stay compiled
        (python runs only at discovery+trace), and the RETURNED float is
        fresh every call (emitted as a program output, synced on read)."""
        host_log = []
        calls = {"n": 0}

        @paddle.jit.to_static
        def step(x, w):
            calls["n"] += 1
            y = x @ w                     # the compute that must compile
            loss = (y * y).sum()
            f = loss.item()               # observation-only read
            host_log.append(f)            # logged (side effect at trace)
            return y, f

        rng = np.random.RandomState(0)
        w = paddle.to_tensor(rng.randn(4, 4).astype("float32"))
        x1 = paddle.to_tensor(rng.randn(2, 4).astype("float32"))
        x2 = paddle.to_tensor(rng.randn(2, 4).astype("float32"))

        with warnings.catch_warnings():
            warnings.simplefilter("error")   # any graph-break warns -> fail
            y1, f1 = step(x1, w)             # discovery
            y1b, f1b = step(x1, w)           # compiled
            y2, f2 = step(x2, w)             # compiled, same signature
        assert not step._fallback_sigs       # did NOT fall back to eager
        (entry,) = step._graphs.values()
        assert len(entry.by_key) == 1        # one compiled specialization
        # compiled runs execute no python: discovery + one trace
        assert calls["n"] == 2
        # the returned float is FRESH each call, not the baked trace value
        exp1 = float((np.asarray(x1.numpy()) @ np.asarray(w.numpy()))
                     .astype(np.float32).__pow__(2).sum())
        exp2 = float((np.asarray(x2.numpy()) @ np.asarray(w.numpy()))
                     .astype(np.float32).__pow__(2).sum())
        np.testing.assert_allclose([f1, f1b, f2], [exp1, exp1, exp2],
                                   rtol=1e-5)

    def test_observed_float_arithmetic_return_fresh(self):
        """Derived values (f * scale) returned from the step mirror onto
        the traced scalar and stay fresh per call."""
        @paddle.jit.to_static
        def step(x):
            return 2.0 * x.sum().item() + 1.0

        a = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
        b = paddle.to_tensor(np.array([5.0, 2.0], "float32"))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert step(a) == 7.0        # discovery
            assert step(a) == 7.0        # compiled
            assert step(b) == 15.0       # compiled, fresh value
        assert not step._fallback_sigs

    @pytest.mark.slow  # ~7s (8 recompiles by design): fast-gate budget
    def test_unstable_branch_gives_up(self):
        @paddle.jit.to_static
        def f(x):
            if x.sum() > 0:
                return x + 1
            return x - 1

        pos, neg = _pos(), _neg()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(40):       # alternate forever
                np.testing.assert_allclose(f(pos).numpy(), [2.0, 3.0])
                np.testing.assert_allclose(f(neg).numpy(), [-2.0, -3.0])
        assert any("re-specialized" in str(x.message) for x in w)
        assert len(f._fallback_sigs) == 1

    def test_guarded_train_step_state_committed_once(self):
        """A guarded mispredicted run must not commit state: train the
        same model with eager and compiled+flipping-branch loops and
        assert identical losses."""
        x1 = paddle.to_tensor(
            np.random.RandomState(0).randn(8, 4).astype("float32"))
        y1 = paddle.to_tensor(
            np.random.RandomState(1).randn(8, 1).astype("float32"))

        def make_step(model, opt, compiled):
            loss_fn = nn.MSELoss()

            def step(x, y, flip):
                pred = model(x)
                loss = loss_fn(pred, y)
                if flip.sum() > 0:     # guarded branch inside the step
                    loss = loss * 1.0
                else:
                    loss = loss * 1.0
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            return paddle.jit.to_static(step) if compiled else step

        def run(compiled):
            paddle.seed(3)
            model = nn.Linear(4, 1)
            opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
            step = make_step(model, opt, compiled)
            out = []
            for i in range(6):
                flip = paddle.to_tensor(
                    np.array([1.0 if i % 2 else -1.0], "float32"))
                out.append(float(step(x1, y1, flip).item()))
            return out

        np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


class TestGradStaleWarning:
    def test_grad_read_after_compiled_step_warns(self):
        paddle.seed(0)
        model = nn.Linear(4, 1)
        opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
        loss_fn = nn.MSELoss()
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(8, 4).astype("float32"))
        y = paddle.to_tensor(
            np.random.RandomState(1).randn(8, 1).astype("float32"))

        @paddle.jit.to_static
        def step(x, y):
            loss = loss_fn(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step(x, y)      # discovery (eager)
        step(x, y)      # compiled — grads consumed inside the program
        with pytest.warns(UserWarning, match="stale"):
            _ = model.weight.grad

    def test_eager_grad_read_does_not_warn(self):
        paddle.seed(0)
        model = nn.Linear(4, 1)
        loss = nn.MSELoss()(model(_pos().reshape((1, 2)).tile((1, 2))),
                            paddle.to_tensor(np.zeros((1, 1), "float32")))
        loss.backward()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert model.weight.grad is not None


# --------------------------------------------------------------------------
# compile-around-break: broken signatures run as compiled SEGMENTS
# --------------------------------------------------------------------------

def test_compile_around_break_segments():
    """A genuine graph break (branching on float(loss)) no longer drops
    the signature to per-op eager: the function runs as jit-compiled
    segments split at the break — the matmul regions on BOTH sides
    execute inside compiled programs (probe: segment stats)."""
    rng = np.random.RandomState(0)
    w1 = paddle.to_tensor(rng.randn(8, 8).astype(np.float32))
    w2 = paddle.to_tensor(rng.randn(8, 8).astype(np.float32))
    x_np = rng.randn(4, 8).astype(np.float32)

    def raw(x):
        h = paddle.matmul(x, w1)
        s = float(h.sum())            # unguardable: float() branched on
        if s > 0:
            y = paddle.matmul(h, w2)
        else:
            y = paddle.matmul(h, w2) * 2.0
        return y.sum()

    fn = paddle.jit.to_static(raw)
    x = paddle.to_tensor(x_np)
    with pytest.warns(UserWarning, match="graph break|concretization"):
        out1 = float(fn(x).item())     # discovery: registers the break
    out2 = float(fn(x).item())         # segmented execution
    ref = float(raw(x).item())
    assert abs(out1 - ref) < 1e-5 and abs(out2 - ref) < 1e-5
    segs, ops = fn._segment_stats
    # at least the prefix (matmul 1 + sum, flushed at float()) and the
    # suffix (matmul 2 + sum, flushed at the output read)
    assert segs >= 2, (segs, ops)
    assert ops >= 3, (segs, ops)


def test_compile_around_break_train_step():
    """A full train step (backward + optimizer) with a float(loss)
    branch mid-step still trains to the same losses as eager, running
    as compiled segments (the backward tape is recorded and flushed
    compiled too)."""
    x_np = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    y_np = np.random.RandomState(1).randn(8, 1).astype(np.float32)

    def make():
        paddle.seed(3)
        model = paddle.nn.Linear(6, 1)
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
        return model, opt

    def body(model, opt, x, y):
        pred = model(x)
        loss = ((pred - y) ** 2).mean()
        lv = float(loss)               # the break
        scale = 1.0 if lv > 0 else 2.0
        (loss * scale).backward()
        opt.step()
        opt.clear_grad()
        return loss

    # eager oracle
    model_e, opt_e = make()
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    ref = [float(body(model_e, opt_e, x, y).item()) for _ in range(3)]

    model_s, opt_s = make()
    step = paddle.jit.to_static(
        lambda x, y: body(model_s, opt_s, x, y))
    with pytest.warns(UserWarning):
        losses = [float(step(x, y).item())]
    losses += [float(step(x, y).item()) for _ in range(2)]
    np.testing.assert_allclose(losses, ref, rtol=1e-5, atol=1e-6)
    segs, ops = step._segment_stats
    assert segs >= 2, (segs, ops)


def test_segmented_outputs_are_plain_arrays():
    """Tensors escaping a segmented call must carry real arrays — a
    comparison on the returned loss (outside segment mode) must work."""
    w = paddle.to_tensor(np.random.RandomState(0).randn(4, 4)
                         .astype(np.float32))

    def f(x):
        h = paddle.matmul(x, w)
        if float(h.sum()) > -1e30:
            return (h * 2).sum()
        return h.sum()

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 4)
                         .astype(np.float32))
    with pytest.warns(UserWarning):
        sf(x)
    out = sf(x)                      # segmented
    cmp = out > 0                    # must not crash
    assert cmp.dtype == paddle.bool if hasattr(paddle, "bool") \
        else np.asarray(cmp._data).dtype == np.bool_


def test_segment_unsafe_op_retries_eager():
    """A broken signature whose function uses an op that consumes raw
    arrays outside the apply() funnel (paddle.any here) cannot carry
    lazy segments — the call must roll back cleanly, retry fully eager
    with CORRECT results, and remember the signature."""
    w = paddle.to_tensor(np.random.RandomState(0).randn(4, 4)
                         .astype(np.float32))

    def f(x):
        h = paddle.matmul(x, w)
        if float(h.sum()) > -1e30:
            flag = paddle.any(h > 0).astype("float32")
            return h.sum() + flag
        return h.sum()

    sf = paddle.jit.to_static(f)
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 4)
                         .astype(np.float32))
    ref = float(f(x).item())
    with pytest.warns(UserWarning):
        a = float(sf(x).item())        # discovery: registers the break
    with pytest.warns(UserWarning, match="eagerly"):
        b = float(sf(x).item())        # segment attempt -> eager retry
    c = float(sf(x).item())            # remembered: straight eager
    assert abs(a - ref) < 1e-5 and abs(b - ref) < 1e-5 \
        and abs(c - ref) < 1e-5
