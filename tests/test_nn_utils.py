"""Tests for paddle.nn.utils (weight_norm, spectral_norm,
parameters_to_vector, grad clipping) — SURVEY.md §2.2 `paddle.nn` row."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


class TestVectorize:
    def test_roundtrip(self):
        paddle.seed(0)
        lin = nn.Linear(3, 4)
        vec = nn.utils.parameters_to_vector(lin.parameters())
        assert vec.shape == [16]
        w0 = lin.weight.numpy().copy()
        nn.utils.vector_to_parameters(vec * 2.0, lin.parameters())
        np.testing.assert_allclose(lin.weight.numpy(), w0 * 2.0, rtol=1e-6)

    def test_size_mismatch_raises(self):
        lin = nn.Linear(2, 2)
        with pytest.raises(ValueError, match="elements"):
            nn.utils.vector_to_parameters(
                paddle.to_tensor(np.zeros(99, "float32")),
                lin.parameters())

    def test_vector_grad_flows(self):
        paddle.seed(0)
        lin = nn.Linear(2, 2)
        v = nn.utils.parameters_to_vector(lin.parameters())
        (v * v).sum().backward()
        assert lin.weight.grad is not None
        np.testing.assert_allclose(lin.weight.grad.numpy(),
                                   2 * lin.weight.numpy(), rtol=1e-5)


class TestClipValue:
    def test_clips_in_place(self):
        lin = nn.Linear(2, 2)
        (lin(paddle.to_tensor(np.full((1, 2), 100.0, "float32")))
         .sum() * 100.0).backward()
        nn.utils.clip_grad_value_(lin.parameters(), 1.0)
        for p in lin.parameters():
            assert np.abs(p.grad.numpy()).max() <= 1.0


class TestWeightNorm:
    def test_preserves_function_and_splits_params(self):
        paddle.seed(0)
        lin = nn.Linear(4, 3)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype("float32"))
        ref = lin(x).numpy()
        nn.utils.weight_norm(lin, "weight", dim=0)
        names = [n for n, _ in lin.named_parameters()]
        assert "weight_g" in names and "weight_v" in names
        assert "weight" not in names
        np.testing.assert_allclose(lin(x).numpy(), ref, atol=1e-5)

    def test_grad_flows_to_g_and_v(self):
        paddle.seed(0)
        lin = nn.Linear(4, 3)
        nn.utils.weight_norm(lin)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype("float32"))
        lin(x).sum().backward()
        g = dict(lin.named_parameters())
        assert g["weight_g"].grad is not None
        assert g["weight_v"].grad is not None
        assert np.isfinite(g["weight_v"].grad.numpy()).all()

    def test_training_with_weight_norm(self):
        paddle.seed(0)
        lin = nn.Linear(4, 1)
        nn.utils.weight_norm(lin)
        opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(16, 4).astype("float32"))
        y = paddle.to_tensor(rng.randn(16, 1).astype("float32"))
        losses = []
        for _ in range(20):
            loss = nn.functional.mse_loss(lin(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        assert losses[-1] < losses[0] * 0.7

    def test_remove_weight_norm(self):
        paddle.seed(0)
        lin = nn.Linear(4, 3)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype("float32"))
        nn.utils.weight_norm(lin)
        ref = lin(x).numpy()
        nn.utils.remove_weight_norm(lin)
        names = [n for n, _ in lin.named_parameters()]
        assert "weight" in names and "weight_g" not in names
        np.testing.assert_allclose(lin(x).numpy(), ref, atol=1e-5)

    def test_double_apply_raises(self):
        lin = nn.Linear(2, 2)
        nn.utils.weight_norm(lin)
        with pytest.raises(RuntimeError, match="already"):
            nn.utils.weight_norm(lin)


class TestSpectralNorm:
    def test_unit_spectral_radius(self):
        paddle.seed(0)
        lin = nn.Linear(6, 8)
        nn.utils.spectral_norm(lin, n_power_iterations=20)
        x = paddle.to_tensor(np.eye(6, dtype="float32"))
        lin(x)  # recompute via hook
        w = lin.weight.numpy()
        smax = np.linalg.svd(w, compute_uv=False).max()
        np.testing.assert_allclose(smax, 1.0, atol=1e-2)

    def test_grad_flows(self):
        paddle.seed(0)
        lin = nn.Linear(3, 3)
        nn.utils.spectral_norm(lin)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 3).astype("float32"))
        lin(x).sum().backward()
        g = dict(lin.named_parameters())
        assert g["weight_orig"].grad is not None

    def test_default_iterations_converge_across_forwards(self):
        # u must persist between calls: with n_power_iterations=1, sigma
        # converges over repeated forwards (torch/paddle semantics)
        paddle.seed(3)
        lin = nn.Linear(6, 8)
        nn.utils.spectral_norm(lin)  # default: 1 iteration
        x = paddle.to_tensor(np.eye(6, dtype="float32"))
        for _ in range(30):
            lin(x)
        smax = np.linalg.svd(lin.weight.numpy(), compute_uv=False).max()
        np.testing.assert_allclose(smax, 1.0, atol=1e-2)
