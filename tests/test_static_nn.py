"""Tests for paddle.static.nn + Program.capture/Executor.run replay
(SURVEY.md §2.2 `paddle.static` row)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import static


class TestStaticNN:
    def test_capture_run_and_param_persistence(self):
        paddle.seed(0)
        prog = static.Program()

        def net(feed):
            h = static.nn.fc(feed["x"], 16, activation="relu")
            out = static.nn.fc(h, 1)
            return {"out": out}

        prog.capture(net)
        exe = static.Executor()
        x = np.random.RandomState(0).randn(4, 8).astype("float32")
        r1 = exe.run(prog, feed={"x": x}, fetch_list=["out"])
        r2 = exe.run(prog, feed={"x": x}, fetch_list=["out"])
        # layer slots reused -> identical params -> identical outputs
        np.testing.assert_allclose(r1[0], r2[0])
        assert len(prog.parameters()) == 4  # 2x (weight, bias)

    def test_conv_bn_pipeline(self):
        paddle.seed(0)
        prog = static.Program()

        def net(feed):
            h = static.nn.conv2d(feed["img"], 4, 3, padding=1, act="relu")
            h = static.nn.batch_norm(h)
            out = static.nn.fc(h, 3)
            return {"out": out}

        prog.capture(net)
        exe = static.Executor()
        img = np.random.RandomState(1).randn(2, 3, 8, 8).astype("float32")
        out = exe.run(prog, feed={"img": img}, fetch_list=["out"])[0]
        assert out.shape == (2, 3)
        assert np.isfinite(out).all()

    def test_embedding_and_layer_norm(self):
        paddle.seed(0)
        prog = static.Program()

        def net(feed):
            e = static.nn.embedding(feed["ids"], size=[50, 8])
            h = static.nn.layer_norm(e, begin_norm_axis=2)
            return {"h": h}

        prog.capture(net)
        exe = static.Executor()
        ids = np.array([[1, 2], [3, 4]], "int64")
        h = exe.run(prog, feed={"ids": ids}, fetch_list=["h"])[0]
        assert h.shape == (2, 2, 8)
        np.testing.assert_allclose(h.mean(-1), 0.0, atol=1e-5)

    def test_training_via_program_parameters(self):
        paddle.seed(0)
        prog = static.Program()

        def net(feed):
            h = static.nn.fc(feed["x"], 8, activation="tanh")
            return {"y": static.nn.fc(h, 1)}

        prog.capture(net)
        exe = static.Executor()
        rng = np.random.RandomState(0)
        x = rng.randn(16, 4).astype("float32")
        target = rng.randn(16, 1).astype("float32")
        exe.run(prog, feed={"x": x}, fetch_list=["y"])  # init params
        opt = paddle.optimizer.SGD(0.1, parameters=prog.parameters())
        losses = []
        for _ in range(25):
            out = prog.build_fn({"x": x})["y"]
            loss = paddle.nn.functional.mse_loss(
                out, paddle.to_tensor(target))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        assert losses[-1] < losses[0] * 0.5

    def test_sequence_ops_documented_unsupported(self):
        with pytest.raises(NotImplementedError, match="out of TPU scope"):
            static.nn.sequence_expand(None, None)

    def test_plain_run_without_capture_raises(self):
        prog = static.Program()
        exe = static.Executor()
        with pytest.raises(RuntimeError, match="capture"):
            exe.run(prog, feed={}, fetch_list=[])


class TestStaticNnFilled:
    """Previously-raising static.nn rows (VERDICT round-1 item 8)."""

    def test_conv2d_transpose_derives_kernel_from_output_size(self):
        prog = static.Program()

        def net(feed):
            y = static.nn.conv2d_transpose(feed["x"], num_filters=2,
                                           output_size=16, stride=2,
                                           padding=1)
            return {"y": y}

        prog.capture(net)
        x = np.random.RandomState(0).randn(1, 3, 8, 8).astype("float32")
        (out,) = static.Executor().run(prog, feed={"x": x},
                                       fetch_list=["y"])
        # k = 16 - (8-1)*2 + 2*1 = 4 -> output exactly 16x16
        assert out.shape == (1, 2, 16, 16)

    def test_prelu_element_mode(self):
        prog = static.Program()

        def net(feed):
            return {"y": static.nn.prelu(feed["x"], mode="element")}

        prog.capture(net)
        x = np.array([[[-2.0, 4.0], [-6.0, 8.0]]], "float32")
        (out,) = static.Executor().run(prog, feed={"x": x},
                                       fetch_list=["y"])
        # alpha init 0.25: negatives scaled, positives passed through
        np.testing.assert_allclose(out, [[[-0.5, 4.0], [-1.5, 8.0]]])
        # one alpha per element (non-batch dims)
        (param,) = prog.parameters()
        assert list(param.shape) == [2, 2]


class TestPassManager:
    def test_delegated_passes_accepted(self):
        prog = static.Program()
        prog.capture(lambda feed: {"y": feed["x"] * 2})
        static.PassManager(["constant_folding",
                            "fuse_gemm_epilogue"]).apply(prog)
        assert prog._applied_passes == ["constant_folding",
                                        "fuse_gemm_epilogue"]
        x = np.ones((2, 2), "float32")
        (out,) = static.Executor().run(prog, feed={"x": x},
                                       fetch_list=["y"])
        np.testing.assert_allclose(out, 2.0)

    def test_unknown_pass_rejected(self):
        with pytest.raises(ValueError, match="unknown pass"):
            static.PassManager(["bogus_pass"])

    def test_amp_pass_rewrites_builder(self):
        prog = static.Program()

        def net(feed):
            h = static.nn.fc(feed["x"], 4)
            return {"y": h}

        prog.capture(net)
        static.PassManager(["auto_mixed_precision"]).apply(prog)
        x = np.random.RandomState(0).randn(2, 4).astype("float32")
        (out,) = static.Executor().run(prog, feed={"x": x},
                                       fetch_list=["y"])
        assert str(out.dtype) == "bfloat16"  # matmul ran under autocast
        # the registered custom-pass hook works end to end
        calls = []

        @static.register_pass("test_counting_pass")
        def counting(build):
            def wrapped(feed):
                calls.append(1)
                return build(feed)
            return wrapped

        static.PassManager(["test_counting_pass"]).apply(prog)
        static.Executor().run(prog, feed={"x": x}, fetch_list=["y"])
        assert calls == [1]
