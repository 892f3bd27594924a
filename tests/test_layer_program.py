"""``jit.layer_program.LayerPrograms``: layers of one kind share one
compiled function of (inputs, parameters); its outputs and every gradient
match the layers called op by op, with and without recomputation, and a
kind's program neither keeps its template alive nor leaks the arguments
into the template's parameters."""

import gc
import weakref

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit.layer_program import LayerPrograms


class Block(nn.Layer):
    def __init__(self, width):
        super().__init__()
        self.fc1 = nn.Linear(width, 2 * width)
        self.fc2 = nn.Linear(2 * width, width)

    def forward(self, x):
        h = self.fc1(x)
        return x + self.fc2(paddle.nn.functional.gelu(h)), h.mean()


def _stack(n=3, width=8):
    paddle.seed(7)
    return [Block(width) for _ in range(n)]


def _run(blocks, x, programs=None, remat=False):
    aux = 0.0
    for b in blocks:
        if programs is None:
            x, a = b(x)
        else:
            x, a = programs(b, "block", x, remat=remat, n_outputs=2)
        aux = aux + a
    return (x * x).mean() + aux


@pytest.mark.parametrize("remat", [False, True])
def test_outputs_and_gradients_match_the_plain_layers(remat):
    blocks = _stack()
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8)
                         .astype("float32"))
    plain = _run(blocks, x)
    plain.backward()
    want = [p.grad.numpy().copy() for b in blocks for p in b.parameters()]
    for b in blocks:
        for p in b.parameters():
            p.clear_gradient()
    programs = LayerPrograms()
    got = _run(blocks, x, programs, remat=remat)
    got.backward()
    np.testing.assert_allclose(got.numpy(), plain.numpy(), rtol=1e-6)
    for g, w in zip((p.grad.numpy() for b in blocks
                     for p in b.parameters()), want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
    assert len(programs._fns) == 1      # one program for the three layers


def test_the_template_keeps_its_parameters_and_is_not_held():
    blocks = _stack(2)
    before = [p.numpy().copy() for p in blocks[0].parameters()]
    programs = LayerPrograms()
    x = paddle.ones([2, 8])
    _run(blocks, x, programs)
    for p, b in zip(blocks[0].parameters(), before):
        np.testing.assert_array_equal(p.numpy(), b)
    ref = weakref.ref(blocks[0])
    del blocks, p
    gc.collect()
    assert ref() is None
