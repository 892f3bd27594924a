"""End-to-end user script: train an MLP regressor with the paddle-shaped
API — Layer, DataLoader, AdamW + LR schedule + grad clip, eager backward,
then a to_static-compiled train step, checkpoint save/resume."""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.io import DataLoader, TensorDataset

from paddle_tpu.framework.compile_cache import ensure_compile_cache

ensure_compile_cache()
paddle.seed(42)

# synthetic regression task
N, D = 512, 16
w_true = np.random.RandomState(0).randn(D, 1).astype(np.float32)
X = np.random.RandomState(1).randn(N, D).astype(np.float32)
Y = X @ w_true + 0.01 * np.random.RandomState(2).randn(N, 1).astype(np.float32)

ds = TensorDataset([paddle.to_tensor(X), paddle.to_tensor(Y)])
loader = DataLoader(ds, batch_size=64, shuffle=True, drop_last=True)

model = nn.Sequential(nn.Linear(D, 64), nn.GELU(), nn.Linear(64, 1))
sched = paddle.optimizer.lr.CosineAnnealingDecay(1e-2, T_max=50)
opt = paddle.optimizer.AdamW(
    learning_rate=sched, parameters=model.parameters(),
    grad_clip=nn.ClipGradByGlobalNorm(1.0))
loss_fn = nn.MSELoss()

print("== eager training ==")
first = last = None
for epoch in range(5):
    for bx, by in loader:
        loss = loss_fn(model(bx), by)
        loss.backward()
        opt.step()
        opt.clear_grad()
        sched.step()
    v = float(loss.item())
    first = v if first is None else first
    last = v
    print(f"epoch {epoch} loss {v:.5f} lr {opt.get_lr():.5f}")
assert last < first / 5, f"loss did not drop: {first} -> {last}"

print("== to_static compiled step ==")


@paddle.jit.to_static
def train_step(bx, by):
    loss = loss_fn(model(bx), by)
    loss.backward()
    opt.step()
    opt.clear_grad()
    return loss


t0 = time.perf_counter()
losses = []
for epoch in range(5):
    for bx, by in loader:
        losses.append(float(train_step(bx, by).item()))
print(f"compiled 5 epochs in {time.perf_counter() - t0:.2f}s, "
      f"final loss {losses[-1]:.6f}")
assert losses[-1] <= last + 1e-3, "compiled step regressed the loss"

print("== checkpoint save / resume ==")
import tempfile

_ckpt = tempfile.mkdtemp(prefix="verify_mlp_")
paddle.save(model.state_dict(), f"{_ckpt}/model.pdparams")
paddle.save(opt.state_dict(), f"{_ckpt}/opt.pdopt")
model2 = nn.Sequential(nn.Linear(D, 64), nn.GELU(), nn.Linear(64, 1))
model2.set_state_dict(paddle.load(f"{_ckpt}/model.pdparams"))
pred1 = model(paddle.to_tensor(X[:4])).numpy()
pred2 = model2(paddle.to_tensor(X[:4])).numpy()
np.testing.assert_allclose(pred1, pred2, rtol=1e-6)
print("state_dict round-trip: predictions identical")
print("ALL OK")
