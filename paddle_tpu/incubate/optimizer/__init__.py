"""``paddle.incubate.optimizer`` — LookAhead / ModelAverage
(python/paddle/incubate/optimizer/ parity, UNVERIFIED: lookahead.py,
modelaverage.py).

Both are wrapper optimizers over an inner optimizer: LookAhead blends
slow/fast weights every k steps; ModelAverage keeps a running average of
parameters applied at eval time."""

from __future__ import annotations

import jax.numpy as jnp

from ...framework.core import Tensor, no_grad
from ...optimizer.optimizer import Optimizer

__all__ = ["LookAhead", "ModelAverage"]


class LookAhead(Optimizer):
    """k fast steps with the inner optimizer, then pull the slow weights
    toward the fast ones: slow += alpha * (fast - slow); fast = slow."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5, name=None):
        self._inner = inner_optimizer
        self.alpha = float(alpha)
        self.k = max(int(k), 1)
        self._step_count = 0
        self._slow: dict[int, jnp.ndarray] = {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        self._inner.step()
        self._step_count += 1
        if self._step_count % self.k:
            return
        with no_grad():
            for p in self._inner._parameter_list:
                slow = self._slow.get(id(p))
                if slow is None:
                    slow = p._data.astype(jnp.float32)
                slow = slow + self.alpha * (
                    p._data.astype(jnp.float32) - slow)
                self._slow[id(p)] = slow
                p.set_data(slow.astype(p.dtype))

    def minimize(self, loss, *a, **k):
        out = self._inner.minimize(loss, *a, **k)
        return out

    def clear_grad(self, set_to_zero=False):
        self._inner.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def state_dict(self):
        sd = self._inner.state_dict()
        sd["@lookahead_step"] = self._step_count
        return sd

    def set_state_dict(self, state):
        self._step_count = int(state.pop("@lookahead_step", 0))
        self._inner.set_state_dict(state)


class ModelAverage(Optimizer):
    """Maintains sum of parameter values over steps; ``apply()`` swaps in
    the average (eval), ``restore()`` swaps back (paddle's
    min/max_average_window control when the accumulator restarts)."""

    def __init__(self, average_window_rate=0.15, parameters=None,
                 min_average_window=10000, max_average_window=10000,
                 name=None):
        super().__init__(0.0, parameters, None, None, name)
        self.rate = float(average_window_rate)
        self.min_w = int(min_average_window)
        self.max_w = int(max_average_window)
        self._sum: dict[int, jnp.ndarray] = {}
        self._num = 0
        self._backup: dict[int, jnp.ndarray] | None = None

    def step(self):
        with no_grad():
            # the sums are buffers of their own, never a parameter's
            # (astype to the same dtype aliases): a compiled step donates
            # the parameter's
            for p in self._parameter_list:
                acc = self._sum.get(id(p))
                self._sum[id(p)] = \
                    jnp.array(p._data, jnp.float32) if acc is None \
                    else acc + p._data.astype(jnp.float32)
        self._num += 1
        # restart the window once it outgrows max_average_window
        if self._num > self.max_w and self._num > self.min_w:
            for p in self._parameter_list:
                self._sum[id(p)] = jnp.array(p._data, jnp.float32)
            self._num = 1

    def apply(self, executor=None, need_restore=True):
        """Swap averaged weights in (context-manager friendly)."""
        self._backup = {}
        with no_grad():
            for p in self._parameter_list:
                self._backup[id(p)] = p._data
                acc = self._sum.get(id(p))
                if acc is not None and self._num:
                    p.set_data((acc / self._num).astype(p.dtype))
        import contextlib

        @contextlib.contextmanager
        def ctx():
            try:
                yield
            finally:
                if need_restore:
                    self.restore()
        return ctx()

    def restore(self, executor=None):
        if self._backup:
            for p in self._parameter_list:
                b = self._backup.get(id(p))
                if b is not None:
                    p.set_data(b)
        self._backup = None


from ...optimizer.optimizer import LBFGS  # noqa: E402 — re-export (upstream
# incubate.optimizer.LBFGS graduated to paddle.optimizer; both paths work)
from ...optimizer import Lamb as _Lamb  # noqa: E402


class DistributedFusedLamb(_Lamb):
    """paddle.incubate.DistributedFusedLamb parity. The reference fuses
    multi-tensor LAMB kernels and shards optimizer states across the data
    group by hand; here XLA fuses the update and state sharding comes
    from wrapping with ``fleet.distributed_optimizer`` / GSPMD — so this
    IS Lamb, keeping the extra constructor knobs for signature parity."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 clip_after_allreduce=True, is_grad_scaled_by_nranks=True,
                 alignment=128, use_master_param_norm=True,
                 gradient_accumulation_steps=1, use_master_acc_grad=True,
                 nproc_per_node=None, use_hierarchical_allreduce=False,
                 name=None):
        super().__init__(learning_rate=learning_rate,
                         lamb_weight_decay=lamb_weight_decay,
                         beta1=beta1, beta2=beta2, epsilon=epsilon,
                         parameters=parameters, grad_clip=grad_clip,
                         exclude_from_weight_decay_fn=(
                             exclude_from_weight_decay_fn))


__all__ += ["LBFGS", "DistributedFusedLamb"]


from . import functional  # noqa: E402,F401

__all__ += ["functional"]
