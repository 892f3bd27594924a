"""Optimizer base + the standard family
(python/paddle/optimizer/ parity, UNVERIFIED).

Update math runs as jax ops on the wrapped arrays; under
``paddle_tpu.jit.to_static`` the whole step (grads → clip → update) traces
into the compiled program, which is where XLA fuses it into the fused
multi-tensor-apply the reference implements by hand (SURVEY.md §3.2 step 4).
Accumulators are persistable Tensors so the functionalizer captures them.
Master weights: when a parameter is low-precision (bf16/fp16), Adam-family
optimizers keep an fp32 master copy (paddle `multi_precision`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, Parameter, no_grad, is_floating
from .lr import LRScheduler
from .clip import ClipGradBase

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax",
           "Adagrad", "Adadelta", "RMSProp", "Lamb", "LBFGS"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=True):
        if parameters is None:
            raise ValueError(
                "parameters must be given in dygraph mode "
                "(pass model.parameters())")
        self._parameter_list = list(parameters)
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            self._param_groups = self._parameter_list
            flat = []
            for g in self._param_groups:
                flat.extend(g["params"])
            self._parameter_list = flat
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._weight_decay = weight_decay
        self._multi_precision = multi_precision
        self._accumulators: dict[str, dict[int, Tensor]] = {}
        self._master_weights: dict[int, Tensor] = {}
        # the step counter lives in a persistable device scalar (like
        # _lr_state below) so a to_static-compiled train step advances
        # it INSIDE the compiled program — a python int would only tick
        # on the discovery run and checkpoints saved after N compiled
        # steps would record step 1. The _step_count property keeps the
        # eager-facing int surface (state_dict "@step", tests).
        self._step_state = Tensor(jnp.asarray(0, jnp.int32))
        self._step_state.persistable = True
        self._step_state.name = "@step_state"
        # checkpoint loaded before the first step(): accumulators are lazy,
        # so stash the state and apply it as they get created
        self._pending_state: dict | None = None
        # lr lives in a persistable scalar so a to_static-compiled train
        # step reads the CURRENT lr as state input instead of baking the
        # trace-time value; scheduler.step() outside the compiled region
        # refreshes it (the jax-idiomatic "lr is part of opt state")
        self._lr_state = Tensor(jnp.asarray(self.get_lr(), jnp.float32))
        self._lr_state.persistable = True
        self._lr_state.name = "learning_rate"
        if isinstance(self._learning_rate, LRScheduler):
            self._learning_rate._bind(self)

    # -- lr ---------------------------------------------------------------

    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float) -> None:
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when a LRScheduler is in use")
        self._learning_rate = value
        self._sync_lr_state(value)

    def _sync_lr_state(self, value: float) -> None:
        from ..framework.core import trace_clean
        if trace_clean():
            self._lr_state.set_data(jnp.asarray(value, jnp.float32))

    # -- accumulators ------------------------------------------------------

    def _param_key(self, p: Tensor) -> str:
        if not hasattr(self, "_id2name"):
            self._id2name = {id(q): (q.name or f"param_{i}")
                             for i, q in enumerate(self._parameter_list)}
        return self._id2name.get(id(p), str(id(p)))

    def _acc(self, name: str, p: Tensor, init=None, dtype=None):
        store = self._accumulators.setdefault(name, {})
        key = id(p)
        if key not in store:
            data = jnp.zeros(p._data.shape, dtype or jnp.float32) \
                if init is None else init
            pending = (self._pending_state or {}).get(
                f"{self._param_key(p)}_{name}")
            if pending is not None:
                data = pending._data if isinstance(pending, Tensor) \
                    else jnp.asarray(pending)
            t = Tensor(data)
            t.persistable = True
            t.name = f"{self._param_key(p)}_{name}"
            store[key] = t
        return store[key]

    def _master(self, p: Tensor):
        """fp32 master weight for low-precision params."""
        if not self._multi_precision or p.dtype == jnp.float32 \
                or not is_floating(p.dtype):
            return None
        key = id(p)
        if key not in self._master_weights:
            data = p._data.astype(jnp.float32)
            pending = (self._pending_state or {}).get(
                f"{self._param_key(p)}_master")
            if pending is not None:
                data = pending._data if isinstance(pending, Tensor) \
                    else jnp.asarray(pending)
            t = Tensor(data)
            t.persistable = True
            self._master_weights[key] = t
        return self._master_weights[key]

    # -- step --------------------------------------------------------------

    def _collect_params_grads(self):
        from ..framework.segment import SegValue
        pgs = []
        for p in self._parameter_list:
            if not getattr(p, "trainable", True):
                continue
            g = p.grad
            if g is not None and isinstance(g._data, SegValue):
                # compile-around-break path: the backward tape was
                # recorded lazily; materialize every pending grad in ONE
                # flushed segment before the raw-jnp update math (which
                # cannot consume placeholders)
                g._data = g._data.force()
            pgs.append((p, g))
        return pgs

    def _decay_grad(self, p, gd):
        """Fold coupled weight decay into a raw grad array. Handles scalar
        coefficients and ``paddle.regularizer`` objects; a per-parameter
        regularizer attached via ParamAttr takes precedence over the
        optimizer-level ``weight_decay`` (paddle semantics)."""
        from ..regularizer import WeightDecayRegularizer
        wd = getattr(p, "regularizer", None)
        if wd is None:
            wd = self._weight_decay
        if wd is None or wd == 0.0:
            return gd
        pd = p._data.astype(gd.dtype)
        if isinstance(wd, WeightDecayRegularizer):
            return wd(pd, gd)
        coeff = float(wd) if not isinstance(wd, (list, tuple)) \
            else float(wd[0])
        return gd + coeff * pd

    def _apply_decay(self, p, g, lr):
        """L2 regularization folded into grad (paddle weight_decay on
        non-AdamW optimizers)."""
        return Tensor(self._decay_grad(p, g._data))

    def _lr_array(self):
        """Scalar lr used by update math. Outside a trace it is refreshed
        from the scheduler; inside a trace it is read as state, so compiled
        steps see per-call lr."""
        from ..framework.core import trace_clean
        if trace_clean():
            self._lr_state.set_data(jnp.asarray(self.get_lr(), jnp.float32))
        return self._lr_state.jax()

    @property
    def _step_count(self) -> int:
        st = self.__dict__.get("_step_state")
        if st is None:     # wrapper optimizers (LookAhead) that skip
            return self.__dict__.get("_step_count_py", 0)  # __init__
        return int(np.asarray(st._data))

    @_step_count.setter
    def _step_count(self, value) -> None:
        st = self.__dict__.get("_step_state")
        if st is None:
            self.__dict__["_step_count_py"] = int(value)
        else:
            st.set_data(jnp.asarray(int(value), jnp.int32))

    def step(self) -> None:
        with no_grad():
            pgs = [(p, g) for p, g in self._collect_params_grads()
                   if g is not None]
            if self._grad_clip is not None:
                pgs = self._grad_clip(pgs)
            lr = self._lr_array()
            for p, g in pgs:
                self._update_param(p, g, lr)
        # device-side increment, NOT the python property: inside a
        # compiled trace this must stay a traced op (int(tracer) would
        # be a per-step guard that mispredicts every call)
        self._step_state.set_data(self._step_state.jax() + 1)

    def _update_param(self, p: Tensor, g: Tensor, lr: float) -> None:
        raise NotImplementedError

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    def clear_grad(self, set_to_zero: bool = False) -> None:
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    # -- state dict --------------------------------------------------------

    def state_dict(self) -> dict:
        sd = {}
        for store in self._accumulators.values():
            for t in store.values():
                sd[t.name] = t
        for pid, t in self._master_weights.items():
            # master weights are keyed by param
            name = next((f"{self._param_key(p)}_master"
                         for p in self._parameter_list if id(p) == pid),
                        f"{pid}_master")
            sd[name] = t
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["@step"] = self._step_count
        return sd

    def set_state_dict(self, state: dict) -> None:
        """Restore optimizer state. Accumulators are created lazily at the
        first step, so state for not-yet-created slots is stashed and
        applied on creation (resume-before-first-step works). Values are
        COPIED now — state_dict() hands out live tensors, and the source
        optimizer may keep stepping before our slots materialize (a
        compiled step donates, and so deletes, the buffers it held)."""
        def value(src):
            return jnp.array(src._data if isinstance(src, Tensor) else src)

        self._pending_state = {
            k: (Tensor(value(v)) if isinstance(v, Tensor) else v)
            for k, v in state.items()}
        for store in self._accumulators.values():
            for t in store.values():
                if t.name in state:
                    t.set_data(value(state[t.name]))
        for pid, t in self._master_weights.items():
            name = next((f"{self._param_key(p)}_master"
                         for p in self._parameter_list if id(p) == pid),
                        None)
            if name and name in state:
                t.set_data(value(state[name]))
        if "LR_Scheduler" in state and isinstance(self._learning_rate,
                                                  LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        self._step_count = state.get("@step", self._step_count)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data)
        m = self._master(p)
        if m is not None:
            new = m._data - lr * gd.astype(jnp.float32)
            m.set_data(new)
            p.set_data(new.astype(p.dtype))
        else:
            p.set_data(p._data - (lr * gd).astype(p.dtype))


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        vel = self._acc("velocity", p)
        v = self._momentum * vel._data + gd
        vel.set_data(v)
        if self._nesterov:
            upd = gd + self._momentum * v
        else:
            upd = v
        m = self._master(p)
        if m is not None:
            new = m._data - lr * upd
            m.set_data(new)
            p.set_data(new.astype(p.dtype))
        else:
            p.set_data((p._data.astype(jnp.float32) - lr *
                        upd).astype(p.dtype))


class _AdamBase(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=True,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _adam_update(self, p, g, lr, decoupled_wd=0.0, apply_l2=True):
        gd = g._data.astype(jnp.float32)
        if apply_l2 and not decoupled_wd:
            gd = self._decay_grad(p, gd)
        m_t = self._acc("moment1", p)
        v_t = self._acc("moment2", p)
        b1p = self._acc("beta1_pow", p,
                        init=jnp.asarray(1.0, jnp.float32))
        b2p = self._acc("beta2_pow", p,
                        init=jnp.asarray(1.0, jnp.float32))
        b1 = self._beta1() if callable(self._beta1) else self._beta1
        b2 = self._beta2() if callable(self._beta2) else self._beta2
        m = b1 * m_t._data + (1 - b1) * gd
        v = b2 * v_t._data + (1 - b2) * jnp.square(gd)
        b1_pow = b1p._data * b1
        b2_pow = b2p._data * b2
        m_t.set_data(m)
        v_t.set_data(v)
        b1p.set_data(b1_pow)
        b2p.set_data(b2_pow)
        m_hat = m / (1 - b1_pow)
        v_hat = v / (1 - b2_pow)
        master = self._master(p)
        base = master._data if master is not None else \
            p._data.astype(jnp.float32)
        if decoupled_wd:
            base = base * (1.0 - lr * decoupled_wd)
        new = base - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        if master is not None:
            master.set_data(new)
        p.set_data(new.astype(p.dtype))


class Adam(_AdamBase):
    def _update_param(self, p, g, lr):
        self._adam_update(p, g, lr)


class AdamW(_AdamBase):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name)
        self._coeff = weight_decay
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _update_param(self, p, g, lr):
        decay = self._coeff
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            decay = 0.0
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        self._adam_update(p, g, lr, decoupled_wd=decay, apply_l2=False)


class Adamax(_AdamBase):
    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        m_t = self._acc("moment", p)
        u_t = self._acc("inf_norm", p)
        b1p = self._acc("beta1_pow", p, init=jnp.asarray(1.0, jnp.float32))
        m = self._beta1 * m_t._data + (1 - self._beta1) * gd
        u = jnp.maximum(self._beta2 * u_t._data, jnp.abs(gd))
        b1_pow = b1p._data * self._beta1
        m_t.set_data(m)
        u_t.set_data(u)
        b1p.set_data(b1_pow)
        master = self._master(p)
        base = master._data if master is not None else \
            p._data.astype(jnp.float32)
        new = base - lr / (1 - b1_pow) * m / (u + self._epsilon)
        if master is not None:
            master.set_data(new)
        p.set_data(new.astype(p.dtype))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        acc = self._acc("moment", p,
                        init=jnp.full(p._data.shape, self._init_acc,
                                      jnp.float32))
        a = acc._data + jnp.square(gd)
        acc.set_data(a)
        p.set_data((p._data.astype(jnp.float32) -
                    lr * gd / (jnp.sqrt(a) + self._epsilon)).astype(p.dtype))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._rho = rho

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        avg_sq = self._acc("avg_squared_grad", p)
        avg_up = self._acc("avg_squared_update", p)
        asg = self._rho * avg_sq._data + (1 - self._rho) * jnp.square(gd)
        upd = jnp.sqrt(avg_up._data + self._epsilon) / \
            jnp.sqrt(asg + self._epsilon) * gd
        asu = self._rho * avg_up._data + (1 - self._rho) * jnp.square(upd)
        avg_sq.set_data(asg)
        avg_up.set_data(asu)
        p.set_data((p._data.astype(jnp.float32) - lr * upd).astype(p.dtype))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        ms = self._acc("mean_square", p)
        mom = self._acc("momentum", p)
        new_ms = self._rho * ms._data + (1 - self._rho) * jnp.square(gd)
        ms.set_data(new_ms)
        if self._centered:
            mg = self._acc("mean_grad", p)
            new_mg = self._rho * mg._data + (1 - self._rho) * gd
            mg.set_data(new_mg)
            denom = jnp.sqrt(new_ms - jnp.square(new_mg) + self._epsilon)
        else:
            denom = jnp.sqrt(new_ms + self._epsilon)
        v = self._momentum * mom._data + lr * gd / denom
        mom.set_data(v)
        p.set_data((p._data.astype(jnp.float32) - v).astype(p.dtype))


class Lamb(_AdamBase):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 name=None, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, name=name)
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_param(self, p, g, lr):
        gd = g._data.astype(jnp.float32)
        m_t = self._acc("moment1", p)
        v_t = self._acc("moment2", p)
        b1p = self._acc("beta1_pow", p, init=jnp.asarray(1.0, jnp.float32))
        b2p = self._acc("beta2_pow", p, init=jnp.asarray(1.0, jnp.float32))
        m = self._beta1 * m_t._data + (1 - self._beta1) * gd
        v = self._beta2 * v_t._data + (1 - self._beta2) * jnp.square(gd)
        b1_pow, b2_pow = b1p._data * self._beta1, b2p._data * self._beta2
        m_t.set_data(m); v_t.set_data(v)
        b1p.set_data(b1_pow); b2p.set_data(b2_pow)
        m_hat = m / (1 - b1_pow)
        v_hat = v / (1 - b2_pow)
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        pf = p._data.astype(jnp.float32)
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon) + wd * pf
        w_norm = jnp.linalg.norm(pf)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        p.set_data((pf - lr * trust * r).astype(p.dtype))


class LBFGS(Optimizer):
    """Accepted for API parity; performs plain gradient descent with line
    search omitted (full L-BFGS is a later-phase item, rarely used in the
    baseline workloads)."""

    def __init__(self, learning_rate=1.0, max_iter=20, parameters=None,
                 **kw):
        super().__init__(learning_rate, parameters, None, None, None)

    def step(self, closure=None):
        loss = None
        if closure is not None:
            loss = closure()
        with no_grad():
            for p, g in self._collect_params_grads():
                if g is not None:
                    p.set_data(p._data - self.get_lr() * g._data)
        return loss


class Rprop(Optimizer):
    """Resilient backpropagation (paddle.optimizer.Rprop parity): per-
    element step sizes grown/shrunk by the sign agreement of successive
    gradients; only the gradient SIGN is used."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None, **kw):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_min, self._lr_max = (float(learning_rate_range[0]),
                                      float(learning_rate_range[1]))
        self._eta_minus, self._eta_plus = float(etas[0]), float(etas[1])

    def _update_param(self, p, g, lr):
        gd = g._data.astype(jnp.float32)
        prev = self._acc("prev_grad", p)
        step = self._acc("step_size", p,
                         init=jnp.full(p._data.shape, float(lr),
                                       jnp.float32))
        sign = jnp.sign(gd) * jnp.sign(prev._data)
        factor = jnp.where(sign > 0, self._eta_plus,
                           jnp.where(sign < 0, self._eta_minus, 1.0))
        new_step = jnp.clip(step._data * factor, self._lr_min, self._lr_max)
        # on sign flip: revert nothing (iRprop-), zero the stored grad so
        # the next step is neutral
        g_eff = jnp.where(sign < 0, 0.0, gd)
        upd = -jnp.sign(g_eff) * new_step
        prev.set_data(g_eff)
        step.set_data(new_step)
        p.set_data((p._data.astype(jnp.float32) + upd).astype(p.dtype))


class ASGD(Optimizer):
    """Averaged SGD (paddle.optimizer.ASGD parity): SGD steps plus a
    running average of the iterates stored per parameter."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None, **kw):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._batch_num = max(int(batch_num), 1)

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        # running mean of the last batch_num grads (paddle keeps a
        # d-buffer; the streaming mean is the TPU-friendly equivalent)
        buf = self._acc("grad_mean", p)
        n_t = self._acc("n_seen", p, init=jnp.zeros((), jnp.float32))
        n = jnp.minimum(n_t._data + 1.0, float(self._batch_num))
        mean = buf._data + (gd - buf._data) / n
        buf.set_data(mean)
        n_t.set_data(n)
        p.set_data((p._data.astype(jnp.float32) - lr * mean)
                   .astype(p.dtype))


class _NAdamRAdamBase(_AdamBase):
    def _moments(self, p, gd):
        m_t = self._acc("moment1", p)
        v_t = self._acc("moment2", p)
        b1p = self._acc("beta1_pow", p, init=jnp.asarray(1.0, jnp.float32))
        b2p = self._acc("beta2_pow", p, init=jnp.asarray(1.0, jnp.float32))
        m = self._beta1 * m_t._data + (1 - self._beta1) * gd
        v = self._beta2 * v_t._data + (1 - self._beta2) * jnp.square(gd)
        b1 = b1p._data * self._beta1
        b2 = b2p._data * self._beta2
        m_t.set_data(m)
        v_t.set_data(v)
        b1p.set_data(b1)
        b2p.set_data(b2)
        return m, v, b1, b2

    def _write(self, p, new):
        master = self._master(p)
        if master is not None:
            master.set_data(new)
        p.set_data(new.astype(p.dtype))

    def _base(self, p):
        master = self._master(p)
        return master._data if master is not None else \
            p._data.astype(jnp.float32)


class NAdam(_NAdamRAdamBase):
    """Nesterov-momentum Adam (paddle.optimizer.NAdam parity)."""

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        m, v, b1, b2 = self._moments(p, gd)
        m_hat = (self._beta1 * m / (1 - b1 * self._beta1)
                 + (1 - self._beta1) * gd / (1 - b1))
        v_hat = v / (1 - b2)
        new = self._base(p) - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        self._write(p, new)


class RAdam(_NAdamRAdamBase):
    """Rectified Adam (paddle.optimizer.RAdam parity): per-step variance
    rectification; falls back to momentum SGD while the variance estimate
    is untrustworthy (small t)."""

    def _update_param(self, p, g, lr):
        gd = self._decay_grad(p, g._data.astype(jnp.float32))
        m, v, b1, b2 = self._moments(p, gd)
        rho_inf = 2.0 / (1 - self._beta2) - 1.0
        # t from beta2^t (avoids a separate step counter accumulator)
        t = jnp.log(b2) / jnp.log(jnp.asarray(self._beta2, jnp.float32))
        rho_t = rho_inf - 2.0 * t * b2 / (1 - b2)
        m_hat = m / (1 - b1)
        r_num = (rho_t - 4) * (rho_t - 2) * rho_inf
        r_den = (rho_inf - 4) * (rho_inf - 2) * rho_t
        rect = jnp.sqrt(jnp.maximum(r_num / jnp.maximum(r_den, 1e-30),
                                    0.0))
        v_hat = jnp.sqrt(v / (1 - b2))
        adam_step = rect * m_hat / (v_hat + self._epsilon)
        sgd_step = m_hat
        new = self._base(p) - lr * jnp.where(rho_t > 5.0, adam_step,
                                             sgd_step)
        self._write(p, new)
