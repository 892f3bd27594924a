"""Core of the TPU-native framework: Tensor façade over ``jax.Array`` plus a
tape-based eager autograd engine.

Reference parity (see SURVEY.md §2.1/§3; reference mount was empty, paths
unverified): plays the role of Paddle's PHI core (``DenseTensor``,
``paddle/phi/core/``) + the eager autograd engine (``paddle/fluid/eager/``,
``GradNodeBase``/``RunBackward``).  Design is TPU-first instead of a port:

- A ``Tensor`` wraps an immutable ``jax.Array``; "in-place" ops rebind the
  wrapped array, preserving Python identity (Paddle semantics) while staying
  functional underneath (XLA semantics).
- Autograd does not need per-op grad kernels: every differentiable op is a
  pure jax function, and the tape records the ``jax.vjp`` residual closure.
  ``backward()`` walks the tape.  Under ``paddle_tpu.jit.to_static`` the same
  tape runs on tracers and lowers into one XLA program, so eager and compiled
  mode share one autograd implementation (Paddle needs two: eager GradNodes
  and static-graph grad ops).
- State (parameters, buffers, optimizer accumulators, RNG key) is observable
  via a read/write tracking hook so the trace-and-compile path can
  functionalize user code that mutates state imperatively.
"""

from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Tensor",
    "Parameter",
    "apply",
    "backward",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "to_jax_dtype",
    "dtype_name",
    "track_state",
    "current_tracking",
]

# --------------------------------------------------------------------------
# dtype handling
# --------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": jnp.float32, "fp32": jnp.float32,
    "float64": jnp.float64, "fp64": jnp.float64, "double": jnp.float64,
    "float16": jnp.float16, "fp16": jnp.float16, "half": jnp.float16,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
    "int8": jnp.int8, "uint8": jnp.uint8,
    "int16": jnp.int16, "int32": jnp.int32, "int64": jnp.int64,
    "bool": jnp.bool_,
    "complex64": jnp.complex64, "complex128": jnp.complex128,
    "float8_e4m3fn": jnp.float8_e4m3fn, "float8_e5m2": jnp.float8_e5m2,
}


def to_jax_dtype(dtype) -> jnp.dtype:
    """Normalize a user-facing dtype (string / numpy / jax) to a jnp dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        try:
            return jnp.dtype(_DTYPE_ALIASES[dtype])
        except KeyError:
            raise ValueError(f"Unknown dtype name: {dtype!r}")
    if isinstance(dtype, Tensor):
        return dtype.dtype
    return jnp.dtype(dtype)


def dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


def trace_clean() -> bool:
    """True when no jax trace is in progress (i.e. eager host execution).
    Single wrapper around the unstable jax internal so a jax upgrade has
    one place to fix (pyproject.toml pins the jax minor)."""
    from jax._src.core import trace_state_clean
    return trace_state_clean()


def is_floating(dtype) -> bool:
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def is_complex(dtype) -> bool:
    return jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating)


def is_integer(dtype) -> bool:
    return jnp.issubdtype(jnp.dtype(dtype), jnp.integer)


def _coerce_host_data(data, dtype):
    """Paddle creation-dtype semantics for host data: python floats (and
    lists of them) default to float32; python ints to int64; numpy arrays
    keep their own dtype (so an explicit np.float64 array stays float64)."""
    if dtype is not None or isinstance(data, np.ndarray):
        return data
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        return arr.astype(np.float32)
    return arr


# --------------------------------------------------------------------------
# grad mode
# --------------------------------------------------------------------------

class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


def set_grad_enabled(mode: bool) -> None:
    _grad_state.enabled = bool(mode)


class _NoGrad(contextlib.ContextDecorator):
    """``paddle.no_grad`` equivalent — usable as context manager or decorator."""

    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = False
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev
        return False


class _EnableGrad(contextlib.ContextDecorator):
    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = True
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev
        return False


no_grad = _NoGrad
enable_grad = _EnableGrad


# --------------------------------------------------------------------------
# state read/write tracking (used by jit.to_static functionalization)
# --------------------------------------------------------------------------

class StateTracking:
    """Records which persistable tensors are read / written during a call."""

    def __init__(self):
        self.read: dict[int, "Tensor"] = {}
        self.written: dict[int, "Tensor"] = {}

    def record_read(self, t: "Tensor") -> None:
        self.read.setdefault(id(t), t)

    def record_write(self, t: "Tensor") -> None:
        self.written.setdefault(id(t), t)


class _TrackState(threading.local):
    def __init__(self):
        self.current: StateTracking | None = None


_track_state = _TrackState()


def current_tracking() -> StateTracking | None:
    return _track_state.current


@contextlib.contextmanager
def track_state(tracking: StateTracking):
    prev = _track_state.current
    _track_state.current = tracking
    try:
        yield tracking
    finally:
        _track_state.current = prev


# --------------------------------------------------------------------------
# scalar concretization record/replay (to_static guarded specialization)
# --------------------------------------------------------------------------

class _ConcretizeState(threading.local):
    """SOT-style branch specialization support. During to_static discovery
    (eager) every scalar concretization (bool/int of a Tensor) is RECORDED;
    during the jit trace the same sites REPLAY the recorded value as a
    python constant and register the traced tensor as a GUARD output, so
    the compiled program can verify each step that the branch decisions
    still hold (mismatch -> re-specialize)."""

    def __init__(self):
        self.mode = None      # None | "record" | "replay"
        self.log = None       # list of (kind, value)
        self.cursor = 0
        self.guards = None    # replay: list of (traced_array, kind, value)


_concretize_state = _ConcretizeState()

#: set by utils.monitor.enable_op_stats(): called as hook(name, dtype)
#: from apply() — amp.debugging operator-stats collection
_op_stat_hook = None


@contextlib.contextmanager
def record_concretizations(log: list):
    st = _concretize_state
    prev = (st.mode, st.log, st.cursor, st.guards)
    st.mode, st.log, st.cursor, st.guards = "record", log, 0, None
    try:
        yield log
    finally:
        st.mode, st.log, st.cursor, st.guards = prev


@contextlib.contextmanager
def replay_concretizations(log: list, guards: list):
    st = _concretize_state
    prev = (st.mode, st.log, st.cursor, st.guards)
    st.mode, st.log, st.cursor, st.guards = "replay", log, 0, guards
    try:
        yield guards
    finally:
        st.mode, st.log, st.cursor, st.guards = prev


class GraphBreak(Exception):
    """Raised during a to_static replay trace when the graph cannot be
    captured (replay divergence or an unguardable concretization); the
    to_static runner treats it like jax's tracer errors: warn + eager
    fallback. A plain exception — jax's ConcretizationTypeError requires
    a Tracer to construct, and divergence can involve concrete data."""


def _replay_divergence(data, why: str):
    return GraphBreak(
        f"to_static replay diverged from the discovery run ({why}); "
        "breaking the graph")


class _ObsCell:
    """Bookkeeping for one observed float concretization site (a
    ``float()``/``.item()`` read recorded during to_static discovery)."""

    __slots__ = ("misused", "strict")

    def __init__(self, strict=False):
        self.misused = False
        self.strict = strict     # replay trace: misuse must abort, not flag


class ObservedFloat(float):
    """A float ``.item()``-read out of a to_static-captured function
    (SOT-style partial capture, SURVEY.md §3.5 "graph breaks").

    Observation-only uses — logging, formatting, returning the value —
    keep the graph compiled: the read becomes an extra program output
    (fresh every call when returned). Uses that would change the program
    — branching on it, feeding it back into tensor math, int() indexing —
    flag ``misused`` during discovery (→ eager fallback for the
    signature) and raise ``GraphBreak`` during a replay trace.
    Arithmetic propagates observation: the python result mirrors onto the
    traced scalar, so derived returned values stay fresh too.

    Only ``.item()`` reads get this treatment: CPython force-converts
    ``__float__`` results to exact float, so ``float(t)`` cannot carry
    the taint and stays a hard graph break (its warning steers users to
    ``.item()``). Known hole (documented divergence): conversions that
    coerce via ``__float__`` (``math.isnan(f)``, ``"%f" % f``) are
    treated as observation; branching on the coerced value goes
    undetected."""

    __slots__ = ("_origins", "_traced")

    def __new__(cls, value, origins=(), traced=None):
        obj = super().__new__(cls, value)
        obj._origins = tuple(origins)
        obj._traced = traced
        return obj

    def _misuse(self, what):
        strict = False
        for c in self._origins:
            c.misused = True
            strict = strict or c.strict
        if strict:
            raise GraphBreak(
                f"a float read from the compiled graph was used for "
                f"{what} — this cannot be captured (a stale value would "
                "change the program); breaking the graph")

    # -- uses that change the program: flag / abort ------------------------

    def __bool__(self):
        self._misuse("branching")
        return super().__bool__()

    def _cmp(self, name, other):
        self._misuse("a comparison (likely branching)")
        return getattr(float, name)(float(self), other)

    def __lt__(self, o):
        return self._cmp("__lt__", o)

    def __le__(self, o):
        return self._cmp("__le__", o)

    def __gt__(self, o):
        return self._cmp("__gt__", o)

    def __ge__(self, o):
        return self._cmp("__ge__", o)

    def __eq__(self, o):
        return self._cmp("__eq__", o)

    def __ne__(self, o):
        return self._cmp("__ne__", o)

    __hash__ = float.__hash__

    def __int__(self):
        self._misuse("int conversion (indexing/branching)")
        return super().__int__()

    __index__ = __trunc__ = __int__

    def __round__(self, *a):
        self._misuse("rounding to int")
        return float(self).__round__(*a)

    # -- observation-preserving arithmetic ---------------------------------

    def _binop(self, name, other):
        if not isinstance(other, (int, float)):
            return NotImplemented
        res = getattr(float, name)(float(self), float(other))
        if res is NotImplemented:
            return res
        origins = self._origins
        o_traced = None
        if isinstance(other, ObservedFloat):
            origins = origins + other._origins
            o_traced = other._traced
        traced = None
        if self._traced is not None or o_traced is not None:
            # keep the traced value's own dtype (no float32 forcing):
            # under x64 a float64 loss must mirror in float64, or
            # compiled-call results would drift from the eager discovery
            a = self._traced if self._traced is not None else float(self)
            b = o_traced if o_traced is not None else float(other)
            try:
                traced = getattr(jnp.asarray(a), name)(jnp.asarray(b))
                if traced is NotImplemented:
                    traced = None
            except Exception:
                traced = None
        return ObservedFloat(res, origins, traced)

    def __add__(self, o):
        return self._binop("__add__", o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop("__sub__", o)

    def __rsub__(self, o):
        return self._binop("__rsub__", o)

    def __mul__(self, o):
        return self._binop("__mul__", o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop("__truediv__", o)

    def __rtruediv__(self, o):
        return self._binop("__rtruediv__", o)

    def __pow__(self, o):
        return self._binop("__pow__", o)

    def __rpow__(self, o):
        return self._binop("__rpow__", o)

    def __mod__(self, o):
        return self._binop("__mod__", o)

    def __rmod__(self, o):
        return self._binop("__rmod__", o)

    def __floordiv__(self, o):
        return self._binop("__floordiv__", o)

    def __rfloordiv__(self, o):
        return self._binop("__rfloordiv__", o)

    def __divmod__(self, o):
        return (self.__floordiv__(o), self.__mod__(o))

    def __rdivmod__(self, o):
        return (self.__rfloordiv__(o), self.__rmod__(o))

    def __neg__(self):
        return ObservedFloat(
            -float(self), self._origins,
            None if self._traced is None else -self._traced)

    def __pos__(self):
        return self

    def __abs__(self):
        return ObservedFloat(
            abs(float(self)), self._origins,
            None if self._traced is None else jnp.abs(self._traced))

    def __float__(self):
        # exact float (CPython deprecates returning a strict subclass
        # from __float__); the taint ends here — documented hole
        return float.__add__(self, 0.0)


def _is_obs_float_kind(kind, value):
    # only .item() reads: float() results are force-converted to exact
    # float by CPython, so they cannot carry the observation taint
    return (kind == "item" and isinstance(value, float)
            and not isinstance(value, bool))


def _concretize(data, kind: str, cast):
    """Single funnel for Tensor scalar conversions (bool/int/float/item)."""
    from .segment import SegValue as _SegValue
    if isinstance(data, _SegValue):
        # lazy-segment placeholder: a scalar read IS the graph break —
        # flush the recorded segment (one compiled program), then hand
        # Python the concrete value and keep going into the next segment
        data = data.force()
    st = _concretize_state
    if st.mode == "replay":
        if st.cursor >= len(st.log):
            raise _replay_divergence(data, "more concretizations than "
                                           "recorded")
        entry = st.log[st.cursor]
        rec_kind, rec_val = entry[0], entry[1]
        st.cursor += 1
        if rec_kind != kind:
            raise _replay_divergence(
                data, f"expected {rec_kind}, got {kind}")
        if isinstance(data, jax.core.Tracer):
            if not guardable_concretization(kind, rec_val):
                if _is_obs_float_kind(kind, rec_val):
                    # observed float read (SOT partial capture): hand the
                    # user code the recorded value but keep the TRACED
                    # scalar alongside — observation (logging, return)
                    # stays compiled; misuse aborts the trace (strict)
                    return ObservedFloat(rec_val, (_ObsCell(strict=True),),
                                         traced=data)
                raise GraphBreak(
                    f"a {kind} concretization cannot be value-guarded "
                    "(replaying a stale value would silently change "
                    "numerics); breaking the graph. Observation-only "
                    ".item() reads stay compiled — prefer .item() over "
                    "float() inside compiled functions")
            # guardable scalar: feed the recorded value, emit a guard
            st.guards.append((data, kind, rec_val))
            return rec_val
        val = cast(data)   # concrete even under trace: a baked constant
        if val != rec_val:
            raise _replay_divergence(
                data, f"constant changed {rec_val!r} -> {val!r}")
        return val
    val = cast(data)       # eager (record mode or plain): concrete value
    if st.mode == "record":
        if _is_obs_float_kind(kind, val) and not \
                guardable_concretization(kind, val):
            cell = _ObsCell()
            st.log.append((kind, val, cell))
            return ObservedFloat(val, (cell,))
        st.log.append((kind, val))
    return val


def guardable_concretization(kind: str, value) -> bool:
    """Branch decisions / index choices can be value-guarded. float
    concretizations can NOT — a replayed stale float would silently change
    numerics (logging, lr math), and an equality guard on a moving loss
    would mispredict every step — so they break the graph."""
    if kind in ("bool", "int"):
        return True
    return kind == "item" and isinstance(value, (bool, int, np.integer))


# --------------------------------------------------------------------------
# autograd tape
# --------------------------------------------------------------------------

class GradNode:
    """One tape entry.  Mirrors the role of Paddle's ``GradNodeBase``
    (paddle/fluid/eager/grad_node_info.h, UNVERIFIED) but holds a ``jax.vjp``
    residual closure instead of pointing at a hand-written grad kernel."""

    __slots__ = ("vjp_fn", "parents", "n_outputs", "out_grads", "name",
                 "pending", "out_avals", "_hooks")

    def __init__(self, vjp_fn, parents, n_outputs, name="", out_avals=None):
        self.vjp_fn = vjp_fn
        # parents: list of Tensors that required grad (inputs of the op)
        self.parents: list[Tensor] = parents
        self.n_outputs = n_outputs
        self.out_grads: list[Any] = [None] * n_outputs
        self.name = name
        self.pending = 0
        # (shape, dtype) per output so unseeded outputs can be zero-filled
        self.out_avals = out_avals
        self._hooks: list[Callable] | None = None

    def add_out_grad(self, idx: int, g):
        cur = self.out_grads[idx]
        self.out_grads[idx] = g if cur is None else cur + g


class Tensor:
    """Paddle-shaped tensor.  Wraps a ``jax.Array`` (or jax tracer).

    ``stop_gradient`` defaults to True, matching ``paddle.Tensor``; set to
    False (or use ``Parameter``) to take part in autograd.
    """

    # let Tensor win in e.g. np_array * tensor
    __array_priority__ = 100

    __slots__ = ("_data", "_stop_gradient", "_grad_value", "_grad_stale",
                 "_node", "_out_idx", "name", "persistable", "_grad_hooks",
                 "trainable", "__weakref__")

    def __init__(self, data, dtype=None, stop_gradient: bool = True,
                 name: str = ""):
        if isinstance(data, Tensor):
            data = data._data
        from .segment import SegValue as _SegValue
        if isinstance(data, _SegValue):
            # lazy-segment placeholder: keep lazy, but honor a requested
            # cast (recorded as a node — dropping it would silently
            # diverge from the eager path's dtype)
            if dtype is not None and data.dtype != to_jax_dtype(dtype):
                data = data.astype(to_jax_dtype(dtype))
        elif not isinstance(data, jax.Array) and \
                not isinstance(data, jax.core.Tracer):
            data = jnp.asarray(_coerce_host_data(data, dtype),
                               dtype=to_jax_dtype(dtype))
        elif dtype is not None and data.dtype != to_jax_dtype(dtype):
            data = data.astype(to_jax_dtype(dtype))
        self._data = data
        self._stop_gradient = stop_gradient
        self._grad_value: Tensor | None = None
        self._grad_stale = False
        self._node: GradNode | None = None
        self._out_idx: int = 0
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient
        self._grad_hooks: list[Callable] | None = None

    # -- data access -------------------------------------------------------

    @property
    def data(self) -> "Tensor":
        return self

    @data.setter
    def data(self, value):
        self.set_data(value._data if isinstance(value, Tensor) else jnp.asarray(value))

    def jax(self):
        """The underlying jax.Array (TPU-native escape hatch)."""
        tr = _track_state.current
        if tr is not None and self.persistable:
            tr.record_read(self)
        return self._data

    def set_data(self, new_data, *, _clear_tape: bool = True) -> None:
        """Rebind the wrapped array. This is the single mutation point, so the
        to_static functionalizer can observe writes."""
        tr = _track_state.current
        if tr is not None and self.persistable:
            tr.record_write(self)
        from .segment import current_recorder
        rec = current_recorder()
        if rec is not None:
            # segment mode: log for rollback — a call that aborts before
            # its final flush must not leave half-committed state
            rec.log_mutation(self, self._data)
        self._data = new_data
        if _clear_tape:
            self._node = None
            self._out_idx = 0

    @property
    def stop_gradient(self) -> bool:
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, value: bool) -> None:
        self._stop_gradient = bool(value)

    @property
    def grad(self) -> "Tensor | None":
        if self._grad_stale:
            warnings.warn(
                "reading .grad after a compiled to_static step: gradients "
                "are consumed inside the compiled program and are NOT "
                "synchronized back to eager .grad — this value is stale or "
                "None. Inspect grads inside the compiled function, or run "
                "the step eagerly.", UserWarning, stacklevel=2)
            self._grad_stale = False
        return self._grad_value

    @grad.setter
    def grad(self, value) -> None:
        from .segment import current_recorder
        rec = current_recorder()
        if rec is not None:
            # abort-rollback must undo grad (re)binding too, or the
            # eager retry's backward would double-accumulate
            rec.log_grad_mutation(self, self._grad_value)
        self._grad_value = value
        self._grad_stale = False

    # -- metadata ----------------------------------------------------------

    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.ndim else 1

    @property
    def place(self):
        from . import device
        return device.place_of(self._data)

    @property
    def is_leaf(self) -> bool:
        return self._node is None

    def numel(self):
        from ..ops import creation
        return creation.to_tensor(self.size, dtype="int64")

    def dim(self):
        return self.ndim

    def rank(self):
        return self.ndim

    def element_size(self) -> int:
        return self._data.dtype.itemsize

    # -- conversion --------------------------------------------------------

    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def item(self):
        return _concretize(self._data, "item", lambda d: d.item())

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __float__(self):
        return _concretize(self._data, "float", float)

    def __int__(self):
        return _concretize(self._data, "int", int)

    def __index__(self):
        return _concretize(self._data, "int", int)

    def __bool__(self):
        return _concretize(self._data, "bool", bool)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        try:
            body = repr(np.asarray(self._data))
        except Exception:  # tracers
            body = repr(self._data)
        return (f"Tensor(shape={self.shape}, dtype={dtype_name(self.dtype)}, "
                f"stop_gradient={self._stop_gradient},\n       {body})")

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- autograd ----------------------------------------------------------

    def detach(self) -> "Tensor":
        """A tensor off the tape that SHARES this one's buffer (as in
        Paddle, where it aliases the live parameter): a compiled step
        that reassigns this tensor donates that buffer, and the detached
        view is then deleted. A snapshot is ``clone()`` or ``numpy()``."""
        return Tensor(self._data, stop_gradient=True)

    def detach_(self) -> "Tensor":
        self._node = None
        self._stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from ..ops import manipulation
        return manipulation.clone(self)

    def backward(self, grad_tensor=None, retain_graph: bool = False) -> None:
        backward([self], [grad_tensor] if grad_tensor is not None else None,
                 retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self.grad = None

    def clear_gradient(self, set_to_zero: bool = False) -> None:
        self._grad_stale = False   # explicit reset supersedes staleness
        if set_to_zero and self._grad_value is not None:
            self._grad_value.set_data(
                jnp.zeros_like(self._grad_value._data))
        else:
            self.grad = None

    def register_hook(self, hook: Callable) -> Callable:
        """Register a grad hook fired when this tensor's grad is computed.
        Returns a remover callable."""
        if self._grad_hooks is None:
            self._grad_hooks = []
        self._grad_hooks.append(hook)

        def remove():
            try:
                self._grad_hooks.remove(hook)
            except ValueError:
                pass
        return remove

    @property
    def requires_grad(self) -> bool:  # torch-style alias used in tests
        return not self._stop_gradient

    # in-place helpers used by optimizers (no autograd)
    def _inplace_update(self, new_data):
        self.set_data(new_data)
        return self


class Parameter(Tensor):
    """Trainable, persistable tensor — ``paddle.nn.Parameter`` equivalent."""

    def __init__(self, data, dtype=None, name: str = "", trainable: bool = True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.persistable = True
        self.trainable = trainable


# --------------------------------------------------------------------------
# op dispatch: eager execution + tape recording
# --------------------------------------------------------------------------

def _wrap_out(data, node=None, idx=0, stop_gradient=True):
    t = Tensor(data, stop_gradient=stop_gradient)
    if node is not None:
        t._node = node
        t._out_idx = idx
    return t


def apply(fn: Callable, *tensors, n_outputs: int = 1, name: str = "",
          differentiable: bool = True, **static_kwargs):
    """Execute op ``fn(*arrays, **static_kwargs)`` over Tensor inputs.

    The single entry point every op goes through (the analogue of Paddle's
    generated ``*_ad_func`` + PHI API dispatch, SURVEY.md §3.1). Handles:
      - unwrapping Tensors (and passing through python scalars),
      - state-read tracking for the to_static functionalizer,
      - recording a GradNode via ``jax.vjp`` when grad is required.

    ``fn`` must be a pure jax function. Tensor-valued kwargs are not allowed;
    pass tensors positionally.
    """
    if _op_stat_hook is not None:
        _op_stat_hook(name, str(getattr(
            next((t._data for t in tensors if isinstance(t, Tensor)),
                 None), "dtype", "-")))
    from . import segment as _segment
    rec = _segment.current_recorder()
    tr = _track_state.current
    datas = []
    for t in tensors:
        if isinstance(t, Tensor):
            if tr is not None and t.persistable:
                tr.record_read(t)
            if rec is None and \
                    isinstance(t._data, _segment.SegValue) and \
                    t._data.concrete is not None:
                # normalize a flushed placeholder back to its array the
                # first time it is touched outside segment mode
                t._data = t._data.concrete
            datas.append(t._data)
        else:
            if isinstance(t, ObservedFloat):
                # a float read out of the compiled graph feeding back into
                # tensor math: the recorded value would go stale — flag
                # (discovery) or abort the trace (replay)
                t._misuse("tensor computation")
            datas.append(t)

    needs_grad = (
        differentiable
        and _grad_state.enabled
        and any(isinstance(t, Tensor) and not t._stop_gradient for t in tensors)
    )

    if rec is not None:
        return _apply_segment(rec, fn, tensors, datas, n_outputs, name,
                              static_kwargs, needs_grad)

    if not needs_grad:
        out = fn(*datas, **static_kwargs)
        if n_outputs == 1:
            return _wrap_out(out)
        return tuple(_wrap_out(o) for o in out)

    # Differentiate only w.r.t. inputs that require grad; close over the rest.
    diff_idx = [i for i, t in enumerate(tensors)
                if isinstance(t, Tensor) and not t._stop_gradient]
    diff_parents = [tensors[i] for i in diff_idx]

    def pure(*diff_args):
        full = list(datas)
        for i, a in zip(diff_idx, diff_args):
            full[i] = a
        return fn(*full, **static_kwargs)

    out, vjp_fn = jax.vjp(pure, *(datas[i] for i in diff_idx))
    if n_outputs == 1:
        node = GradNode(vjp_fn, diff_parents, 1, name=name or fn.__name__,
                        out_avals=[(out.shape, out.dtype)])
        return _wrap_out(out, node, 0, stop_gradient=False)
    node = GradNode(vjp_fn, diff_parents, n_outputs, name=name or fn.__name__,
                    out_avals=[(o.shape, o.dtype) for o in out])
    outs = tuple(
        _wrap_out(o, node, i, stop_gradient=False) for i, o in enumerate(out)
    )
    return outs


def _apply_segment(rec, fn, tensors, datas, n_outputs, name,
                   static_kwargs, needs_grad):
    """apply() under segment mode: record the op instead of running it
    (compile-around-break — see framework/segment.py). The GradNode's
    vjp re-runs ``jax.vjp`` of the op inside a LATER segment, so the
    backward pass is recorded-and-flushed compiled too (a
    rematerializing tape with identical numerics)."""
    from . import segment as _segment
    opname = name or getattr(fn, "__name__", "op")
    outs = rec.record_kw(fn, datas, static_kwargs, n_outputs, opname)
    if not needs_grad:
        if n_outputs == 1:
            return _wrap_out(outs[0])
        return tuple(_wrap_out(o) for o in outs)

    diff_idx = [i for i, t in enumerate(tensors)
                if isinstance(t, Tensor) and not t._stop_gradient]
    diff_parents = [tensors[i] for i in diff_idx]

    def lazy_vjp(cts):
        ct_list = [cts] if n_outputs == 1 else list(cts)
        n_ct = len(ct_list)

        def grad_fn(*args):
            cta = args[:n_ct]
            full = list(args[n_ct:])

            def pure(*diff_args):
                f2 = list(full)
                for i, a in zip(diff_idx, diff_args):
                    f2[i] = a
                return fn(*f2, **static_kwargs)

            _, vjp = jax.vjp(pure, *(full[i] for i in diff_idx))
            gr = tuple(vjp(cta[0] if n_outputs == 1 else tuple(cta)))
            # the recorder's single-output contract is an unwrapped
            # value, not a 1-tuple
            return gr[0] if len(diff_idx) == 1 else gr

        rec2 = _segment.current_recorder()
        if rec2 is not None:
            return rec2.record(grad_fn, ct_list + list(datas),
                               n_outputs=len(diff_idx),
                               name=opname + "_bwd")
        # backward pulled outside segment mode: run on concrete values
        conc = [a.force() if isinstance(a, _segment.SegValue) else a
                for a in ct_list + list(datas)]
        gr = grad_fn(*conc)
        return (gr,) if len(diff_idx) == 1 else gr

    node = GradNode(lazy_vjp, diff_parents, n_outputs, name=opname,
                    out_avals=[(o.shape, o.dtype) for o in outs])
    if n_outputs == 1:
        return _wrap_out(outs[0], node, 0, stop_gradient=False)
    return tuple(_wrap_out(o, node, i, stop_gradient=False)
                 for i, o in enumerate(outs))


# --------------------------------------------------------------------------
# backward engine
# --------------------------------------------------------------------------

def _ones_like(data):
    from .segment import SegValue as _SegValue
    if isinstance(data, _SegValue):
        rec = data.recorder
        return rec.record(jnp.ones_like, [data], 1, "ones_like")[0]
    return jnp.ones_like(data)


def backward(tensors: Sequence[Tensor], grad_tensors=None,
             retain_graph: bool = False, accumulate_ids=None) -> None:
    """Run reverse-mode over the recorded tape — the analogue of
    ``egr::Backward`` (paddle/fluid/eager/backward.cc, UNVERIFIED).

    Topologically orders reachable GradNodes by dependency counting, then
    pulls vjp closures in reverse order, accumulating into ``.grad`` of leaf
    tensors with ``stop_gradient=False``. ``accumulate_ids`` (used by
    ``paddle.grad``) additionally accumulates into the named *non-leaf*
    tensors as their cotangents stream past."""
    roots = [t for t in tensors if isinstance(t, Tensor)]
    accumulate_ids = accumulate_ids or frozenset()
    if grad_tensors is None:
        grad_tensors = [None] * len(roots)
    # 1) seed grads
    for t, g in zip(roots, grad_tensors):
        if t._stop_gradient:
            continue
        seed = g._data if isinstance(g, Tensor) else (
            jnp.asarray(g, dtype=t.dtype) if g is not None else _ones_like(t._data))
        if id(t) in accumulate_ids:
            _accumulate_leaf(t, seed)
        if t._node is None:
            if id(t) not in accumulate_ids:
                _accumulate_leaf(t, seed)
        else:
            t._node.add_out_grad(t._out_idx, seed)

    # 2) collect reachable node graph & in-degrees (number of child nodes
    #    that will feed grads into each node)
    nodes: dict[int, GradNode] = {}
    indeg: dict[int, int] = {}
    stack = [t._node for t in roots if t._node is not None and not t._stop_gradient]
    seen = set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        nodes[id(node)] = node
        for p in node.parents:
            pn = p._node
            if pn is not None:
                indeg[id(pn)] = indeg.get(id(pn), 0) + 1
                if id(pn) not in seen:
                    stack.append(pn)

    # 3) ready queue: nodes all of whose consumers have fired
    ready = [n for nid, n in nodes.items() if indeg.get(nid, 0) == 0]
    fired = set()
    while ready:
        node = ready.pop()
        fired.add(id(node))
        grads_out = tuple(
            g if g is not None else jnp.zeros(av[0], av[1])
            for g, av in zip(node.out_grads, node.out_avals)
        )
        in_grads = node.vjp_fn(grads_out[0] if node.n_outputs == 1 else grads_out)
        if not retain_graph:
            node.vjp_fn = None
        for parent, g in zip(node.parents, in_grads):
            pn = parent._node
            if g is None:
                # still release the dependency edge so upstream nodes fire
                if pn is not None:
                    indeg[id(pn)] -= 1
                    if indeg[id(pn)] == 0:
                        ready.append(pn)
                continue
            if parent._grad_hooks:
                gt = Tensor(g, stop_gradient=True)
                for hook in parent._grad_hooks:
                    res = hook(gt)
                    if res is not None:
                        gt = res if isinstance(res, Tensor) else Tensor(res)
                g = gt._data
            if id(parent) in accumulate_ids:
                _accumulate_leaf(parent, g)
            if pn is None:
                if not parent._stop_gradient and \
                        id(parent) not in accumulate_ids:
                    _accumulate_leaf(parent, g)
            else:
                pn.add_out_grad(parent._out_idx, g)
                indeg[id(pn)] -= 1
                if indeg[id(pn)] == 0:
                    ready.append(pn)
        node.out_grads = [None] * node.n_outputs
    # Nodes never fired (unreached due to missing seeds) are fine — their
    # vjp closures get collected with the tape.


def tape_alias(t: Tensor) -> Tensor:
    """A fresh Tensor sharing t's data AND tape position. In-place ops must
    run the functional op on an alias — recording the op with the mutated
    tensor itself as parent would create a self-referential node."""
    a = Tensor(t._data, stop_gradient=t._stop_gradient)
    a._node, a._out_idx = t._node, t._out_idx
    return a


def tape_rebind(t: Tensor, out: Tensor) -> Tensor:
    """Point t at out's data and tape node (the in-place op epilogue)."""
    t.set_data(out._data, _clear_tape=False)
    t._node, t._out_idx = out._node, out._out_idx
    t._stop_gradient = out._stop_gradient
    return t


def _accumulate_leaf(t: Tensor, g) -> None:
    if g.dtype != t.dtype and is_floating(t.dtype):
        g = g.astype(t.dtype)
    # _grad_value, not .grad: accumulating fresh grads must not trip the
    # stale-after-compiled-step warning (and it supersedes staleness)
    if t._grad_value is None:
        t.grad = Tensor(g, stop_gradient=True)
    else:
        t._grad_value.set_data(t._grad_value._data + g)
        t._grad_stale = False
