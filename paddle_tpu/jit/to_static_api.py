"""``paddle.jit.to_static`` — the trace-and-compile path.

Reference role (SURVEY.md §3.5, UNVERIFIED paths): SOT bytecode capture →
PIR program → CINN fusion → InterpreterCore executor. TPU-native design: the
user's imperative function (forward, or a whole train step with
``loss.backward()`` and ``optimizer.step()``) is *functionalized* and handed
to ``jax.jit`` — XLA plays the roles of PIR, CINN, and the executor at once.

How functionalization works (this replaces SOT's bytecode interception):
1. **Discovery pass** — the first call for a given input signature runs
   eagerly under a ``StateTracking`` scope. Every read/write of a
   *persistable* tensor (parameters, buffers, optimizer accumulators, RNG
   key) funnels through ``core.apply`` / ``Tensor.set_data``, so we learn
   exactly which state the function touches.
2. **Pure wrapper** — ``(written state, read-only state, args) ->
   (new_state, outputs)`` temporarily rebinds the tracked tensors to
   tracer arrays, replays the user function (the autograd tape runs on
   tracers, so ``.backward()`` lowers into the same XLA program), and
   reads back mutated state.
3. ``jax.jit`` compiles it; python scalars in the signature are baked in as
   constants (they're part of the cache key, like SOT guards). A
   guard-free graph DONATES the written state: the program updates it in
   place, and the arrays those tensors held before the call are deleted
   (``to_static``'s docstring says what that means for a caller).

Graph breaks and guarded specialization (the SOT role): data-dependent
Python control flow on SCALARS (``if loss_improved:``, ``int(idx)``) does
NOT break the graph. Discovery records every scalar concretization; the
trace replays each recorded value as a baked constant and emits the traced
tensor as a *guard output*; every compiled step re-checks the guards on
device results before committing state. A guard mismatch discards that
run, re-runs eagerly (correctness), and re-specializes — distinct branch
patterns each get their own cached executable (SOT/dynamo branch
specialization). Only unguardable concretizations — ``float()``/``item()``
on floats (stale value would change numerics) and bulk host reads
(``.numpy()``) — fall back to eager for the signature, with a warning.

Caveat (documented divergence): ``.grad`` values left un-cleared across a
compiled call are not synchronized back — the standard step pattern
(backward → optimizer.step → clear_grad inside the function) is fully
supported; reading ``.grad`` after a compiled step warns.
"""

from __future__ import annotations

import collections
import functools
import itertools
import logging
import time as _time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (GraphBreak, ObservedFloat, Tensor,
                              StateTracking, guardable_concretization,
                              record_concretizations, replay_concretizations,
                              track_state)
from ..profiler import metrics as _pmetrics
from ..profiler.trace import trace_span as _span

__all__ = ["to_static", "StaticFunction", "not_to_static", "ignore_module"]

logger = logging.getLogger(__name__)

# what one compiled call hands back, on the process registry: a step
# whose state comes back in fresh buffers shows as outputs far above
# donated_inputs (the host allocates each one before launch)
_pmetrics.declare("jit/compiled_calls", "counter",
                  "to_static calls that ran the compiled program")
_pmetrics.declare("jit/outputs", "counter",
                  "device buffers compiled to_static calls returned "
                  "(reassigned state + outputs)")
_pmetrics.declare("jit/donated_inputs", "counter",
                  "state buffers compiled to_static calls donated to "
                  "their program (the state a guard-free step reassigns)")
_c_calls = _pmetrics.get_registry().counter("jit/compiled_calls")
_c_outputs = _pmetrics.get_registry().counter("jit/outputs")
_c_donated = _pmetrics.get_registry().counter("jit/donated_inputs")


def not_to_static(fn):
    """Mark a function to never be compiled (paddle.jit.not_to_static)."""
    fn._paddle_tpu_not_to_static = True
    return fn


def ignore_module(modules):
    """Accepted for API parity (SOT concept); no-op."""
    return None


# ---- pytree helpers over plain python containers --------------------------

def _tree_flatten(obj, leaves):
    if isinstance(obj, Tensor):
        leaves.append(obj)
        return ("T", len(leaves) - 1)
    if isinstance(obj, (list, tuple)):
        return ("tuple" if isinstance(obj, tuple) else "list",
                [_tree_flatten(o, leaves) for o in obj])
    if isinstance(obj, dict):
        return ("dict", {k: _tree_flatten(v, leaves)
                         for k, v in sorted(obj.items())})
    leaves.append(obj)
    return ("L", len(leaves) - 1)


def _tree_unflatten(spec, leaves):
    kind = spec[0]
    if kind in ("T", "L"):
        return leaves[spec[1]]
    if kind == "dict":
        return {k: _tree_unflatten(v, leaves) for k, v in spec[1].items()}
    seq = [_tree_unflatten(s, leaves) for s in spec[1]]
    return tuple(seq) if kind == "tuple" else seq


def _signature_key(leaves):
    parts = []
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            parts.append(f"T{tuple(leaf._data.shape)}:{leaf._data.dtype}"
                         f":{leaf.stop_gradient}")
        else:
            try:
                parts.append(f"V{type(leaf).__name__}:{leaf!r}")
            except Exception:
                parts.append(f"V{type(leaf).__name__}:?")
    return "|".join(parts)


class _CompiledGraph:
    __slots__ = ("written", "read_only", "jitted", "pure_fn", "guard_log",
                 "call_avals")

    def __init__(self, written, read_only, jitted, pure_fn, guard_log):
        self.written = written       # state the discovery run reassigned
        self.read_only = read_only   # state it only read
        self.jitted = jitted
        self.pure_fn = pure_fn
        self.guard_log = guard_log   # [(kind, value)] from discovery
        self.call_avals = None       # shapes of the first compiled run


class _SigEntry:
    """Specializations for one input signature, keyed by the recorded
    concretization log (the branch-decision vector)."""

    __slots__ = ("by_key", "latest_key", "mispredicts")

    def __init__(self):
        self.by_key: dict = {}
        self.latest_key = None
        self.mispredicts = 0


class _GuardMismatch(Exception):
    pass


#: CONSECUTIVE mispredict budget per signature before giving up on
#: compilation (pathologically alternating branches); any successful
#: guard-hit compiled run resets the counter, so occasional flips over a
#: long training run never deoptimize
_MAX_MISPREDICTS = 16

_TRACE_ERRORS = (jax.errors.TracerBoolConversionError,
                 jax.errors.ConcretizationTypeError,
                 jax.errors.TracerArrayConversionError,
                 jax.errors.TracerIntegerConversionError,
                 GraphBreak)


class StaticFunction:
    def __init__(self, function: Callable, input_spec=None,
                 build_strategy=None, backend=None, full_graph=False):
        functools.update_wrapper(self, function)
        self._fn = function
        self._name = getattr(function, "__name__", "fn")
        self._input_spec = input_spec
        self._graphs: dict[str, _SigEntry] = {}
        self._fallback_sigs: set[str] = set()
        self._instance = None
        self._enabled = not getattr(function,
                                    "_paddle_tpu_not_to_static", False)
        # run-mode telemetry (hapi fit attribution + tests): how many
        # calls executed as the compiled program vs python (discovery
        # runs and eager fallbacks both count as eager host work)
        self.n_compiled_runs = 0
        self.n_eager_runs = 0
        # cumulative wall seconds inside _discover (eager discovery run
        # + trace/graph construction) — the host-visible recompile cost
        # the goodput ledger books against the "recompile" category
        self.compile_seconds = 0.0

    # descriptor protocol so @to_static works on Layer methods; the bound
    # copy is cached per instance (each instance has its own parameters ⇒
    # its own discovered state and compile cache)
    def __get__(self, instance, owner):
        if instance is None:
            return self
        cache_name = f"__static_fn_{id(self)}"
        bound = instance.__dict__.get(cache_name)
        if bound is None:
            bound = StaticFunction(self._fn, self._input_spec)
            bound._instance = instance
            instance.__dict__[cache_name] = bound
        return bound

    @property
    def function(self):
        return self._fn

    def program_texts(self) -> list[str]:
        """The lowered (StableHLO) text of every program this function
        has RUN compiled — what a check reads to see that a kernel is
        really in the program (a Pallas call is a ``tpu_custom_call``).
        Re-lowering hits jit's trace cache; nothing executes."""
        return [g.jitted.lower(*g.call_avals).as_text()
                for entry in self._graphs.values()
                for g in entry.by_key.values()
                if g.call_avals is not None]

    def rollback(self):
        return self._fn

    def _call_fn(self, *args, **kwargs):
        if self._instance is not None:
            return self._fn(self._instance, *args, **kwargs)
        return self._fn(*args, **kwargs)

    #: flipped by paddle.jit.enable_to_static(False): every StaticFunction
    #: runs its original eager function
    _globally_enabled = True

    def __call__(self, *args, **kwargs):
        with _span("to_static/call", fn=self._name) as call:
            return self._call(call, args, kwargs)

    def _call(self, call, args, kwargs):
        """One call under its ``to_static/call`` span; ``mode`` says how
        it ran: compiled | discover | eager | segmented."""
        if not self._enabled or not StaticFunction._globally_enabled:
            call.set_args(mode="eager")
            self.n_eager_runs += 1
            return self._call_fn(*args, **kwargs)
        with _span("to_static/bind"):
            leaves: list = []
            spec = _tree_flatten((args, kwargs), leaves)
            sig = _signature_key(leaves)
            broken = sig in self._fallback_sigs
            entry = self._graphs.get(sig)
            graph = bound = None
            if not broken and entry is not None \
                    and entry.latest_key is not None:
                graph = entry.by_key[entry.latest_key]
                bound = self._bind(graph, leaves)
        if broken:
            call.set_args(mode="segmented")
            self.n_eager_runs += 1
            return self._call_segmented(sig, args, kwargs)
        if graph is None:
            call.set_args(mode="discover")
            self.n_eager_runs += 1
            return self._discover(sig, spec, leaves, args, kwargs)
        call.set_args(mode="compiled")
        try:
            result = self._run_compiled(graph, *bound)
            self.n_compiled_runs += 1
            entry.mispredicts = 0   # guard-hit run: healthy specialization
            return result
        except _GuardMismatch:
            entry.mispredicts += 1
            if entry.mispredicts > _MAX_MISPREDICTS:
                warnings.warn(
                    f"to_static: {getattr(self._fn, '__name__', '?')} "
                    f"re-specialized more than {_MAX_MISPREDICTS} times "
                    "(unstable data-dependent branches); falling back to "
                    "eager for this signature")
                self._fallback_sigs.add(sig)
                self._graphs.pop(sig, None)
                call.set_args(mode="eager")
                self.n_eager_runs += 1
                return self._call_fn(*args, **kwargs)
            # the discarded run committed nothing; re-run eagerly (correct
            # for the new branch pattern) and re-specialize
            call.set_args(mode="discover")
            self.n_eager_runs += 1
            return self._discover(sig, spec, leaves, args, kwargs)
        except _TRACE_ERRORS as e:
            warnings.warn(
                f"to_static: graph break in "
                f"{getattr(self._fn, '__name__', '?')} "
                f"(data-dependent control flow: {e}); falling back to eager "
                "for this signature")
            self._fallback_sigs.add(sig)
            self._graphs.pop(sig, None)
            call.set_args(mode="eager")
            self.n_eager_runs += 1
            return self._call_fn(*args, **kwargs)

    # ---- broken signatures: compile AROUND the break ---------------------

    def _call_segmented(self, sig, args, kwargs):
        """SOT-style subgraph compilation for a signature with a genuine
        graph break (SURVEY.md §3.5): the function runs ONCE, but op
        dispatches are recorded lazily and flushed as jit-compiled
        SEGMENTS at each point Python actually needs a value (the
        ``float(loss)`` branch, a ``.numpy()`` read). Compiled prefix,
        eager break, compiled suffix — instead of dropping the whole
        signature to per-op eager dispatch. ``_segment_stats`` holds
        (segments_executed, ops_recorded) from the last call (the
        compile-around-break probe used by tests)."""
        from ..framework import segment as _segment
        if sig in getattr(self, "_eager_sigs", set()):
            return self._call_fn(*args, **kwargs)
        rec = _segment.SegmentRecorder()
        try:
            with _segment.segment_mode(rec):
                out = self._call_fn(*args, **kwargs)
        except ValueError as e:
            if "__jax_array__" not in str(e):
                raise
            # the function uses an op that consumes raw arrays outside
            # the apply() funnel — placeholders cannot flow through it
            # (jax 0.9 rejects coercion). segment_mode already rolled
            # back every state mutation, so a plain-eager retry is safe;
            # remember the signature so later calls skip segments
            if not hasattr(self, "_eager_sigs"):
                self._eager_sigs = set()
            self._eager_sigs.add(sig)
            warnings.warn(
                f"to_static: {getattr(self._fn, '__name__', '?')} uses "
                "an op that cannot carry lazy segments; running this "
                "broken signature fully eagerly instead of "
                "compile-around-break")
            return self._call_fn(*args, **kwargs)
        # normalize ESCAPED placeholders: the exit flush made every
        # SegValue concrete, but tensors handed back to the caller must
        # carry real arrays — jax 0.9 rejects __jax_array__ coercion, so
        # a leftover SegValue would crash the first comparison op done
        # on a returned tensor outside segment mode
        leaves: list = []
        _tree_flatten(out, leaves)
        for t in leaves:
            if isinstance(t, Tensor) and \
                    isinstance(t._data, _segment.SegValue):
                t._data = t._data.force()
        self._segment_stats = (rec.flushes, rec.ops_recorded)
        return out

    # ---- pass 1: eager run with state tracking --------------------------

    def _discover(self, sig, spec, leaves, args, kwargs):
        _t0 = _time.perf_counter()
        try:
            with _span("to_static/discover"):
                return self._discover_inner(sig, spec, leaves, args,
                                            kwargs)
        finally:
            self.compile_seconds += _time.perf_counter() - _t0

    def _discover_inner(self, sig, spec, leaves, args, kwargs):
        tracking = StateTracking()
        log: list = []
        with track_state(tracking), record_concretizations(log):
            outputs = self._call_fn(*args, **kwargs)
        # 3-tuple log entries are OBSERVED float reads (SOT partial
        # capture): when only observed (logged/formatted/returned) they
        # ride the compiled program as extra outputs instead of breaking
        # the graph; a misused one (branched on / fed back into tensors)
        # is a genuine break
        unguardable = [(e[0], e[1]) for e in log
                       if not guardable_concretization(e[0], e[1])
                       and not (len(e) == 3 and not e[2].misused)]
        if unguardable:
            kinds = sorted({k for k, _ in unguardable})
            warnings.warn(
                f"to_static: graph break in "
                f"{getattr(self._fn, '__name__', '?')}: {kinds} "
                "concretization(s) pull device values into python in a "
                "way that can change the computation (unguardable — a "
                "replayed stale value would change numerics); running "
                "eagerly for this signature. Observation-only .item() "
                "reads (logging, returning) stay compiled; prefer "
                ".item() over float() inside compiled functions.")
            self._fallback_sigs.add(sig)
            self._graphs.pop(sig, None)
            return outputs
        written = list(tracking.written.values())
        read_only = [t for tid, t in tracking.read.items()
                     if tid not in tracking.written]
        entry = self._graphs.get(sig)
        if entry is None:
            entry = self._graphs[sig] = _SigEntry()
        # specialization key = the branch-decision vector. Observed float
        # VALUES move every step and decide nothing — key them by site
        # only, or every call would re-specialize
        key = tuple((e[0], e[1]) if len(e) == 2 else (e[0], "<obs>")
                    for e in log)
        if key not in entry.by_key:
            pure_fn = self._make_pure_fn(spec, leaves, written, read_only,
                                         log)
            # argument 0 is the program's to overwrite; _bind decides
            # what goes into it
            jitted = jax.jit(pure_fn, donate_argnums=(0,))
            entry.by_key[key] = _CompiledGraph(written, read_only, jitted,
                                               pure_fn, log)
        entry.latest_key = key
        return outputs

    # ---- the pure function ----------------------------------------------

    def _make_pure_fn(self, spec, proto_leaves, written, read_only,
                      guard_log):
        fn = self._call_fn
        state_list = written + read_only
        # leaf prototypes: for tensors remember stop_gradient; for python
        # values bake in the discovery-call value (sig key guards equality)
        protos = [(True, leaf.stop_gradient) if isinstance(leaf, Tensor)
                  else (False, leaf) for leaf in proto_leaves]
        holder = {}

        def pure_fn(donated, kept, read_arrays, arg_arrays):
            # the written group arrives split by _bind: donated[i] is
            # None where written[i]'s buffer stays the caller's, and
            # those come in kept, in order
            kept = iter(kept)
            state_arrays = [next(kept) if a is None else a
                            for a in donated] + list(read_arrays)
            # _grad_value (not .grad): internal save/restore must neither
            # trigger nor clear the stale-grad warning
            originals = [(t, t._data, t._node, t._grad_value)
                         for t in state_list]
            guards: list = []
            try:
                for t, a in zip(state_list, state_arrays):
                    t._data = a
                    t._node = None
                leaves2, ai = [], 0
                for is_tensor, v in protos:
                    if is_tensor:
                        leaves2.append(Tensor(arg_arrays[ai],
                                              stop_gradient=v))
                        ai += 1
                    else:
                        leaves2.append(v)
                built_args, built_kwargs = _tree_unflatten(spec, leaves2)
                with replay_concretizations(guard_log, guards):
                    outputs = fn(*built_args, **built_kwargs)
                out_leaves: list = []
                out_spec = _tree_flatten(outputs, out_leaves)
                # observed floats in the return value: emit the TRACED
                # scalar instead of baking the stale python value, and
                # remember to convert back to float per call (the "eager
                # read" of the partial-capture scheme)
                obs_ret = []
                out_list = []
                for i, o in enumerate(out_leaves):
                    if isinstance(o, Tensor):
                        out_list.append(o._data)
                    elif isinstance(o, ObservedFloat) and \
                            o._traced is not None:
                        out_list.append(o._traced)
                        obs_ret.append(i)
                    else:
                        out_list.append(o)
                out_arrays = tuple(out_list)
                holder["obs_ret"] = obs_ret
                holder["out_spec"] = out_spec
                holder["out_is_tensor"] = [isinstance(o, Tensor)
                                           for o in out_leaves]
                # every written tensor is an output: a donated buffer
                # that no output aliases is lost. Read-only state is one
                # only if the trace REASSIGNED it after all (identity
                # check against the input tracer): returning untouched
                # weights would force fresh device buffers for the whole
                # model every call
                changed = [i for i, (t, a) in
                           enumerate(zip(read_only, read_arrays))
                           if t._data is not a]
                holder["changed"] = changed
                new_state = tuple(
                    t._data for t in
                    written + [read_only[i] for i in changed])
                # only tracer-backed concretizations become guards
                # (constants were verified equal at trace time). One
                # stacked int64 vector => ONE host sync per step at check
                # time, however many guards there are.
                # the staged dtype must match what the device actually
                # stores: with x64 disabled jnp silently downcasts int64
                # to int32, so guard_expect must wrap identically or an
                # out-of-int32-range guard value would mismatch forever
                # (permanent eager fallback for the signature)
                import jax as _jax
                gdt = jnp.int64 if _jax.config.jax_enable_x64 \
                    else jnp.int32
                if guards:
                    guard_vec = jnp.stack(
                        [jnp.asarray(g).astype(gdt).reshape(())
                         for g, _, _ in guards])
                else:
                    guard_vec = ()
                holder["guard_expect"] = np.asarray(
                    [int(v) for _, _, v in guards],
                    dtype=np.int64).astype(np.int64 if gdt == jnp.int64
                                           else np.int32)
                return new_state, out_arrays, guard_vec
            finally:
                for t, d, n, g in originals:
                    t._data = d
                    t._node = n
                    t._grad_value = g

        pure_fn._holder = holder
        # host and module events of the program read jit_to_static_<fn>
        pure_fn.__name__ = pure_fn.__qualname__ = \
            f"to_static_{self._name}"
        return pure_fn

    @staticmethod
    def _bind(graph: _CompiledGraph, leaves):
        """The arrays a compiled call hands its program: the written
        state it gives away, the written state it keeps, the read-only
        state, the call arguments."""
        arg_arrays = tuple(leaf._data for leaf in leaves
                           if isinstance(leaf, Tensor))
        read_arrays = tuple(t._data for t in graph.read_only)
        written = tuple(t._data for t in graph.written)
        if graph.guard_log:
            # a guarded run must be DISCARDABLE on mismatch, and a
            # donated buffer is gone: guarded graphs give nothing away
            return (None,) * len(written), written, read_arrays, arg_arrays
        # a buffer can be given away only if nothing else of this call
        # reads it: one that a call argument or a second state tensor
        # also holds stays the caller's (returned fresh)
        ids = list(map(id, itertools.chain(written, read_arrays,
                                           arg_arrays)))
        if len(set(ids)) == len(ids):
            return written, (), read_arrays, arg_arrays
        held = collections.Counter(ids)
        donated = tuple(a if held[id(a)] == 1 else None for a in written)
        kept = tuple(a for a, d in zip(written, donated) if d is None)
        return donated, kept, read_arrays, arg_arrays

    def _run_compiled(self, graph: _CompiledGraph, *arrays):
        if graph.call_avals is None:
            # keep a sharding only where it spans devices: one-device
            # arrays are uncommitted and follow the others, as in a call
            graph.call_avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding
                    if len(a.sharding.device_set) > 1 else None),
                arrays)
        donated, kept = arrays[:2]      # kept fills donated's Nones
        with _span("to_static/execute"):
            new_state, out_arrays, guard_vec = graph.jitted(*arrays)
        _c_calls.inc()
        _c_outputs.inc(len(new_state) + len(out_arrays))
        _c_donated.inc(len(donated) - len(kept))
        with _span("to_static/commit"):
            return self._commit(graph, new_state, out_arrays, guard_vec)

    @staticmethod
    def _commit(graph, new_state, out_arrays, guard_vec):
        holder = graph.pure_fn._holder
        # verify the guarded branch decisions BEFORE committing state —
        # a mismatched run must leave no trace (its outputs followed the
        # wrong branch). Single stacked vector: one host sync.
        expect = holder.get("guard_expect")
        if expect is not None and expect.size:
            if not np.array_equal(np.asarray(guard_vec), expect):
                raise _GuardMismatch()
        reassigned = graph.written + [graph.read_only[i]
                                      for i in holder["changed"]]
        for t, a in zip(reassigned, new_state):
            t.set_data(a)
            if not t._stop_gradient:
                t._grad_stale = True
        obs = set(holder.get("obs_ret", ()))
        out_leaves = [Tensor(a) if is_t else
                      (float(a) if i in obs else a)
                      for i, (a, is_t) in enumerate(
                          zip(out_arrays, holder["out_is_tensor"]))]
        return _tree_unflatten(holder["out_spec"], out_leaves)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """Decorator/wrapper converting an imperative function or a Layer into a
    compiled whole-program (paddle.jit.to_static parity).

    What a compiled call consumes: the persistable state the function
    REASSIGNS (parameters and optimizer slots of a train step, an RNG
    key) is donated to the program, which writes the new values into the
    same device buffers, so the arrays those tensors held BEFORE the call
    are deleted by it. The tensors themselves are rebound and stay valid.
    ``p.detach()`` shares the parameter's buffer, as in Paddle, and reads
    of it raise after the next compiled step; a snapshot that must
    outlive a step is ``p.clone()`` or ``p.numpy()``. State the function
    only reads (the weights of an inference function) and the call's
    arguments are never donated, and graphs with guarded branches donate
    nothing (a mispredicted run must be discardable)."""

    def decorate(fn):
        from ..nn.layer.layers import Layer
        if isinstance(fn, Layer):
            static_fwd = StaticFunction(type(fn).forward, input_spec)
            static_fwd._instance = fn
            fn.forward = static_fwd
            return fn
        return StaticFunction(fn, input_spec, build_strategy, backend,
                              full_graph)
    if function is not None:
        return decorate(function)
    return decorate
