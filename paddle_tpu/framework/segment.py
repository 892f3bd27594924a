"""Lazy-segment executor — compile-around-graph-break (SURVEY.md §3.5).

The reference's SOT compiles the bytecode subgraphs on BOTH sides of a
genuine graph break. Our tracing design has no bytecode: a signature
whose discovery hits an unguardable concretization (``float(loss)``
branched on, ``.numpy()`` mid-function) used to drop the WHOLE function
to eager per-op dispatch. This module recovers the reference behavior
the jax way:

- Under ``segment_mode()``, ``core.apply`` does not execute ops. It
  records each dispatch as a node and returns ``SegValue`` placeholders
  (aval from ``jax.eval_shape`` — shape/dtype flow without compute).
- When Python NEEDS a value — a scalar concretization, ``.numpy()``,
  or any direct jax consumption (``__jax_array__``) — the recorder
  FLUSHES: every recorded node since the last flush is replayed inside
  ONE ``jax.jit`` call (XLA fuses the whole segment), results are bound
  back onto the placeholders, and Python continues eagerly past the
  break into the next segment.
- The function therefore runs as K = (#breaks + 1) compiled segments
  per call — a compiled prefix, the eager break, a compiled suffix —
  exactly the SOT split, with re-tracing per call but XLA compiles
  deduped by jax's HLO-keyed compilation cache.

Autograd composes: in segment mode ``apply`` records a node whose
GradNode re-runs ``jax.vjp`` of the op INSIDE a later segment (the
backward pass is itself recorded and flushed compiled) — a
rematerializing tape, numerically identical to the eager one.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SegValue", "SegmentRecorder", "segment_mode",
           "current_recorder"]


class _SegTLS(threading.local):
    """Segment mode is a PER-THREAD property: a compiled-around-break
    call on the trainer thread must not capture unrelated ops running
    concurrently on other threads (the DevicePrefetcher/DataLoader
    collate threads dispatch jnp work mid-step — recording those as
    lazy placeholders corrupts their shapes)."""

    def __init__(self):
        self.recorder = None


_tls = _SegTLS()
_cache_checked: list = [False]


def current_recorder():
    return _tls.recorder


def _ensure_compile_cache():
    """Segmented flushes re-trace fresh closures every call; without the
    persistent (HLO-keyed) compilation cache, every flush of a LARGE
    segment would also pay a full XLA compile. Configure the cache once
    (``framework.compile_cache`` — a directory placed from outside
    wins)."""
    if _cache_checked[0]:
        return
    _cache_checked[0] = True
    from .compile_cache import ensure_compile_cache
    ensure_compile_cache()


class SegValue:
    """Placeholder for one not-yet-computed op output.

    Carries shape/dtype (from abstract eval) so metadata flows without
    compute; materializes via the recorder on scalar reads, numpy
    export, or direct jax consumption."""

    __slots__ = ("aval", "node", "out_idx", "concrete", "recorder")

    def __init__(self, aval, node, out_idx, recorder):
        self.aval = aval
        self.node = node
        self.out_idx = out_idx
        self.concrete = None
        self.recorder = recorder

    # ---- metadata ---------------------------------------------------------
    @property
    def shape(self):
        return self.concrete.shape if self.concrete is not None \
            else self.aval.shape

    @property
    def dtype(self):
        return self.concrete.dtype if self.concrete is not None \
            else self.aval.dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= int(s)
        return n

    # ---- materialization --------------------------------------------------
    def force(self):
        if self.concrete is None:
            self.recorder.flush()
        return self.concrete

    def __jax_array__(self):
        # any direct jnp/lax consumption outside apply(): materialize.
        # Correct (just unfused) — the safety net for stray jax calls.
        return self.force()

    def __array__(self, dtype=None):
        arr = np.asarray(self.force())
        return arr.astype(dtype) if dtype is not None else arr

    # ---- arithmetic used by the tape (grad accumulation etc.) -------------
    def _bin(self, other, fn, name):
        rec = self.recorder
        return rec.record(fn, [self, other], n_outputs=1, name=name)[0]

    def __add__(self, other):
        return self._bin(other, lambda a, b: a + b, "seg_add")

    __radd__ = __add__

    def __mul__(self, other):
        return self._bin(other, lambda a, b: a * b, "seg_mul")

    __rmul__ = __mul__

    def __sub__(self, other):
        return self._bin(other, lambda a, b: a - b, "seg_sub")

    def __truediv__(self, other):
        return self._bin(other, lambda a, b: a / b, "seg_div")

    def __neg__(self):
        rec = self.recorder
        return rec.record(lambda a: -a, [self], 1, "seg_neg")[0]

    def astype(self, dtype):
        rec = self.recorder
        return rec.record(lambda a: a.astype(dtype), [self], 1,
                          "seg_astype")[0]

    def reshape(self, *shape):
        rec = self.recorder
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return rec.record(lambda a: a.reshape(shape), [self], 1,
                          "seg_reshape")[0]


class _Node:
    __slots__ = ("fn", "args", "kwargs", "n_outputs", "outs", "name")

    def __init__(self, fn, args, kwargs, n_outputs, name):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.n_outputs = n_outputs
        self.outs = None
        self.name = name


class SegmentRecorder:
    """Records apply()-level op dispatches; flushes them as one jitted
    program when a value is needed."""

    def __init__(self):
        self.pending: list[_Node] = []
        self.flushes = 0        # segments executed (the "probe")
        self.ops_recorded = 0
        # (tensor, original value) undo log: segment-mode mutations must
        # be revertible if the call aborts before its final flush (the
        # eager retry must not see half-committed state). FIRST write
        # per tensor only — rollback needs the oldest value, and keeping
        # every intermediate would pin a previous copy of all state for
        # the whole call (double HBM on a large train step).
        self.mutations: list = []
        self._mutated: set = set()

    def log_mutation(self, tensor, old_data):
        key = ("data", id(tensor))
        if key in self._mutated:
            return
        self._mutated.add(key)
        self.mutations.append(("data", tensor, old_data))

    def log_grad_mutation(self, tensor, old_grad):
        key = ("grad", id(tensor))
        if key in self._mutated:
            return
        self._mutated.add(key)
        self.mutations.append(("grad", tensor, old_grad))

    def abort(self):
        """Discard everything pending and restore every tensor mutated
        during this recording (arrays AND grad bindings) to its
        pre-call state."""
        self.pending.clear()
        for kind, t, old in reversed(self.mutations):
            if kind == "data":
                t._data = old
            else:
                t._grad_value = old
        self.mutations.clear()
        self._mutated.clear()

    # ---- recording --------------------------------------------------------
    def record(self, fn, args, n_outputs, name=""):
        """args: list of SegValue | jax array | python scalar. Returns a
        tuple of SegValues (n_outputs)."""
        node = _Node(fn, list(args), {}, n_outputs, name)
        avals = self._eval_shape(node)
        outs = tuple(SegValue(a, node, i, self)
                     for i, a in enumerate(avals))
        node.outs = outs
        self.pending.append(node)
        self.ops_recorded += 1
        return outs

    def record_kw(self, fn, args, kwargs, n_outputs, name=""):
        node = _Node(fn, list(args), dict(kwargs), n_outputs, name)
        avals = self._eval_shape(node)
        outs = tuple(SegValue(a, node, i, self)
                     for i, a in enumerate(avals))
        node.outs = outs
        self.pending.append(node)
        self.ops_recorded += 1
        return outs

    def _eval_shape(self, node):
        def shaped(a):
            if isinstance(a, SegValue):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return a

        args = [shaped(a) for a in node.args]
        out = jax.eval_shape(lambda *a: node.fn(*a, **node.kwargs), *args)
        if node.n_outputs == 1:
            return [out]
        return list(out)

    # ---- flushing ---------------------------------------------------------
    def flush(self):
        """Execute every pending node inside one jit; bind results.

        Each flush wraps a FRESH closure in jax.jit (op closures are
        recreated per call, so executable reuse by structural key would
        risk wrong cache hits on closed-over constants): segmented calls
        re-TRACE per call, and the XLA compile — the expensive part —
        is deduped by the persistent HLO-keyed compilation cache, which
        ``_ensure_compile_cache`` turns on if the app has not."""
        if not self.pending:
            return
        _ensure_compile_cache()
        nodes, self.pending = self.pending, []
        # gather external (concrete) inputs in first-use order
        ext = []
        ext_ids = {}

        def ext_slot(a):
            key = id(a)
            if key not in ext_ids:
                ext_ids[key] = len(ext)
                ext.append(a)
            return ext_ids[key]

        plan = []   # per node: list of ('e', idx) | ('v', node_i, out_i)
        node_index = {id(n): i for i, n in enumerate(nodes)}
        for n in nodes:
            wiring = []
            for a in n.args:
                if isinstance(a, SegValue):
                    if a.concrete is not None:
                        wiring.append(("e", ext_slot(a.concrete)))
                    else:
                        owner = node_index.get(id(a.node))
                        if owner is None:
                            # produced by an even earlier flush
                            wiring.append(("e", ext_slot(a.force())))
                        else:
                            wiring.append(("v", owner, a.out_idx))
                    continue
                if isinstance(a, (jax.Array, np.ndarray)):
                    wiring.append(("e", ext_slot(a)))
                else:
                    wiring.append(("c", a))       # python scalar: bake
            plan.append(wiring)

        def seg_fn(*ext_arrays):
            results = []
            for n, wiring in zip(nodes, plan):
                args = []
                for w in wiring:
                    if w[0] == "e":
                        args.append(ext_arrays[w[1]])
                    elif w[0] == "v":
                        r = results[w[1]]
                        args.append(r[w[2]])
                    else:
                        args.append(w[1])
                out = n.fn(*args, **n.kwargs)
                results.append((out,) if n.n_outputs == 1 else tuple(out))
            flat = [o for r in results for o in r]
            return tuple(flat)

        flat = jax.jit(seg_fn)(*ext)
        i = 0
        for n in nodes:
            for o in n.outs:
                o.concrete = flat[i]
                i += 1
        self.flushes += 1


@contextlib.contextmanager
def segment_mode(recorder: SegmentRecorder):
    prev = _tls.recorder
    _tls.recorder = recorder
    try:
        yield recorder
    except BaseException:
        _tls.recorder = prev
        recorder.abort()   # roll back half-committed state mutations
        raise
    else:
        _tls.recorder = prev
        try:
            recorder.flush()
        except BaseException:
            # the exit flush itself failed (compile OOM, a recorded op
            # erroring under jit): same rollback guarantee applies
            recorder.abort()
            raise
