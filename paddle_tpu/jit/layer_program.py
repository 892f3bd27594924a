"""One compiled program per kind of layer, shared by the layers of that kind.

A deep model repeats a few kinds of block. Called op by op, each block of
each kind is traced, lowered and (eagerly, as ``to_static``'s first call
runs) executed op by op at full size. :class:`LayerPrograms` instead makes
each kind ONE ``jax.jit`` function of ``(inputs, the layer's parameters)``:
it traces a template layer of the kind with its parameters rebound to the
arguments, and every layer of that kind calls the same function with its
own parameters, recorded on the tape as one node. Inside a ``to_static``
trace the function inlines; in eager mode it runs as one compiled program.
With ``remat`` the function is wrapped in ``incubate.recompute``'s
checkpoint, so the backward recomputes the block's activations.

Layers given one ``kind`` must share their structure and every buffer the
forward reads (a buffer is taken from the template, not passed); only
their parameters may differ.

    programs = LayerPrograms()
    for layer in self.layers:
        x = programs(layer, layer.kind, x, remat=self.training)
"""

from __future__ import annotations

import weakref

__all__ = ["LayerPrograms"]


class LayerPrograms:
    def __init__(self):
        self._fns = {}

    def function(self, layer, kind, remat=False):
        """The jitted ``f(*inputs, *parameters) -> outputs`` of ``kind``
        (amp's state is part of the key: it changes what is traced)."""
        import jax

        from ..amp.auto_cast import amp_state
        amp = amp_state()
        key = (kind, bool(remat), amp.enabled,
               str(amp.dtype) if amp.enabled else None)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        # a weak reference: a program the jit caches outlive must not keep
        # the template's parameters alive after the model is gone
        template = weakref.ref(layer)

        def pure(*arrays):
            from ..framework.core import Tensor, no_grad
            lay = template()
            leaves = lay.parameters()
            n_in = len(arrays) - len(leaves)
            held = [p._data for p in leaves]
            try:
                for p, a in zip(leaves, arrays[n_in:]):
                    p._data = a
                with no_grad():
                    out = lay(*[Tensor(a) for a in arrays[:n_in]])
            finally:
                for p, a in zip(leaves, held):
                    p._data = a
            return out._data if isinstance(out, Tensor) else \
                tuple(o._data for o in out)

        if remat:
            from ..incubate.recompute import checkpoint_with_policy
            pure = checkpoint_with_policy(pure)
        fn = self._fns[key] = jax.jit(pure)
        return fn

    def __call__(self, layer, kind, *inputs, remat=False, n_outputs=1,
                 name="layer_program"):
        """``layer(*inputs)`` through its kind's program, as one tape
        node."""
        from ..framework.core import apply
        return apply(self.function(layer, kind, remat), *inputs,
                     *layer.parameters(), n_outputs=n_outputs, name=name)
