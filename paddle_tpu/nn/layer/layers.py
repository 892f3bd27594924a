"""``paddle.nn.Layer`` — the module system (python/paddle/nn/layer/layers.py
parity, UNVERIFIED).  Layers are mutable containers of Parameters/buffers/
sublayers with hooks and state_dict; execution stays functional underneath
(parameters are persistable Tensors the jit functionalizer captures)."""

from __future__ import annotations

import collections
from typing import Callable, Iterator

import jax.numpy as jnp
import numpy as np

from ...framework.core import Tensor, Parameter, to_jax_dtype, is_floating
from ...framework.default_dtype import get_default_dtype
from .. import initializer as I

__all__ = ["Layer"]


class HookRemoveHelper:
    def __init__(self, hooks: dict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self) -> None:
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self.training = True
        self._dtype = to_jax_dtype(dtype) if dtype else get_default_dtype()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ---- attribute routing ----------------------------------------------

    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None:
                buffers[name] = None
            else:
                buffers[name] = value if isinstance(value, Tensor) \
                    else Tensor(value)
                buffers[name].persistable = True
        else:
            if params is not None and name in params and value is None:
                params.pop(name)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._sub_layers) + list(self._buffers)

    # ---- construction helpers -------------------------------------------

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """Mirrors Layer.create_parameter: resolves ParamAttr + initializer."""
        from ..param_attr import ParamAttr
        dtype = to_jax_dtype(dtype) if dtype is not None else self._dtype
        attr = ParamAttr._to_attr(attr)
        init = None
        if attr is not None and attr.initializer is not None:
            init = attr.initializer
        elif default_initializer is not None:
            init = default_initializer
        else:
            init = I.global_initializer(is_bias)
            if init is None:
                init = I.Constant(0.0) if is_bias else I.XavierNormal()
        data = init(tuple(int(s) for s in shape), dtype)
        trainable = attr.trainable if attr is not None else True
        p = Parameter(data, trainable=trainable,
                      name=(attr.name if attr is not None else "") or "")
        if attr is not None:
            p.optimize_attr = {"learning_rate": attr.learning_rate}
            p.regularizer = attr.regularizer
        return p

    def add_parameter(self, name: str, parameter: Parameter | None):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Tensor | None,
                        persistable: bool = True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        if tensor is not None:
            tensor.persistable = True
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ---- iteration -------------------------------------------------------

    def parameters(self, include_sublayers: bool = True) -> list[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix: str = "",
                         include_sublayers: bool = True
                         ) -> Iterator[tuple[str, Parameter]]:
        seen = set()
        for name, layer in self._walk(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p

    def buffers(self, include_sublayers: bool = True) -> list[Tensor]:
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True
                      ) -> Iterator[tuple[str, Tensor]]:
        seen = set()
        for name, layer in self._walk(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator[tuple[str, "Layer"]]:
        seen = set()
        for name, layer in self._sub_layers.items():
            if layer is not None and id(layer) not in seen:
                seen.add(id(layer))
                yield name, layer

    def sublayers(self, include_self: bool = False) -> list["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix: str = "", include_self: bool = False,
                        layers_set=None) -> Iterator[tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self.named_children():
            if layer is None or id(layer) in layers_set:
                continue
            layers_set.add(id(layer))
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, layer
            yield from layer.named_sublayers(prefix=sub_prefix,
                                             include_self=False,
                                             layers_set=layers_set)

    def _walk(self, prefix: str, include_sublayers: bool):
        yield prefix, self
        if include_sublayers:
            yield from self.named_sublayers(prefix=prefix)

    # ---- modes / transforms ---------------------------------------------

    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dtype = to_jax_dtype(dtype)
            for p in self.parameters():
                if is_floating(p.dtype):
                    p.set_data(p._data.astype(dtype))
            for b in self.buffers():
                if is_floating(b.dtype):
                    b.set_data(b._data.astype(dtype))
            for l in self.sublayers(include_self=True):
                l._dtype = dtype
        return self

    def astype(self, dtype=None):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    # ---- state dict ------------------------------------------------------

    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", use_hook: bool = True):
        dest = destination if destination is not None else \
            collections.OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, layer in [("", self)] + (
                list(self.named_sublayers(
                    prefix=structured_name_prefix.rstrip(".")))
                if include_sublayers else []):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                key = f"{name}.{bname}" if name else bname
                dest[key] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        own = self.state_dict()
        missing, unexpected = [], []
        for key, target in own.items():
            if key in state_dict:
                src = state_dict[key]
                data = src._data if isinstance(src, Tensor) else \
                    jnp.asarray(np.asarray(src))
                if tuple(data.shape) != tuple(target._data.shape):
                    raise ValueError(
                        f"shape mismatch for {key}: loaded "
                        f"{tuple(data.shape)} vs param "
                        f"{tuple(target._data.shape)}")
                # from a tensor, a copy: it may be live state that a
                # compiled step donates (its old buffer is then deleted)
                target.set_data(
                    jnp.array(data, dtype=target._data.dtype)
                    if isinstance(src, Tensor)
                    else data.astype(target.dtype))
            else:
                missing.append(key)
        for key in state_dict:
            if key not in own:
                unexpected.append(key)
        return missing, unexpected

    load_dict = set_state_dict

    # ---- hooks -----------------------------------------------------------

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ---- call ------------------------------------------------------------

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    # ---- misc ------------------------------------------------------------

    def full_name(self) -> str:
        return self._name_scope

    def clear_gradients(self) -> None:
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, child in self.named_children():
            mod_str = repr(child)
            mod_str = "\n".join(
                ["  " + l for l in mod_str.split("\n")])
            lines.append(f"  ({name}): {mod_str.strip()}" if "\n" not in
                         mod_str else f"  ({name}): {mod_str.lstrip()}")
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
