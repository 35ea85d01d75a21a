"""Layer: the module base class.

Redesign of the reference's ``fluid.dygraph.Layer``
(reference: python/paddle/fluid/dygraph/layers.py — parameters/sublayers
registries, hooks, state_dict, train/eval). Parameters are eager
:class:`Parameter` tensors; the jit path extracts them as a flat dict pytree
(see paddle_tpu/jit) so the same Layer drives both eager and compiled modes.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ..core import dtypes
from ..core.tensor import Parameter, Tensor, no_grad
from .initializer import Initializer, ParamAttr, XavierNormal, _resolve_attr

__all__ = ["Layer", "Sequential", "LayerList", "ParameterList", "LayerDict"]


class HookRemoveHelper:
    def __init__(self, hooks: dict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


#: the fixed vocabulary of block scopes: device time splits by these
#: (jit/aot.py ``scopes``), whatever XLA names its fusions
BLOCKS = ("embed", "attn", "ffn", "moe", "norm", "loss", "optimizer",
          "sampling", "kv_write", "mla", "indexer", "select", "mhc", "conv")


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self._parameters: Dict[str, Optional[Parameter]] = collections.OrderedDict()
        self._buffers: Dict[str, Optional[Tensor]] = collections.OrderedDict()
        self._sub_layers: Dict[str, "Layer"] = collections.OrderedDict()
        self._non_persistable_buffer_names: set = set()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._hook_id = 0
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype)
        self._name_scope = name_scope or self.__class__.__name__.lower()

    #: the block of the model this layer's device operations count under
    #: (``__call__`` enters ``jax.named_scope(block)``: HLO metadata,
    #: which changes no compiled code and keys no retrace): one word of
    #: :data:`BLOCKS`, set on a class or on an instance; None = the
    #: enclosing layer's
    block: Optional[str] = None

    # -- attribute plumbing -------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
                del params[name]
            if layers is not None and name in layers:
                del layers[name]
            if buffers is not None and name in buffers:
                if isinstance(value, Tensor) or value is None:
                    buffers[name] = value
                    return
                del buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._buffers) + list(self._sub_layers)

    # -- parameter creation -------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Optional[Parameter]:
        """Create a Parameter (reference: layers.py create_parameter)."""
        if attr is False:
            return None
        dtype = dtypes.convert_dtype(dtype) or self._dtype
        if default_initializer is None:
            from .initializer import Constant, XavierUniform
            default_initializer = Constant(0.0) if is_bias else XavierUniform()
        resolved = _resolve_attr(attr, default_initializer)
        if resolved is None:
            return None
        init, trainable, name = resolved
        data = init(shape, dtype)
        p = Parameter(data, name=name, trainable=trainable)
        if isinstance(attr, ParamAttr):
            p.optimize_attr["learning_rate"] = attr.learning_rate
            p.regularizer = attr.regularizer
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False, layers_set=None
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            p = prefix + ("." if prefix else "") + name
            yield from layer.named_sublayers(prefix=p, include_self=True,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for layer_name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (layer_name + ("." if layer_name else "") + pname, p)

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters()]

    def named_buffers(self, prefix="", include_sublayers=True,
                      include_non_persistable=True
                      ) -> Iterator[Tuple[str, Tensor]]:
        seen = set()
        for layer_name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                if (not include_non_persistable
                        and bname in layer._non_persistable_buffer_names):
                    continue
                yield (layer_name + ("." if layer_name else "") + bname, b)

    def buffers(self, include_sublayers=True) -> List[Tensor]:
        return [b for _, b in self.named_buffers()]

    # -- mode & application -------------------------------------------------
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
            dtype = dtypes.convert_dtype(dtype)
        with no_grad():
            for _, p in self.named_parameters():
                d = dtype if (dtype is not None and dtypes.is_floating_point(p.dtype)) else None
                new = p.to(device=device, dtype=d)
                p._data = new._data
            for _, b in self.named_buffers():
                d = dtype if (dtype is not None and dtypes.is_floating_point(b.dtype)) else None
                new = b.to(device=device, dtype=d)
                b._data = new._data
        if dtype is not None:
            self._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        if self.block is None:
            outputs = self.forward(*inputs, **kwargs)
        else:
            with jax.named_scope(self.block):
                outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True) -> Dict[str, Tensor]:
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix,
                                          include_non_persistable=False):
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Load values into existing parameters/buffers (shape-checked)."""
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            arr = value.data if isinstance(value, Tensor) else np.asarray(value)
            if tuple(arr.shape) != tuple(target.shape):
                raise ValueError(
                    f"shape mismatch for {name}: got {tuple(arr.shape)}, "
                    f"expected {tuple(target.shape)}")
            import jax.numpy as jnp
            target._data = jnp.asarray(arr, target.dtype)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # -- misc ---------------------------------------------------------------
    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            rep = [rep[0]] + ["  " + r for r in rep[1:]]
            lines.append(f"  ({name}): " + "\n".join(rep))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"


class Sequential(Layer):
    """reference: python/paddle/fluid/dygraph/container.py Sequential"""

    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, layer in layers[0].items():
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                if isinstance(layer, tuple):
                    self.add_sublayer(layer[0], layer[1])
                else:
                    self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return self._sub_layers[str(idx if idx >= 0 else len(self) + idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self)), parameter)
        return self


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def keys(self):
        return self._sub_layers.keys()

    def items(self):
        return self._sub_layers.items()

    def values(self):
        return self._sub_layers.values()

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, dict) else sublayers
        for key, layer in items:
            self.add_sublayer(key, layer)
        return self

    def pop(self, key):
        layer = self._sub_layers[key]
        del self._sub_layers[key]
        return layer

    def clear(self):
        self._sub_layers.clear()
