"""Layer base class (reference: python/paddle/nn/layer/layers.py `Layer`).

Parameters are Tensors with stop_gradient=False; sublayers auto-register via
__setattr__. state_dict round-trips through paddle_tpu.framework.io_.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Callable, Iterator

import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dtype import get_default_dtype, to_jax_dtype
from paddle_tpu.core.tensor import Tensor

__all__ = ["Layer", "Parameter", "Sequential", "LayerList", "ParameterList", "LayerDict", "Identity"]


class Parameter(Tensor):
    """Trainable tensor (reference: EagerParamBase, base/framework.py)."""

    def __init__(self, value, trainable: bool = True, name: str | None = None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def __repr__(self):
        return "Parameter " + super().__repr__()


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        self._dtype = dtype
        self.training = True
        self._forward_pre_hooks: OrderedDict = OrderedDict()
        self._forward_post_hooks: OrderedDict = OrderedDict()
        self._casted_dtype = None

    # ---- registration -----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        subs = self.__dict__.get("_sub_layers")
        bufs = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if subs is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            subs[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                if value is None:
                    del params[name]
                else:
                    params[name] = value
                    return
            if subs is not None and name in subs and value is None:
                del subs[name]
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # ---- parameter creation ----------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from paddle_tpu.nn import initializer as I

        dtype = dtype or self._dtype or get_default_dtype()
        init = None
        name = None
        trainable = True
        if attr is not None and attr is not False:
            init = getattr(attr, "initializer", None)
            name = getattr(attr, "name", None)
            trainable = getattr(attr, "trainable", True)
        if attr is False:
            return None
        if init is None:
            init = default_initializer or (I.Constant(0.0) if is_bias else I.XavierUniform())
        value = init(shape, to_jax_dtype(dtype))
        return Parameter(value, trainable=trainable, name=name)

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(jnp.asarray(tensor))
        if tensor is not None:
            tensor.persistable = persistable
        self._buffers[name] = tensor
        return tensor

    # ---- traversal --------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False) -> Iterator:
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield p, sub
            yield from sub.named_sublayers(prefix=p)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator:
        seen = set()
        for name, p in self._parameters.items():
            if p is None or id(p) in seen:
                continue
            seen.add(id(p))
            yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{lname}" if prefix else lname
                for n, p in sub.named_parameters(prefix=sp):
                    if id(p) in seen:
                        continue
                    seen.add(id(p))
                    yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is None:
                continue
            yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sp = f"{prefix}.{lname}" if prefix else lname
                yield from sub.named_buffers(prefix=sp)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # ---- modes ------------------------------------------------------------
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

    def apply(self, fn: Callable):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ---- state dict -------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        out = destination if destination is not None else OrderedDict()
        for n, p in self.named_parameters(include_sublayers=include_sublayers):
            out[n] = p
        for n, b in self.named_buffers(include_sublayers=include_sublayers):
            if b.persistable:
                out[n] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            val = v._value if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            tgt = own[k]
            if tuple(val.shape) != tuple(tgt._value.shape):
                raise ValueError(f"shape mismatch for '{k}': {val.shape} vs {tgt._value.shape}")
            tgt._set_value(val.astype(tgt._value.dtype))
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = to_jax_dtype(dtype)
            for p in self.parameters():
                # `keep_dtype`: a value that accumulates small steps (a
                # router's correction bias) stays in the type it was made in
                if (jnp.issubdtype(p._value.dtype, np.floating)
                        and not getattr(p, "keep_dtype", False)):
                    p._set_value(p._value.astype(d))
            for b in self.buffers():
                if jnp.issubdtype(b._value.dtype, np.floating):
                    b._set_value(b._value.astype(d))
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ---- hooks ------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        key = len(self._forward_pre_hooks)
        self._forward_pre_hooks[key] = hook
        return _HookHandle(self._forward_pre_hooks, key)

    def register_forward_post_hook(self, hook):
        key = len(self._forward_post_hooks)
        self._forward_post_hooks[key] = hook
        return _HookHandle(self._forward_post_hooks, key)

    # ---- call -------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{type(self).__name__}({extra}"]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        return "\n".join(lines) + ")"

    def full_name(self):
        return type(self).__name__.lower()

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class _HookHandle:
    def __init__(self, store, key):
        self._store = store
        self._key = key

    def remove(self):
        self._store.pop(self._key, None)


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and not isinstance(layers[0], Layer):
            layers = layers[0]
        if len(layers) and isinstance(layers[0], tuple) and not isinstance(layers[0], Layer):
            for name, layer in layers:
                self.add_sublayer(str(name), layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x

    def __getitem__(self, idx):
        vals = list(self._sub_layers.values())
        if isinstance(idx, slice):
            return Sequential(*vals[idx])
        return vals[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self

    def insert(self, index, layer):
        vals = list(self._sub_layers.values())
        vals.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(vals):
            self._sub_layers[str(i)] = l

    def __getitem__(self, idx):
        vals = list(self._sub_layers.values())
        if isinstance(idx, slice):
            return LayerList(vals[idx])
        return vals[idx]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self

    def __getitem__(self, idx):
        return list(self._parameters.values())[idx]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers:
            for k, v in (sublayers.items() if isinstance(sublayers, dict) else sublayers):
                self.add_sublayer(k, v)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __len__(self):
        return len(self._sub_layers)

    def keys(self):
        return self._sub_layers.keys()

    def items(self):
        return self._sub_layers.items()

    def values(self):
        return self._sub_layers.values()


class Identity(Layer):
    def forward(self, x):
        return x
