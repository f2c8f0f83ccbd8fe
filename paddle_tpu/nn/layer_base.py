"""nn.Layer: the module base class.

Reference parity: ``paddle.nn.Layer`` (python/paddle/fluid/dygraph/layers.py:84)
— parameter/buffer/sublayer registries, hooks, state_dict, train/eval mode.
TPU-native design: parameters are ordinary framework Tensors holding jax.Arrays
(functionally immutable payloads swapped in-place by the optimizer), so a whole
``Layer.forward`` traces cleanly under ``to_static``/jit.
"""
from __future__ import annotations

import collections
import re
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import tensor as tensor_mod
from ..core.tensor import Tensor, to_tensor
from ..core import dtype as dtype_mod
from . import initializer as I


class Parameter(Tensor):
    """A trainable Tensor (reference: fluid/framework.py Parameter).

    ``stop_gradient`` defaults to False and the payload participates in
    state_dict/optimizer walks.
    """

    def __init__(self, data, trainable=True, name=None):
        arr = data._value() if isinstance(data, Tensor) else jnp.asarray(data)
        super().__init__()
        self._data = arr
        self.stop_gradient = not trainable
        self.trainable = trainable
        self.persistable = True
        self.name = name or ""

    @property
    def is_parameter(self):
        return True

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


# global per-class name counters for full_name() parity
_layer_name_counters: Dict[str, int] = collections.defaultdict(int)

_NOT_SCOPE = re.compile(r"[^A-Za-z0-9_.]")


def _held_as(parent, sublayer, name) -> None:
    """The key under which ``parent`` holds ``sublayer`` is the scope the
    sublayer's calls open while ``to_static`` traces (``Layer.__call__``):
    ``gpt``, ``attn``, ``qkv_proj``.  A list or dict of layers is never
    called itself (it has no ``forward``), so its members read its name
    before their own: ``layers/3``.  A layer held twice reads the name
    given last."""
    if sublayer is None:
        return
    scope = _NOT_SCOPE.sub("_", str(name))
    if type(parent).forward is Layer.forward \
            and "_scope_name" in parent.__dict__:
        scope = f"{parent.__dict__['_scope_name']}/{scope}"
    object.__setattr__(sublayer, "_scope_name", scope)
    if type(sublayer).forward is Layer.forward:
        for key, member in sublayer._sub_layers.items():
            _held_as(sublayer, member, key)


class HookRemoveHelper:
    def __init__(self, hooks: dict, key):
        self._hooks = hooks
        self._key = key

    def remove(self):
        self._hooks.pop(self._key, None)


class Layer:
    """Base class for all network layers (reference: dygraph/layers.py:84)."""

    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        cls = self.__class__.__name__.lower()
        scope = name_scope or cls
        idx = _layer_name_counters[scope]
        _layer_name_counters[scope] += 1
        self._full_name = f"{scope}_{idx}"
        self._dtype = dtype_mod.convert_dtype(dtype) if dtype else None
        self.training = True
        self._parameters: "collections.OrderedDict[str, Parameter]" = collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = collections.OrderedDict()
        self._forward_pre_hooks: "collections.OrderedDict" = collections.OrderedDict()
        self._forward_post_hooks: "collections.OrderedDict" = collections.OrderedDict()
        self._hook_id = 0

    # -- naming -----------------------------------------------------------

    def full_name(self) -> str:
        return self._full_name

    # -- mode -------------------------------------------------------------

    def train(self):
        self.training = True
        for l in self.sublayers(include_self=False):
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers(include_self=False):
            l.training = False
        return self

    # -- registration ------------------------------------------------------

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError(f"add_parameter expects Parameter, got {type(parameter)}")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        if sublayer is not None and not isinstance(sublayer, Layer):
            raise TypeError(f"add_sublayer expects Layer, got {type(sublayer)}")
        self._sub_layers[name] = sublayer
        _held_as(self, sublayer, name)
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = to_tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias: bool = False,
        default_initializer=None,
    ) -> Parameter:
        """Create+register-free parameter (caller assigns it to an attribute).

        ``attr`` mirrors paddle.ParamAttr: may carry name/initializer/trainable;
        plain initializers and None are accepted.
        """
        dtype = dtype_mod.convert_dtype(dtype or self._dtype or "float32")
        init = default_initializer
        trainable = True
        name = None
        if attr is False:
            return None
        attr_init = None
        if attr is not None:
            attr_init = getattr(attr, "initializer", None)
            trainable = getattr(attr, "trainable", True)
            name = getattr(attr, "name", None)
            if isinstance(attr, I.Initializer):
                attr_init = attr
        # precedence (reference set_global_initializer contract): explicit
        # ParamAttr initializer > global initializer > layer default
        if attr_init is not None:
            init = attr_init
        else:
            g = I._get_global_initializer() if hasattr(
                I, "_get_global_initializer") else None
            if g is not None and (g[1] if is_bias else g[0]) is not None:
                init = g[1] if is_bias else g[0]
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        arr = init(shape, dtype)
        return Parameter(arr, trainable=trainable, name=name)

    # -- attribute magic ---------------------------------------------------

    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            if buffers is not None and name in buffers:
                del buffers[name]
            params[name] = value
            if not value.name:
                value.name = f"{self._full_name}.{name}"
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            _held_as(self, value, name)
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params[name] = None
            else:
                raise TypeError(f"cannot assign non-Parameter to parameter {name!r}")
        elif layers is not None and name in layers:
            if value is None:
                layers[name] = None
            else:
                raise TypeError(f"cannot assign non-Layer to sublayer {name!r}")
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                buffers[name] = to_tensor(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for registry in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(registry)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{self.__class__.__name__}' object has no attribute {name!r}"
        )

    def __delattr__(self, name):
        for registry in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(registry)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = []
        for registry in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(registry)
            if d:
                extra.extend(d.keys())
        return list(super().__dir__()) + extra

    # -- traversal ---------------------------------------------------------

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield from l.named_sublayers(prefix=p, include_self=True, layers_set=layers_set)

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for lp, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{lp}.{name}" if lp else name), p

    def buffers(self, include_sublayers: bool = True) -> List[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{lp}.{name}" if lp else name), b

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- hooks -------------------------------------------------------------

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- state dict --------------------------------------------------------

    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip("."), include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip("."), include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers.get(part, owner)
            if short in getattr(owner, "_non_persistable_buffer_names", ()):
                continue
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Load values into existing parameters/buffers (shape-checked)."""
        missing, unexpected = [], []
        own = self.state_dict()
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            t = own[k]
            arr = v._value() if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            if tuple(arr.shape) != tuple(t.shape):
                raise ValueError(
                    f"state_dict shape mismatch for {k}: {list(arr.shape)} vs {t.shape}"
                )
            t._set_data(jnp.asarray(arr, dtype=t._value().dtype))
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype / device ----------------------------------------------------

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = dtype_mod.convert_dtype(dtype)
            for p in self.parameters():
                if dtype_mod.is_floating_point(p.dtype):
                    p._set_data(p._value().astype(dt))
            for b in self.buffers():
                if b is not None and dtype_mod.is_floating_point(b.dtype):
                    b._set_data(b._value().astype(dt))
            self._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- call --------------------------------------------------------------

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        if tensor_mod._trace_hook is None:
            outputs = self.forward(*inputs, **kwargs)
        else:
            # while ``to_static`` traces, a layer's call is a scope: every
            # operation's op_name in the compiled program then carries the
            # path of the layers it was built under (``gpt/h/3/attn/qkv_proj``),
            # which ``obs.hlo_cost.scope_map`` reads back.  The scope exists
            # at trace time only; an eager call pays the read above
            with jax.named_scope(self.__dict__.get("_scope_name")
                                 or type(self).__name__):
                outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # -- misc --------------------------------------------------------------

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_gradient()

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            body = repr(l).split("\n")
            body = [body[0]] + ["  " + b for b in body[1:]]
            lines.append(f"({name}): " + "\n".join(body))
        main = self.__class__.__name__ + "("
        if extra and not lines:
            return main + extra + ")"
        if lines:
            return main + (extra + "\n  " if extra else "\n  ") + "\n  ".join(
                "\n  ".join(l.split("\n")) for l in lines
            ) + "\n)"
        return main + ")"


class ParamAttr:
    """Mirror of paddle.ParamAttr: bundles name/initializer/trainable/lr."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip
