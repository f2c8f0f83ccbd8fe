"""The paddle_tpu Tensor: an imperative façade over jax.Array.

Reference parity: ``phi::DenseTensor`` (dense_tensor.h:37) + the eager
``paddle::experimental::Tensor`` python object (pybind eager_method.cc).
TPU-native design: the payload is an immutable ``jax.Array`` (or jax tracer,
under to_static capture); imperative semantics (in-place ops, ``.grad``,
version counter) live in this thin python shell.  All compute goes through
``paddle_tpu.core.dispatch`` which records the autograd tape.

Every read of the payload goes through ``_value()`` and every write through
``_set_data()`` so that the to_static tracer (jit/trace.py) can lift
externally-created tensors (parameters, optimizer state, RNG state) into
arguments/results of the compiled program — the trace-based equivalent of the
reference's dy2static variable scoping (run_program_op.cc:221).
"""
from __future__ import annotations

from typing import Any, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from .device import Place, current_place
from . import autograd

# Set by paddle_tpu.jit.trace while a to_static capture is active.

# Trace-time shape-read taint hook — installed by paddle_tpu.static while a
# Program is being recorded.  Signature: fn(tensor, [int]) -> [int]; returns
# SymbolicDim-wrapped entries for dims derived from a None-declared feed so
# closure-baked attrs can be detected (static/program.py).
_shape_taint_hook = None


class SymbolicDim(int):
    """An int read from a feed-derived tensor's shape during static
    recording, carrying WHICH None-declared feeds it may derive from.
    Ops that bake such a value into a closure attribute are flagged;
    Executor.run raises only when one of THOSE feeds is fed a
    contradicting size (reference programs re-infer shapes at run time
    instead)."""

    def __new__(cls, v, feeds=frozenset()):
        self = super().__new__(cls, v)
        self.feeds = frozenset(feeds)
        return self

    def _mix(self, v, o):
        of = o.feeds if isinstance(o, SymbolicDim) else frozenset()
        return SymbolicDim(v, self.feeds | of)

    # arithmetic keeps the taint so `x.shape[0] * n` style attrs are caught;
    # non-int operands (floats etc.) fall back to ordinary numeric semantics
    # — the taint is lost but the value stays correct (0.5 * dim must not
    # become SymbolicDim(0)).
    @staticmethod
    def _intlike(o):
        import numpy as _np
        return (isinstance(o, (int, _np.integer))
                and not isinstance(o, bool))

    def __add__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(self) + int(o), o)

    def __radd__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(o) + int(self), o)

    def __sub__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(self) - int(o), o)

    def __rsub__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(o) - int(self), o)

    def __mul__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(self) * int(o), o)

    def __rmul__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(o) * int(self), o)

    def __floordiv__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(self) // int(o), o)

    def __rfloordiv__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(o) // int(self), o)

    def __mod__(self, o):
        if not self._intlike(o):
            return NotImplemented
        return self._mix(int(self) % int(o), o)

    def __neg__(self): return SymbolicDim(-int(self), self.feeds)

    def __repr__(self):
        return f"SymbolicDim({int(self)}, feeds={sorted(self.feeds)})"


_trace_hook = None

#: serving.sanitize.SyncSanitizer's counting window: when non-None,
#: every host-coercing conversion (numpy/item/tolist/__array__/
#: __float__/__int__/__bool__) reports itself here before converting.
#: Installed only inside a sanitizer decode window — None (one pointer
#: compare per conversion) the rest of the time.
_sync_hook = None


def _active_hook():
    return _trace_hook


def _note_sync(t) -> None:
    h = _sync_hook
    if h is not None:
        h(t)


class Tensor:
    __slots__ = (
        "_data",
        "_grad",
        "_grad_node",
        "stop_gradient",
        "name",
        "persistable",
        "trainable",
        "_version",
        "_backward_hooks",
        # trace-local tags, owner-checked by jit.trace.TraceHook (object
        # identity, never id() — ids of dead tensors get reused)
        "_trace_born",
        "_trace_grad",
        # weakrefs to TapeNodes that consumed this tensor; an in-place op
        # retargets their input entries to the pre-in-place shadow so
        # already-recorded backwards keep routing to the old value
        "_consumers",
        "__weakref__",
    )

    # -- construction -----------------------------------------------------

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True, name=None):
        if data is None:
            arr = None
        else:
            arr = _to_jax_array(data, dtype, place)
        self._data = arr
        self._grad = None
        self._grad_node = None
        self.stop_gradient = stop_gradient
        self.name = name or ""
        self.persistable = False
        self.trainable = True
        self._version = 0
        self._backward_hooks = None
        self._trace_born = None
        self._trace_grad = None
        self._consumers = None
        h = _trace_hook
        if h is not None:
            h.mark_created(self)

    def _init_fields(self, stop_gradient=True, name=None):
        """Initialize every non-payload slot (shared by _wrap, detach and
        any other raw __new__ construction — keep in sync with __slots__
        so no construction path leaves a slot unset)."""
        self._grad = None
        self._grad_node = None
        self.stop_gradient = stop_gradient
        self.name = name or ""
        self.persistable = False
        self.trainable = True
        self._version = 0
        self._backward_hooks = None
        self._trace_born = None
        self._trace_grad = None
        self._consumers = None

    @staticmethod
    def _wrap(arr, stop_gradient=True, name=None) -> "Tensor":
        t = Tensor.__new__(Tensor)
        t._data = arr
        t._init_fields(stop_gradient=stop_gradient, name=name)
        h = _trace_hook
        if h is not None:
            h.mark_created(t)
        return t

    # -- payload access (trace-aware) -------------------------------------

    def _value(self):
        """The jax array for compute.  Trace hook may lift external tensors."""
        h = _trace_hook
        if h is not None:
            return h.read(self)
        return self._data

    def _set_data(self, arr):
        """In-place payload replacement (all in-place ops funnel here)."""
        h = _trace_hook
        if h is not None:
            h.write(self, arr)
        else:
            self._data = arr
        self._version += 1

    def _accumulate_grad(self, g):
        if self._backward_hooks:
            for fn in self._backward_hooks.values():
                out = fn(Tensor._wrap(g, stop_gradient=True))
                if out is not None:
                    g = out._value() if isinstance(out, Tensor) else jnp.asarray(out)
        h = _trace_hook
        cur = h.read_grad_accum(self) if h is not None else self._grad
        new = g if cur is None else cur + g
        if h is not None:
            h.write_grad(self, new)
        else:
            self._grad = new

    # -- metadata ---------------------------------------------------------

    @property
    def shape(self) -> List[int]:
        s = list(self._value().shape)
        h = _shape_taint_hook
        return h(self, s) if h is not None else s

    @property
    def ndim(self) -> int:
        return self._value().ndim

    @property
    def dtype(self):
        return np.dtype(self._value().dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._value().shape)) if self._value().shape else 1

    @property
    def place(self) -> Place:
        d = self._data
        if isinstance(d, jax.Array) and hasattr(d, "devices") and not _is_tracer(d):
            try:
                dev = next(iter(d.devices()))
                kind = "tpu" if dev.platform == "tpu" else "cpu"
                return Place(kind, dev.id)
            except Exception:
                pass
        return current_place()

    @property
    def grad(self) -> Optional["Tensor"]:
        h = _trace_hook
        g = h.read_grad(self) if h is not None else self._grad
        if g is None:
            return None
        return Tensor._wrap(g, stop_gradient=True, name=self.name + "@GRAD")

    @grad.setter
    def grad(self, value):
        if value is None:
            self._clear_grad()
        else:
            g = value._value() if isinstance(value, Tensor) else jnp.asarray(value)
            h = _trace_hook
            if h is not None:
                h.write_grad(self, g)
            else:
                self._grad = g

    def _clear_grad(self):
        h = _trace_hook
        if h is not None:
            h.write_grad(self, None)
        else:
            self._grad = None

    def clear_grad(self):
        self._clear_grad()

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero:
            g = self.grad
            if g is not None:
                zero = jnp.zeros_like(g._value())
                h = _trace_hook
                if h is not None:
                    h.write_grad(self, zero)
                else:
                    self._grad = zero
        else:
            self._clear_grad()

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def inplace_version(self) -> int:
        return self._version

    # -- conversion -------------------------------------------------------

    def numpy(self) -> np.ndarray:
        _note_sync(self)
        return np.asarray(self._value())

    def item(self, *args):
        _note_sync(self)
        v = self._value()
        if args:
            return np.asarray(v).item(*args)
        return np.asarray(v).item()

    def tolist(self):
        _note_sync(self)
        return np.asarray(self._value()).tolist()

    def __array__(self, dtype=None):
        _note_sync(self)
        a = np.asarray(self._value())
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        _note_sync(self)
        return bool(self._value())

    def __format__(self, spec):
        if not spec:
            return str(self)
        v = self._value()
        if v.ndim == 0:
            _note_sync(self)
            return format(v.item(), spec)
        raise TypeError(
            "format spec on a non-scalar Tensor; call .numpy() first")

    def __len__(self):
        s = self._value().shape
        if not s:
            raise TypeError("len() of a 0-d tensor")
        return s[0]

    def __iter__(self):
        # without this, python falls back to the legacy __getitem__
        # iteration protocol, which never terminates because jax clamps
        # out-of-range indices instead of raising IndexError.  Validate
        # the rank EAGERLY (plain method returning a generator), so
        # iter(scalar) raises immediately like len() does.
        s = self._value().shape
        if not s:
            raise TypeError("iteration over a 0-d tensor")
        return (self[i] for i in range(s[0]))

    def __hash__(self):
        return id(self)

    # -- autograd ---------------------------------------------------------

    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def detach(self) -> "Tensor":
        """A tensor SHARING this tensor's storage with autograd cut off
        (reference semantics: detach returns a view — writes through
        either alias are visible to both; `dense_tensor.h:63`
        shallow-copy sharing).  Implemented as a view object delegating
        its payload to the base tensor, since jax arrays are immutable
        and "storage" here is the rebindable payload slot."""
        base = self._base if isinstance(self, _DetachedView) else self
        v = _DetachedView.__new__(_DetachedView)
        v._base = base
        v._init_fields(stop_gradient=True, name=self.name)
        h = _trace_hook
        if h is not None:
            h.mark_created(v)
        return v

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self.stop_gradient = True
        return self

    _hook_counter = 0

    def register_hook(self, hook):
        """Register a grad hook (reference: egr RegisterGradientHook)."""
        if self._backward_hooks is None:
            self._backward_hooks = {}
        Tensor._hook_counter += 1
        key = Tensor._hook_counter
        self._backward_hooks[key] = hook
        tensor = self

        class _Handle:
            def remove(self):
                tensor._backward_hooks.pop(key, None)

        return _Handle()

    def _rebind_from(self, out: "Tensor"):
        """Adopt ``out``'s payload and autograd position (in-place op result).
        The producing TapeNode's output entry is retargeted to ``self`` so the
        backward sweep finds cotangents under this tensor's identity."""
        old_node = self._grad_node
        old_stop = self.stop_gradient
        node = out._grad_node
        if node is not None and any(t is self for t in node.inputs):
            # the producing op consumed `self` PRE-in-place: its input
            # entry must keep the old autograd position, or the node
            # becomes self-referential and upstream grads are dropped
            shadow = Tensor.__new__(Tensor)
            shadow._data = self._data
            shadow._grad = None
            shadow._grad_node = old_node
            shadow.stop_gradient = old_stop
            shadow.name = ""
            shadow.persistable = False
            shadow.trainable = False
            shadow._version = self._version   # pre-in-place version
            shadow._backward_hooks = None
            shadow._trace_born = None
            shadow._trace_grad = None
            shadow._consumers = None
            if old_node is None and not old_stop:
                # leaf requiring grad: cotangents for the pre-in-place
                # value must land on THIS tensor's .grad (reference
                # in-place-on-leaf semantics)
                target = self

                def _route(g, _t=target):
                    _t._accumulate_grad(g._value())
                    return g

                shadow._backward_hooks = {0: _route}
            if old_node is not None:
                # the old producer now emits the PRE-in-place identity
                old_node.outputs = [shadow if o is self else o
                                    for o in old_node.outputs]
            node.inputs = [shadow if t is self else t
                           for t in node.inputs]
            # every EARLIER consumer of `self` recorded the pre-in-place
            # value (vjp residuals are captured by value at forward time),
            # so their backward must deliver cotangents to the old autograd
            # position — retarget their input entries to the shadow
            # (reference: torch's version-counter raises here; capturing by
            # value lets us keep these programs valid AND correct)
            if self._consumers:
                live = []
                for ref in self._consumers:
                    n = ref()
                    if n is None or n.released:
                        continue
                    if n is not node:
                        n.inputs = [shadow if t is self else t
                                    for t in n.inputs]
                    else:
                        live.append(ref)
                self._consumers = live or None
        self._set_data(out._value())
        self._version += 1     # stale backward reads now raise
        self._grad_node = node
        if node is not None:
            node.outputs = [self if o is out else o for o in node.outputs]
        if not out.stop_gradient:
            self.stop_gradient = False
        # static-graph recording: later consumers of `self` must resolve
        # to `out`'s SSA slot, not self's pre-in-place producer
        from . import dispatch as _dispatch_mod

        if _dispatch_mod._static_record_hook is not None:
            _dispatch_mod._static_record_hook(
                "__alias__", None, [out], {}, [self])
        return self

    # -- in-place / value ops ---------------------------------------------

    def set_value(self, value):
        if isinstance(value, Tensor):
            arr = value._value()
        else:
            arr = _to_jax_array(value, self.dtype, None)
        arr = jnp.asarray(arr, dtype=self._value().dtype)
        if tuple(arr.shape) != tuple(self._value().shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._value().shape}"
            )
        self._set_data(arr)
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._set_data(jnp.full_like(self._value(), value))
        return self

    def zero_(self):
        self._set_data(jnp.zeros_like(self._value()))
        return self

    # -- misc -------------------------------------------------------------

    def clone(self) -> "Tensor":
        from . import dispatch

        return dispatch.apply_op("clone", lambda x: x + 0, [self])

    def to(self, *args, **kwargs):
        # to(dtype) / to(device) / to(device, dtype)
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and a.split(":")[0] in ("cpu", "tpu", "gpu"):
                device = a
            else:
                dtype = a
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .device import set_device, current_place

            kind = device.split(":")[0]
            kind = "tpu" if kind in ("gpu", "tpu") else "cpu"
            arr = jax.device_put(out._value(), Place(kind, 0).jax_device)
            out = Tensor._wrap(arr, stop_gradient=out.stop_gradient)
        return out

    def cpu(self):
        return self.to("cpu")

    def pin_memory(self):
        return self

    def cuda(self, *a, **k):
        return self.to("tpu")

    def __repr__(self):
        sg = self.stop_gradient
        d = self._value()
        if _is_tracer(d):
            body = f"<traced {d.aval}>"
        else:
            _note_sync(self)
            body = np.array2string(np.asarray(d), precision=6, separator=", ")
        return (
            f"Tensor(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}, "
            f"place={self.place}, stop_gradient={sg},\n       {body})"
        )

    # astype / math dunders etc. are attached by paddle_tpu.ops at import
    # time via register_tensor_method().


class _DetachedView(Tensor):
    """detach() result: shares the base tensor's payload slot (reference:
    detach returns a storage-sharing view) with its own autograd state.

    The ``_data`` property shadows the base-class slot so EVERY consumer
    — including code reading ``t._data`` directly — sees the base's
    current payload; writes through either alias are visible to both.
    ``_value``/``_set_data`` route through the base so trace-time reads
    and writes carry the BASE identity (the tracer knows the base, not
    the view).  One divergence from the reference: a write through the
    view does not bump the base's inplace version, so a stale-backward
    through earlier consumers computes with their captured pre-write
    residuals instead of raising — values are correct either way."""

    __slots__ = ("_base",)

    @property
    def _data(self):
        return self._base._data

    @_data.setter
    def _data(self, arr):
        self._base._data = arr

    def _value(self):
        return self._base._value()

    def _set_data(self, arr):
        self._base._set_data(arr)
        self._version += 1


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _to_jax_array(data, dtype=None, place=None):
    dt = dtype_mod.convert_dtype(dtype) if dtype is not None else None
    if isinstance(data, Tensor):
        arr = data._value()
        return jnp.asarray(arr, dtype=dt) if dt is not None else arr
    if isinstance(data, (jax.Array,)) or _is_tracer(data):
        return jnp.asarray(data, dtype=dt) if dt is not None else data
    a = np.asarray(data)
    if dt is None and a.dtype == np.float64:
        dt = dtype_mod.get_default_dtype()
    dev = None
    if place is not None:
        dev = place.jax_device if isinstance(place, Place) else None
    arr = jnp.asarray(a, dtype=dt)
    if dev is not None:
        arr = jax.device_put(arr, dev)
    return arr


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor (reference: python/paddle/tensor/creation.py)."""
    if isinstance(place, str):
        kind = place.split(":")[0]
        place = Place("tpu" if kind in ("gpu", "tpu") else "cpu", 0)
    arr = _to_jax_array(data, dtype, place)
    return Tensor._wrap(arr, stop_gradient=stop_gradient)


def register_tensor_method(name, fn):
    """Attach an op as a Tensor method (used by paddle_tpu.ops)."""
    setattr(Tensor, name, fn)


def external_tensor(value, dtype=None) -> Tensor:
    """Create a Tensor treated as *external persistent state* even when
    constructed inside a to_static trace (lazily-created optimizer
    accumulators, scheduler scalars, RNG state — anything that must become a
    program input rather than a baked constant).  The payload is forced
    concrete (ensure_compile_time_eval) because under jax's stackless tracing
    any jnp op inside a trace yields a tracer."""
    with jax.ensure_compile_time_eval():
        if callable(value):
            arr = value()
        else:
            arr = _to_jax_array(np.asarray(value), dtype, None)
    t = Tensor.__new__(Tensor)
    t._data = arr
    t._grad = None
    t._grad_node = None
    t.stop_gradient = True
    t.name = ""
    t.persistable = True
    t.trainable = False
    t._version = 0
    t._backward_hooks = None
    t._trace_born = None
    t._trace_grad = None
    t._consumers = None
    return t
