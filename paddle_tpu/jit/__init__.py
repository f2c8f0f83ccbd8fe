"""paddle.jit: to_static, save/load (reference: fluid/dygraph/jit.py:163,637).

``to_static`` compiles an imperative function (model forward, or a whole
train step including backward and optimizer.step) into one cached XLA
program per input-spec — the reference's StaticFunction + ConcreteProgram
cache (program_translator.py:239,772) with jax.jit as the executor.
"""
from __future__ import annotations

import functools
import hashlib
import os
import traceback
from typing import Any, Callable, List, Optional

import numpy as np
import jax
from jax import export as jax_export
import jax.numpy as jnp

from ..core.tensor import Tensor, to_tensor
from ..core import dtype as dtype_mod
from ..obs import spans as _spans
from .trace import CompiledProgram, _flatten_io, spec_of

# tracer-leak errors: a Tensor whose value exists only inside the trace
# was forced to a concrete python value (bool/int/array) by unconverted
# control flow — mapped back to user source via dy2static.map_trace_error
_TRACER_LEAK_ERRORS = tuple(
    e for e in (getattr(jax.errors, n, None)
                for n in ("TracerBoolConversionError",
                          "TracerArrayConversionError",
                          "TracerIntegerConversionError",
                          "ConcretizationTypeError"))
    if e is not None)


# -- executable-cache miss subscription (ISSUE 13) --------------------------
# Every StaticFunction program-cache miss is one trace + one XLA compile.
# Listeners (obs.CompileLedger) subscribe here to turn each miss into a
# ledger record — cache key, wall seconds, arg specs, attributed call
# site — so steady-state misses become NAMED anomalies instead of a
# mystery latency spike.  With no listener attached the miss path pays
# one falsy check and nothing else.

_compile_listeners: List[Callable[[dict], None]] = []


def subscribe_compiles(listener: Callable[[dict], None]) -> None:
    """Register ``listener(record)`` for every program-cache miss
    (see :class:`paddle_tpu.obs.compile_ledger.CompileLedger` — the
    canonical consumer).  Idempotent per listener object."""
    if listener not in _compile_listeners:
        _compile_listeners.append(listener)


def unsubscribe_compiles(listener: Callable[[dict], None]) -> None:
    try:
        _compile_listeners.remove(listener)
    except ValueError:
        pass


def _compile_call_site() -> str:
    """The innermost stack frame OUTSIDE the framework — who asked for
    this compile.  Only runs on a miss (compiles are seconds; a stack
    walk is microseconds)."""
    here = os.sep + "paddle_tpu" + os.sep
    for fr in reversed(traceback.extract_stack()):
        fn = fr.filename
        if here in fn or (os.sep + "jax" + os.sep) in fn:
            continue
        return f"{fn}:{fr.lineno}"
    return "<framework>"


def _arg_specs_str(leaves: List[Tensor]) -> str:
    return ",".join(f"{t.dtype}[{','.join(str(s) for s in t.shape)}]"
                    for t in leaves)


def _miss_attrs(static_fn, key, leaves) -> dict:
    """What names a program-cache miss: the attributes of its
    ``jit.trace`` / ``jit.compile`` spans and the head of its ledger
    record.  Only computed on a miss."""
    return {
        "fn": getattr(static_fn._fn, "__qualname__",
                      getattr(static_fn._fn, "__name__", "<fn>")),
        "key": hashlib.sha1(repr(key).encode()).hexdigest()[:12],
        "arg_specs": _arg_specs_str(leaves),
        "site": _compile_call_site(),
    }


def _notify_compile(static_fn, key, attrs: dict, seconds: float,
                    executed: bool) -> None:
    prog = static_fn._programs.get(key)
    rec = {
        **attrs,
        "seconds": round(seconds, 6),
        "cache_size": len(static_fn._programs),
        "state_inputs": len(prog.state_keys) if prog is not None else 0,
        # False = trace-only (get_concrete_program: eval_shape discovery,
        # no XLA executable built yet — jax.jit compiles lazily at the
        # first real call)
        "executed": executed,
    }
    for cb in list(_compile_listeners):
        try:
            cb(rec)
        except Exception as e:  # noqa: BLE001 — observers must never
            # break the compile path (or, from the notify-in-finally,
            # mask the first call's REAL exception — e.g. the
            # RESOURCE_EXHAUSTED the bench's OOM-halving matches on)
            import sys
            import traceback as _tb

            print(f"paddle_tpu.jit: compile listener {cb!r} raised "
                  f"{type(e).__name__}: {e} (ignored)", file=sys.stderr)
            _tb.print_exc(file=sys.stderr)


def _build_mapped(prog, leaves):
    """prog.build with tracer-leak errors mapped back to user source."""
    try:
        prog.build(leaves)
    except _TRACER_LEAK_ERRORS as e:
        from .dy2static import map_trace_error

        mapped = map_trace_error(e)
        if mapped is not None:
            raise mapped from e
        raise


class InputSpec:
    """Declarative input signature (reference: paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtype_mod.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"

    def _to_zero_tensor(self) -> Tensor:
        shape = [1 if (s is None or s < 0) else s for s in self.shape]
        return Tensor._wrap(jnp.zeros(shape, dtype=self.dtype),
                            stop_gradient=self.stop_gradient)


class StaticFunction:
    """Callable wrapper caching CompiledPrograms per input spec
    (reference: dygraph_to_static/program_translator.py:239)."""

    def __init__(self, fn, input_spec=None, build_strategy=None,
                 backend=None, donate=True):
        import os

        if not os.environ.get("PADDLE_TPU_NO_AST_CONVERT"):
            # reference program_translator.py:239 — rewrite python
            # if/while/for over tensors into cond/while_loop calls (no-op
            # on functions without convertible control flow)
            from .dy2static import convert_function

            fn = convert_function(fn)
        self._fn = fn
        self._input_spec = input_spec
        self._programs: dict = {}
        self._enabled = True
        self._donate = donate
        functools.update_wrapper(self, fn)

    @property
    def program_cache(self):
        return self._programs

    def last_program(self):
        """The most recently built CompiledProgram (for
        compiled_stats introspection)."""
        if not self._programs:
            raise RuntimeError("no program compiled yet — call the "
                               "function once first")
        return next(reversed(self._programs.values()))

    def _extra_key(self, args):
        """Mode bits that change the traced python path."""
        from ..core.autograd import is_grad_enabled
        from ..nn.layer_base import Layer

        bits = [is_grad_enabled()]
        owner = getattr(self._fn, "__self__", None)
        scan = []
        if isinstance(owner, Layer):
            scan.append(owner)
        for a in args:
            if isinstance(a, Layer):
                scan.append(a)
        for l in scan:
            bits.append(tuple(s.training for s in l.sublayers(include_self=True)))
        return tuple(bits)

    def __call__(self, *args, **kwargs):
        if not self._enabled or not ProgramTranslator.enable_to_static:
            return self._fn(*args, **kwargs)
        leaves: List[Tensor] = []
        args_tree = _flatten_io(list(args), leaves)
        n_args_leaves = len(leaves)
        kwargs_tree = _flatten_io(kwargs, leaves)
        key = (spec_of(args_tree, leaves), spec_of(kwargs_tree, leaves),
               self._extra_key(args))
        prog = self._programs.get(key)
        if prog is None:
            prog = CompiledProgram(self._fn, args_tree, kwargs_tree,
                                   donate=self._donate)
            # a miss is two spans (``obs.spans``; nothing is recorded on
            # a hit): ``jit.trace`` = Python tracing to a fixed point
            # (prog.build), ``jit.compile`` = the FIRST call (jax.jit
            # compiles lazily, so the first execution pays the lowering
            # and the XLA compile or persistent-cache load).  Their
            # stamps are the ledger's wall time too.  Notify in finally:
            # a first call that raises still CACHED the program, and the
            # retry will be a silent hit — skipping the record would
            # undercount that key's compile forever
            attrs = _miss_attrs(self, key, leaves)
            with _spans.span("jit.trace", **attrs) as traced:
                _build_mapped(prog, leaves)
            self._programs[key] = prog
            compiled = _spans.span("jit.compile", **attrs)
            try:
                with compiled:
                    out = prog(leaves)
                    # the handle of the executable the next call runs, from
                    # jax's own cache: what ``scope_map()`` reads on request,
                    # also once the program itself is gone
                    # (``jit.trace.ProgramText``).  Where the first call
                    # changed the state's placement (a step under a mesh:
                    # single-device arrays in, mesh-placed arrays out) this
                    # compiles the signature the second call would compile
                    try:
                        prog.text()
                    except Exception:    # noqa: BLE001 — asked again, and
                        pass             # raised to the asker, on request
            finally:
                if _compile_listeners:
                    _notify_compile(self, key, attrs,
                                    compiled.t1 - traced.t0,
                                    executed=True)
            return out
        return prog(leaves)

    def concrete_program_specify_input_spec(self, input_spec=None):
        spec = input_spec or self._input_spec
        if spec is None:
            raise ValueError("input_spec required")
        tensors = [s._to_zero_tensor() if isinstance(s, InputSpec) else s
                   for s in spec]
        return self.get_concrete_program(*tensors)

    def get_concrete_program(self, *args, **kwargs):
        leaves: List[Tensor] = []
        args_tree = _flatten_io(list(args), leaves)
        kwargs_tree = _flatten_io(kwargs, leaves)
        key = (spec_of(args_tree, leaves), spec_of(kwargs_tree, leaves),
               self._extra_key(args))
        prog = self._programs.get(key)
        if prog is None:
            prog = CompiledProgram(self._fn, args_tree, kwargs_tree,
                                   donate=self._donate)
            attrs = _miss_attrs(self, key, leaves)
            with _spans.span("jit.trace", **attrs) as traced:
                _build_mapped(prog, leaves)
            self._programs[key] = prog
            if _compile_listeners:
                _notify_compile(self, key, attrs, traced.t1 - traced.t0,
                                executed=False)
        return prog

    def rollback(self):
        self._enabled = False
        return self._fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, donate=True, **kwargs):
    """Decorator: compile a dygraph function to one XLA program
    (reference: @paddle.jit.to_static, fluid/dygraph/jit.py:163).

    donate=False disables buffer donation of rewritten state (params,
    optimizer moments): use it when eager code holds aliases of state
    arrays across compiled calls (e.g. an eager GradScaler.step snapshot
    around a compiled optimizer step) — donation would invalidate them.
    Costs a second in-flight copy of every donated buffer."""

    def _decorate(fn):
        from ..nn.layer_base import Layer

        if isinstance(fn, Layer):
            layer = fn
            static_fwd = StaticFunction(layer.forward, input_spec,
                                        donate=donate)
            layer.forward = static_fwd
            return layer
        return StaticFunction(fn, input_spec, donate=donate)

    if function is not None:
        return _decorate(function)
    return _decorate


declarative = to_static


def not_to_static(fn):
    fn._not_to_static = True
    return fn


# ---------------------------------------------------------------------------
# save / load (reference: jit.save fluid/dygraph/jit.py:637, TranslatedLayer
# fluid/dygraph/io.py:1137).  Deployment format: jax.export serialized
# StableHLO bytes + a params .pdparams — portable across processes and
# loadable without the original python model code.
# ---------------------------------------------------------------------------

def save(layer, path, input_spec=None, **configs):
    from ..nn.layer_base import Layer
    from ..framework.io import save as _fsave

    if isinstance(layer, Layer):
        fwd = layer.forward
        net = layer
    else:
        fwd = layer
        net = getattr(layer, "__self__", None)

    if input_spec is None and isinstance(fwd, StaticFunction):
        input_spec = fwd._input_spec
    if input_spec is None:
        raise ValueError("jit.save requires input_spec")

    in_tensors = [s._to_zero_tensor() if isinstance(s, InputSpec) else s
                  for s in input_spec]
    params = dict(net.named_parameters()) if net is not None else {}
    buffers = dict(net.named_buffers()) if net is not None else {}
    state = {**params, **buffers}
    names = sorted(state.keys())

    was_training = net.training if net is not None else False
    if net is not None:
        net.eval()

    raw_fn = fwd._fn if isinstance(fwd, StaticFunction) else fwd
    # AST-convert python control flow exactly like @to_static does —
    # exporting the raw forward would TracerBool on the first
    # tensor-dependent `if` that conversion handles.  Honors the same
    # kill-switch as StaticFunction.
    import os as _os

    if not _os.environ.get("PADDLE_TPU_NO_AST_CONVERT"):
        from .dy2static import convert_function

        raw_fn = convert_function(raw_fn)

    def pure(state_arrays, in_arrays):
        originals = [state[n]._data for n in names]
        for n, arr in zip(names, state_arrays):
            state[n]._data = arr
        try:
            outs = raw_fn(*[Tensor._wrap(a) for a in in_arrays])
            if isinstance(outs, (list, tuple)):
                return [o._value() for o in outs]
            return outs._value()
        finally:
            for n, orig in zip(names, originals):
                state[n]._data = orig

    state_arrays = [state[n]._value() for n in names]
    in_arrays = [t._value() for t in in_tensors]
    # None/-1 InputSpec dims export as SYMBOLIC dimensions (shared scope):
    # the served model accepts any size there (reference
    # save_inference_model's -1 dims; jax shape polymorphism)
    scope = jax_export.SymbolicScope()
    sym_iter = iter(f"_d{i}" for i in range(64))
    in_avals = []
    for spec_i, arr in zip(list(input_spec) + [None] * len(in_arrays),
                           in_arrays):
        declared = list(getattr(spec_i, "shape", arr.shape))
        if any(d is None or (isinstance(d, int) and d < 0)
               for d in declared):
            dims = ",".join(
                next(sym_iter) if (d is None or int(d) < 0) else str(int(d))
                for d in declared)
            shp = jax_export.symbolic_shape(dims, scope=scope)
            in_avals.append(jax.ShapeDtypeStruct(shp, arr.dtype))
        else:
            in_avals.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
    exported = jax_export.export(jax.jit(pure))(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     state_arrays),
        in_avals,
    )
    blob = exported.serialize()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    _fsave({n: state[n] for n in names}, path + ".pdiparams")
    # signature sidecar: real input names (InputSpec.name, else xN) so the
    # serving surface (inference.Predictor) can expose named handles
    # instead of synthesizing them; old artifacts without it still load
    import json as _json

    in_names = [(getattr(s, "name", None) or f"x{i}")
                for i, s in enumerate(input_spec)]
    meta = {
        "format": 1,
        "input_names": in_names,
        "inputs": [
            {"name": name,
             "shape": [None if (d_ is None or (isinstance(d_, int) and d_ < 0))
                       else int(d_)
                       for d_ in getattr(s, "shape", list(arr.shape))],
             "dtype": str(np.dtype(arr.dtype))}
            for name, s, arr in zip(in_names, input_spec, in_arrays)],
    }
    with open(path + ".pdmeta.json", "w") as f:
        _json.dump(meta, f, indent=1)
    if net is not None and was_training:
        net.train()


class TranslatedLayer:
    """Inference-callable loaded from a jit.save artifact (reference:
    fluid/dygraph/io.py:1137)."""

    def __init__(self, exported, state):
        self._exported = exported
        self._names = sorted(state.keys())
        self._state = state

    def __call__(self, *inputs):
        in_arrays = [t._value() if isinstance(t, Tensor) else jnp.asarray(t)
                     for t in inputs]
        state_arrays = [self._state[n]._value() for n in self._names]
        out = self._exported.call(state_arrays, in_arrays)
        if isinstance(out, (list, tuple)):
            outs = [Tensor._wrap(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        return Tensor._wrap(out)

    forward = __call__

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only")

    def state_dict(self):
        return dict(self._state)


def load(path, **configs):
    from ..framework.io import load as _fload

    with open(path + ".pdmodel", "rb") as f:
        blob = f.read()
    exported = jax_export.deserialize(blob)
    state = _fload(path + ".pdiparams")
    return TranslatedLayer(exported, state)


# -- reference-parity shims -------------------------------------------------

class ProgramTranslator:
    """Reference dygraph_to_static ProgramTranslator (singleton toggling
    to_static globally). Here to_static is trace-based; the toggle makes
    decorated functions run eagerly when disabled."""

    _instance = None
    enable_to_static = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static: bool):
        type(self).enable_to_static = bool(enable_to_static)


def enable_to_static(enable: bool = True):
    """paddle.jit.enable_to_static parity."""
    ProgramTranslator.get_instance().enable(enable)


def set_code_level(level=100, also_to_stdout=False):
    """Reference dy2static debug knob: prints transformed code. The
    trace-based to_static has no AST transforms; accepted as a no-op."""


def set_verbosity(level=0, also_to_stdout=False):
    """Reference dy2static logging verbosity; accepted as a no-op (use
    standard logging on paddle_tpu.jit instead)."""


class TracedLayer:
    """Reference fluid dygraph TracedLayer (trace + save for inference).
    The modern path is jit.to_static + jit.save; `trace` compiles a
    wrapper around the layer (the layer itself is left untouched — its
    direct calls stay eager, like the reference) and returns
    (original_outputs, traced)."""

    def __init__(self, layer, inputs):
        self._layer = layer
        # compile a wrapper fn, NOT the layer: to_static(layer) would
        # replace the layer's own call path in place
        self._static = to_static(lambda *a, **k: layer(*a, **k))
        self._inputs = list(inputs)

    @staticmethod
    def trace(layer, inputs):
        outs = layer(*inputs)          # eager originals, pre-compile
        traced = TracedLayer(layer, inputs)
        return outs, traced

    def __call__(self, *args, **kwargs):
        return self._static(*args, **kwargs)

    def save_inference_model(self, path, feed=None, fetch=None, **kwargs):
        save(self._static, path, input_spec=self._inputs)
