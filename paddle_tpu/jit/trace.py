"""Trace-based to_static: one imperative step → one XLA program.

Reference parity: dy2static (``StaticFunction``, program_translator.py:239;
``run_program`` op, run_program_op.cc:221) and the whole static-graph executor
stack (SURVEY.md §2.3) — which, TPU-native, collapse into ``jax.jit``
(SURVEY.md §7).  What remains ours is the *state lifting* machinery:

- The function under trace reads/writes framework Tensors that live outside
  it (parameters, optimizer accumulators, RNG state, BN running stats,
  ``.grad`` buffers).  A ``TraceHook`` installed on the Tensor payload
  accessors lifts every such external tensor into a program input, and turns
  every in-place write into a program output written back after the compiled
  call — the reference does the same by scoping ProgramDesc variables
  (run_program's scope handling).
- Discovery runs under ``jax.eval_shape`` (abstract, no FLOPs) iterated to a
  fixed point, then the program is compiled once per input-spec.
- Grad accumulation reads lift a zeros-initialized input, so cross-call grad
  accumulation and fresh-grad flows share one program structure.
- A traced function that performs an *internal* backward (train-step style)
  compiles to a single fwd+bwd+update program.  A pure-forward trace stays
  differentiable from outside: the compiled callable is dispatched through
  the autograd tape like any other op (reference: run_program grad node).
"""
from __future__ import annotations

import collections
import re
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core import tensor as tensor_mod
from ..core.tensor import Tensor
from ..core.autograd import is_grad_enabled
from ..core.dispatch import apply_op
from ..core.flags import get_flag


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


class _StateKey:
    """Identity of a lifted (tensor, kind) slot; kind: 'data' | 'grad'."""

    __slots__ = ("tensor", "kind", "_zero_cache")

    def __init__(self, tensor, kind):
        self.tensor = tensor
        self.kind = kind
        self._zero_cache = None

    def current(self):
        """Concrete array to feed this slot right now (zeros for absent grad).

        The zeros buffer is cached: after ``clear_grad`` every parameter's
        grad slot is absent, and materializing ~2 eager arrays per parameter
        per step (zeros + dtype cast) was ~30% of the 345M step's wall time
        on host.  Grad-kind inputs are never donated, so reuse is safe."""
        if self.kind == "data":
            return self.tensor._data
        g = self.tensor._grad
        if g is None:
            z = self._zero_cache
            d = self.tensor._data
            if z is None or z.shape != d.shape or z.dtype != d.dtype \
                    or getattr(z, "is_deleted", lambda: False)():
                z = jnp.zeros(d.shape, d.dtype)
                self._zero_cache = z
            return z
        return g

    def apply(self, arr):
        if self.kind == "data":
            self.tensor._data = arr
            self.tensor._version += 1
        else:
            self.tensor._grad = arr

    def __hash__(self):
        return hash((id(self.tensor), self.kind))

    def __eq__(self, other):
        return self.tensor is other.tensor and self.kind == other.kind

    def __repr__(self):
        return f"<{self.kind}:{self.tensor.name or id(self.tensor)}>"


class TraceHook:
    """Installed as tensor_mod._trace_hook while a capture is active."""

    def __init__(self, known: Dict[_StateKey, Any]):
        self.env: Dict[_StateKey, Any] = dict(known)
        self.new_found: List[_StateKey] = []
        self.writes: Dict[_StateKey, Any] = {}
        self.grad_none: set = set()  # grads structurally absent this trace
        self.performed_backward = False  # any non-None grad write seen

    # Trace-local bookkeeping lives ON the tensor (owner-tagged slots), not
    # in id()-keyed sets: a GC'd trace-local tensor's id can be reused by a
    # brand-new external tensor, which an id set would misclassify as local.
    def mark_created(self, t):
        t._trace_born = self

    def unmark_created(self, t):
        t._trace_born = None

    def _is_local(self, t) -> bool:
        return t._trace_born is self or _is_tracer(t._data)

    def _local_grad(self, t):
        lg = t._trace_grad
        if lg is not None and lg[0] is self:
            return lg[1]
        return t._grad

    def _set_local_grad(self, t, arr):
        t._trace_grad = (self, arr)

    def read(self, t: Tensor):
        if self._is_local(t):
            return t._data
        key = _StateKey(t, "data")
        if key in self.writes:
            return self.writes[key]
        if key in self.env:
            return self.env[key]
        # unknown external: record for the next discovery round; use the
        # concrete value (a constant now — becomes an input on retrace)
        self.new_found.append(key)
        self.env[key] = t._data
        return t._data

    def write(self, t: Tensor, arr):
        if self._is_local(t):
            t._data = arr  # trace-local mutation
            return
        key = _StateKey(t, "data")
        if key not in self.env and key not in self.writes:
            self.new_found.append(key)  # written external never read
        self.writes[key] = arr

    def _grad_key_lookup(self, key):
        if key in self.writes:
            return self.writes[key], True
        if key in self.env:
            return self.env[key], True
        return None, False

    def read_grad(self, t: Tensor):
        """Structural read (Tensor.grad property): absent grad stays None."""
        if self._is_local(t):
            return self._local_grad(t)
        key = _StateKey(t, "grad")
        v, hit = self._grad_key_lookup(key)
        if hit:
            return v
        if key in self.grad_none:
            return None
        g = t._grad
        if g is None:
            self.grad_none.add(key)
            return None
        self.new_found.append(key)
        self.env[key] = g
        return g

    def read_grad_accum(self, t: Tensor):
        """Accumulation read: lift a zeros-backed input so fresh-grad and
        accumulate-grad calls share one program structure."""
        if self._is_local(t):
            return self._local_grad(t)
        key = _StateKey(t, "grad")
        v, hit = self._grad_key_lookup(key)
        if hit:
            return v
        self.new_found.append(key)
        g = t._grad
        init = g if g is not None else jnp.zeros(
            t._data.shape, t._data.dtype)
        self.env[key] = init
        return init

    def write_grad(self, t: Tensor, arr):
        if arr is not None:
            self.performed_backward = True
        if self._is_local(t):
            self._set_local_grad(t, arr)
            return
        key = _StateKey(t, "grad")
        if arr is None:
            self.grad_none.discard(key)
            self.writes[key] = None
            return
        self.grad_none.discard(key)
        if key not in self.env and key not in self.writes:
            self.new_found.append(key)
        self.writes[key] = arr


# -- pytree helpers over framework Tensors ----------------------------------

def _flatten_io(obj, leaves: List):
    if isinstance(obj, Tensor):
        leaves.append(obj)
        return ("T", len(leaves) - 1)
    if isinstance(obj, (list, tuple)):
        return ("tuple" if isinstance(obj, tuple) else "list",
                [_flatten_io(o, leaves) for o in obj])
    if isinstance(obj, dict):
        return ("dict", {k: _flatten_io(v, leaves) for k, v in obj.items()})
    return ("C", obj)


def _unflatten_io(tree, leaves: List):
    tag = tree[0]
    if tag == "T":
        return leaves[tree[1]]
    if tag == "C":
        return tree[1]
    if tag == "dict":
        return {k: _unflatten_io(v, leaves) for k, v in tree[1].items()}
    seq = [_unflatten_io(t, leaves) for t in tree[1]]
    return tuple(seq) if tag == "tuple" else seq


def _count_tensor_leaves(tree) -> int:
    tag = tree[0]
    if tag == "T":
        return 1
    if tag == "C":
        return 0
    if tag == "dict":
        return sum(_count_tensor_leaves(v) for v in tree[1].values())
    return sum(_count_tensor_leaves(t) for t in tree[1])


def spec_of(tree, leaves) -> tuple:
    """Hashable cache key for an arg pytree (reference: function_spec.py)."""

    def _spec(tree):
        tag = tree[0]
        if tag == "T":
            t = leaves[tree[1]]
            return ("T", tuple(t.shape), str(t.dtype), t.stop_gradient)
        if tag == "C":
            v = tree[1]
            try:
                hash(v)
                return ("C", v)
            except TypeError:
                return ("C", repr(v))
        if tag == "dict":
            return ("dict",
                    tuple(sorted((k, _spec(v)) for k, v in tree[1].items())))
        return (tag, tuple(_spec(t) for t in tree[1]))

    return _spec(tree)


def program_name(fn) -> str:
    """The name a compiled program runs under: the traced function's own
    ``__name__`` as an identifier (``<lambda>`` reads ``_lambda_``)."""
    name = getattr(fn, "__name__", None) or type(fn).__name__
    return re.sub(r"\W", "_", str(name)) or "program"


class ProgramText:
    """What a program's scope map is read from, and what outlives the
    program for its sake: the name of its HLO module and the jax
    ``Compiled`` of the signature it ran.  The optimized text is asked of
    the executable, parsed and kept only when :meth:`scope_map` is first
    called; that is one ``jit.scope_map`` span in the ring."""

    __slots__ = ("module", "compiled", "_map")

    def __init__(self, module: str, compiled):
        self.module, self.compiled, self._map = module, compiled, None

    def scope_map(self) -> dict:
        if self._map is None:
            from ..obs import hlo_cost, spans

            with spans.span("jit.scope_map", module=self.module) as sp:
                self._map = hlo_cost.scope_map(self.compiled.as_text())
                rows = self._map["instructions"].values()
                sp.set(instructions=len(rows), scoped=sum(
                    1 for scope, _d in rows if scope != hlo_cost.UNSCOPED))
        return self._map


#: every program ``build`` has built and that is still alive — the registry
#: keeps none alive — and the texts of the last few that ran and are gone
#: (a driver may drop its step before anybody reads the profile it ran under)
_built: "weakref.WeakSet[CompiledProgram]" = weakref.WeakSet()
_gone: "collections.deque[ProgramText]" = collections.deque(maxlen=32)


def program_texts(modules) -> List[ProgramText]:
    """The :class:`ProgramText` of every registered program whose HLO
    module is named in ``modules``: live programs first (one that never ran
    is lowered and compiled for it), then those that are gone."""
    live = [p.text() for p in list(_built) if p.module in modules]
    return live + [t for t in list(_gone) if t.module in modules]


class CompiledProgram:
    """One (input-spec → XLA executable) entry (reference: ConcreteProgram +
    cached InterpreterCore, executor_cache.cc)."""

    def __init__(self, fn, args_tree, kwargs_tree, donate=True):
        self.fn = fn
        #: the name of the program's HLO module (``jit_decode_step``): what
        #: a profiler trace's ``XLA Modules`` line calls its executions
        self.module = "jit_" + program_name(fn)
        self._text: Optional[ProgramText] = None
        self.args_tree = args_tree
        self.kwargs_tree = kwargs_tree
        self.donate = donate
        self.state_keys: List[_StateKey] = []
        self.write_keys: List[_StateKey] = []
        self.write_none_mask: List[bool] = []
        self.out_tree = None
        self.jitted = None
        self.has_internal_backward = False
        self._arg_sg: List[bool] = []

    def _run_traced(self, arg_arrays, state_arrays):
        """Trace body: returns (hook, out_tree, out_arrays)."""
        known = {k: a for k, a in zip(self.state_keys, state_arrays)}
        hook = TraceHook(known)
        arg_tensors = [
            Tensor._wrap(a, stop_gradient=sg)
            for a, sg in zip(arg_arrays, self._arg_sg)
        ]
        args = _unflatten_io(self.args_tree, arg_tensors)
        kwargs = _unflatten_io(self.kwargs_tree, arg_tensors)
        prev = tensor_mod._trace_hook
        tensor_mod._trace_hook = hook
        try:
            out = self.fn(*args, **kwargs)
            out_leaves: List[Tensor] = []
            out_tree = _flatten_io(out, out_leaves)
            out_arrays = [t._value() for t in out_leaves]
        finally:
            tensor_mod._trace_hook = prev
        return hook, out_tree, out_arrays

    def build(self, arg_tensors):
        self._arg_sg = [t.stop_gradient for t in arg_tensors]
        arg_arrays = [t._value() for t in arg_tensors]
        for _ in range(8):
            state_arrays = [k.current() for k in self.state_keys]
            box = {}

            def _probe(aa, sa):
                hook, out_tree, out_arrays = self._run_traced(aa, sa)
                box["hook"], box["out_tree"] = hook, out_tree
                return out_arrays

            jax.eval_shape(_probe, arg_arrays, state_arrays)
            hook = box["hook"]
            if not hook.new_found:
                self.out_tree = box["out_tree"]
                self.write_keys = list(hook.writes.keys())
                self.write_none_mask = [
                    hook.writes[k] is None for k in self.write_keys]
                self.has_internal_backward = hook.performed_backward
                break
            for k in hook.new_found:
                if k not in self.state_keys:
                    self.state_keys.append(k)
        else:
            raise RuntimeError("to_static: state discovery did not converge")

        # Buffer donation: data-kind state leaves that are rewritten every
        # call (params, optimizer moments, RNG state) alias their outputs,
        # so the executable updates them in place — without this, a train
        # step holds two copies of every parameter and moment (the
        # reference gets the same effect from inplace ops + buffer-share
        # passes).  Grad-kind leaves are NOT donated: `p.grad` hands out
        # aliases of the raw buffer and a later donated call would
        # invalidate them.  Caveat (shared with torch inplace optimizers):
        # a _value()/state_dict alias of a *parameter* captured before a
        # compiled train step is invalidated by that step's donation.
        replaced = {
            k for k, none in zip(self.write_keys, self.write_none_mask)
            if not none and k.kind == "data"}
        self._don_idx = [i for i, k in enumerate(self.state_keys)
                         if k in replaced]
        self._keep_idx = [i for i, k in enumerate(self.state_keys)
                          if k not in replaced]

        def program(aa, sd, sk):
            sa = [None] * len(self.state_keys)
            for j, i in enumerate(self._don_idx):
                sa[i] = sd[j]
            for j, i in enumerate(self._keep_idx):
                sa[i] = sk[j]
            hook, _, out_arrays = self._run_traced(aa, sa)
            write_arrays = []
            for k, none_at_build in zip(self.write_keys, self.write_none_mask):
                w = hook.writes.get(k)
                if w is None:
                    # None write (grad cleared) or unchanged: dummy scalar
                    write_arrays.append(jnp.zeros((), jnp.float32))
                else:
                    write_arrays.append(w)
            return tuple(out_arrays), tuple(write_arrays)

        # the program carries the traced function's name, so that a
        # profiler trace's ``XLA Modules`` line reads ``jit_decode_step``,
        # ``jit_prefill_step``, ``jit_<the user's train function>`` and
        # every operation's op_name starts ``jit(<that name>)/``
        program.__name__ = program.__qualname__ = program_name(self.fn)
        # donating variant for the state-mutating fast path; non-donating
        # for the differentiable path (vjp residuals may alias state bufs)
        self.jitted = jax.jit(program)
        self.jitted_donate = jax.jit(program, donate_argnums=(1,))
        self._ran = self.jitted_donate if self.donate else self.jitted
        _built.add(self)
        return self

    def _split_state(self, state_arrays):
        sd = [state_arrays[i] for i in self._don_idx]
        sk = [state_arrays[i] for i in self._keep_idx]
        return sd, sk

    def compiled_stats(self):
        """Compile-time introspection of the current program signature:
        XLA memory analysis + optimized HLO text (shares jax's executable
        cache with normal calls — cheap after the first run).  Powers the
        multichip gate's per-config stats (collective bytes, peak HBM)."""
        compiled = self._compiled(
            self.jitted_donate if self.donate else self.jitted)
        out = {"hlo": compiled.as_text()}
        try:
            ma = compiled.memory_analysis()
            out["argument_bytes"] = int(ma.argument_size_in_bytes)
            out["output_bytes"] = int(ma.output_size_in_bytes)
            out["temp_bytes"] = int(ma.temp_size_in_bytes)
            out["alias_bytes"] = int(ma.alias_size_in_bytes)
            out["peak_bytes"] = int(ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes)
        except Exception:
            pass
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            out["cost"] = {
                k.replace(" ", "_"): float(ca[k])
                for k in ("flops", "bytes accessed", "transcendentals")
                if k in ca}
        except Exception:
            pass
        return out

    def _compiled(self, run):
        """The jax ``Compiled`` of ``run`` at the last call's arguments and
        the state as it is now: the signature the next call runs.  Where
        that is the signature already run, jax hands back the lowering and
        the executable it cached (milliseconds); where the first call
        changed the state's placement, this is the next call's compile."""
        sd, sk = self._split_state([k.current() for k in self.state_keys])
        return run.lower(self._last_arg_arrays, sd, sk).compile()

    def text(self) -> ProgramText:
        """The program's :class:`ProgramText`, taken once: at its first
        call (``StaticFunction``, a program-cache miss) or when first asked.
        From then on the text outlives the program: when the program is
        collected it moves to the registry's short list of those gone."""
        if self._text is None:
            self._text = ProgramText(self.module, self._compiled(self._ran))
            weakref.finalize(self, _gone.append, self._text).atexit = False
        return self._text

    def scope_map(self) -> dict:
        """``{"module", "instructions": {name: (scope, direction)}}`` of the
        program's own optimized HLO (``obs.hlo_cost.scope_map``): built on
        request, kept with the program's text."""
        return self.text().scope_map()

    def _writeback(self, write_arrays):
        for k, none_at_build, arr in zip(
                self.write_keys, self.write_none_mask, write_arrays):
            if none_at_build:
                k.apply(None) if k.kind == "grad" else None
            else:
                k.apply(arr)

    def __call__(self, arg_tensors):
        arg_arrays = [t._value() for t in arg_tensors]
        self._last_arg_arrays = arg_arrays
        state_arrays = [k.current() for k in self.state_keys]

        outer_diff = (
            not self.has_internal_backward
            and is_grad_enabled()
            and (any(not t.stop_gradient for t in arg_tensors)
                 or any(k.kind == "data" and not k.tensor.stop_gradient
                        for k in self.state_keys))
        )
        if not outer_diff:
            sd, sk = self._split_state(state_arrays)
            run = self._ran = \
                self.jitted_donate if self.donate else self.jitted
            out_arrays, write_arrays = run(arg_arrays, sd, sk)
            if get_flag("check_nan_inf"):
                from ..core import error_guard

                error_guard.raise_on_error()
            self._writeback(write_arrays)
            out_leaves = [Tensor._wrap(a) for a in out_arrays]
            return _unflatten_io(self.out_tree, out_leaves)

        # pure-forward program: dispatch through the tape so outer backward
        # flows into args and lifted parameters (reference: run_program grad)
        self._ran = self.jitted
        n_out = _count_tensor_leaves(self.out_tree)
        n_args = len(arg_tensors)
        state_wrappers = []
        for k, a in zip(self.state_keys, state_arrays):
            if k.kind == "data":
                state_wrappers.append(k.tensor)
            else:
                state_wrappers.append(Tensor._wrap(a, stop_gradient=True))

        def primal(*arrays):
            aa = list(arrays[:n_args])
            sa = list(arrays[n_args:])
            sd, sk = self._split_state(sa)
            out_arrays, write_arrays = self.jitted(aa, sd, sk)
            flat = tuple(out_arrays) + tuple(write_arrays)
            return flat[0] if len(flat) == 1 else flat

        res = apply_op("run_program", primal,
                       list(arg_tensors) + state_wrappers,
                       n_outs=n_out + len(self.write_keys))
        if get_flag("check_nan_inf"):
            from ..core import error_guard

            error_guard.raise_on_error()
        if not isinstance(res, tuple):
            res = (res,)
        out_leaves = list(res[:n_out])
        writes = [w._value() for w in res[n_out:]]
        self._writeback(writes)
        return _unflatten_io(self.out_tree, out_leaves)
