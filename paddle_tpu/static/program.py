"""paddle.static Program/Executor compatibility layer (reference:
`python/paddle/fluid/framework.py` Program/Variable,
`python/paddle/fluid/executor.py:625` Executor).

TPU-native design: there is no ProgramDesc IR — while static mode is on,
every dispatched op is RECORDED (name, pure-jax primal, input refs,
attrs, outputs) into the current Program via the dispatch chokepoint
(`core/dispatch.py _static_record_hook`).  On first replay the recorded
op list is finalized into SSA form: intermediates become slot indices
(their Tensor objects are released), leaves (placeholders, parameters,
captured constants) are read LIVE at run time — so parameter updates
between Executor.run calls take effect, exactly like the reference
executor reading scope variables.  `Executor.run` replays the SSA DAG
under `jax.jit` with feeds substituted: the InterpreterCore's job done
by the compiler (SURVEY.md §7).

Shape-derived attributes are GUARDED: dims read from feed-derived
tensors during recording come back as SymbolicDim ints; any op that bakes
one into its attrs/primal closure (reshape/flatten computing a target from
a `None` batch dim recorded as 1) is flagged, and Executor.run raises if a
feed contradicts the baked size instead of replaying silently-wrong
numbers (reference programs re-infer shapes at run time).
"""
from __future__ import annotations

import contextlib
import weakref
from typing import Dict, List, Optional

import numpy as np
import jax
from jax import export as jax_export
import jax.numpy as jnp

from ..core import dispatch as dispatch_mod
from ..core import dtype as dtype_mod
from ..core import tensor as tensor_mod
from ..core.tensor import SymbolicDim, Tensor


def _symbolic_feeds(obj, _depth=0):
    """Union of feed names of every SymbolicDim reachable in obj (attrs,
    lists, dicts, or a primal's closure cells — reshape-style ops bake
    computed targets there)."""
    if _depth > 6:
        return frozenset()
    if isinstance(obj, SymbolicDim):
        return obj.feeds or frozenset(["<unknown>"])
    out = frozenset()
    if isinstance(obj, (list, tuple, set)):
        for v in obj:
            out |= _symbolic_feeds(v, _depth + 1)
    elif isinstance(obj, dict):
        for v in obj.values():
            out |= _symbolic_feeds(v, _depth + 1)
    elif callable(obj) and getattr(obj, "__closure__", None):
        for c in obj.__closure__:
            out |= _symbolic_feeds(c.cell_contents, _depth + 1)
    return out


class _RawOp:
    __slots__ = ("name", "primal", "inputs", "kwargs", "outputs")

    def __init__(self, name, primal, inputs, kwargs, outputs):
        self.name = name
        self.primal = primal
        self.inputs = inputs      # list of Tensor | const
        self.kwargs = kwargs
        self.outputs = outputs    # list of Tensor (strong refs until
        #                           finalize; keeps ids stable)


class _SSAOp:
    __slots__ = ("name", "primal", "in_refs", "kwargs", "out_slots")

    def __init__(self, name, primal, in_refs, kwargs, out_slots):
        self.name = name
        self.primal = primal
        # in_refs: ('slot', i) | ('leaf', i) | ('const', value)
        self.in_refs = in_refs
        self.kwargs = kwargs
        self.out_slots = out_slots


class Program:
    """Recorded op list + feed/fetch registry (reference
    `framework.py Program`)."""

    def __init__(self):
        self._raw: List[_RawOp] = []
        self._ssa: Optional[List[_SSAOp]] = None
        self._leaves: List[Tensor] = []           # live-read at replay
        self._feed_vars: Dict[str, Tensor] = {}
        # fetch resolution: id -> (weakref, kind, index); validated by
        # identity at fetch time so a reused id can never mis-resolve
        self._locator: Dict[int, tuple] = {}
        self._name_locator: Dict[str, tuple] = {}
        self._declared_shapes: Dict[str, list] = {}
        self._cache = {}
        self._n_post_run = 0   # ops dispatched (and dropped) after finalize
        # shape-taint bookkeeping: feeds declared with None/-1 dims and the
        # tensors derived from them; ops that baked a SymbolicDim into
        # their attrs/closure are listed with reasons for the run check
        self._sym_feeds: Dict[str, list] = {}    # name -> [axis, ...]
        self._sym_dummy: Dict[int, list] = {}    # dummy size -> [feed, ...]
        # id -> weakref (identity membership; Tensor.__eq__ is elementwise
        # so hash-based sets cannot hold tensors)
        self._descendants: Dict[int, object] = {}
        self._baked_shape_ops: List[str] = []
        # set by Optimizer.minimize while this program records: running
        # the program then TRAINS (reference: the ProgramDesc contains
        # the backward + sgd ops, so exe.run applies updates)
        self._train_spec = None            # (loss Tensor, Optimizer)
        self._train_cache: Dict[tuple, object] = {}

    def _is_descendant(self, t) -> bool:
        r = self._descendants.get(id(t))
        return r is not None and r() is t

    def _add_descendant(self, t):
        self._descendants[id(t)] = weakref.ref(t)

    # -- recording ------------------------------------------------------
    def _record(self, name, primal, tensor_args, kwargs, outs):
        if self._ssa is not None:
            # Ops dispatched after Executor.run finalized this program are
            # between-runs eager computations (LR schedules, metrics built
            # with paddle ops).  They already executed through dispatch and
            # their values are live on the output Tensors — drop the
            # recording (keeping it would pin every intermediate array for
            # the life of the program; the reference re-lowers the whole
            # ProgramDesc on append instead).  Fetching such a tensor from
            # this program still errors by identity validation.
            self._n_post_run += 1
            return
        if self._sym_feeds:
            tainted = any(isinstance(a, Tensor) and self._is_descendant(a)
                          for a in tensor_args)
            if tainted:
                for o in outs:
                    if isinstance(o, Tensor):
                        self._add_descendant(o)
            feeds = _symbolic_feeds((primal, kwargs))
            if feeds:
                self._baked_shape_ops.append((name, feeds))
        self._raw.append(_RawOp(name, primal, list(tensor_args),
                                dict(kwargs), list(outs)))
        self._cache.clear()

    def _register_data(self, name, t: Tensor, declared_shape=None):
        self._feed_vars[name] = t
        if declared_shape is not None:
            self._declared_shapes[name] = list(declared_shape)

    def global_block(self):
        return self

    @property
    def ops(self):
        return self._raw if self._ssa is None else self._ssa

    def list_vars(self):
        return list(self._feed_vars.values())

    # -- finalize to SSA ------------------------------------------------
    def _finalize(self):
        if self._ssa is not None:
            return
        slot_of: Dict[int, int] = {}
        leaf_of: Dict[int, int] = {}
        n_slots = 0
        ssa = []
        for op in self._raw:
            if op.name == "__alias__":
                # in-place rebind: target (outputs[0]) now denotes the
                # source's (inputs[0]) value for all LATER consumers
                src_t = op.inputs[0]
                dst_t = op.outputs[0]
                if id(src_t) in slot_of:
                    slot_of[id(dst_t)] = slot_of[id(src_t)]
                    self._locator[id(dst_t)] = (
                        weakref.ref(dst_t), "slot", slot_of[id(src_t)])
                continue
            in_refs = []
            for a in op.inputs:
                if isinstance(a, Tensor):
                    if id(a) in slot_of:
                        in_refs.append(("slot", slot_of[id(a)]))
                    else:
                        li = leaf_of.get(id(a))
                        if li is None:
                            li = len(self._leaves)
                            leaf_of[id(a)] = li
                            self._leaves.append(a)   # live-read later
                            self._locator[id(a)] = (
                                weakref.ref(a), "leaf", li)
                            if getattr(a, "name", None):
                                self._name_locator[a.name] = ("leaf", li)
                        in_refs.append(("leaf", li))
                else:
                    in_refs.append(("const", a))
            out_slots = []
            for o in op.outputs:
                s = n_slots
                n_slots += 1
                slot_of[id(o)] = s
                out_slots.append(s)
                self._locator[id(o)] = (weakref.ref(o), "slot", s)
                if getattr(o, "name", None):
                    self._name_locator[o.name] = ("slot", s)
            ssa.append(_SSAOp(op.name, op.primal, in_refs, op.kwargs,
                              out_slots))
        # placeholders that never feed an op still need locators
        for fname, t in self._feed_vars.items():
            if id(t) not in self._locator:
                li = len(self._leaves)
                self._leaves.append(t)
                self._locator[id(t)] = (weakref.ref(t), "leaf", li)
                self._name_locator[fname] = ("leaf", li)
        self._n_slots = n_slots
        self._ssa = ssa
        self._raw = []            # release intermediate Tensor refs

    def _locate(self, target):
        """Resolve a fetch/feed target (Tensor or name) to
        ('leaf'|'slot', index) with identity validation."""
        if isinstance(target, str):
            loc = self._name_locator.get(target)
            if loc is None:
                raise KeyError(f"no variable named {target!r} in this "
                               "program")
            return loc
        ent = self._locator.get(id(target))
        if ent is not None:
            ref, kind, idx = ent
            if ref() is target:
                return (kind, idx)
        raise KeyError("fetch target was not produced by this program")

    # -- replay ---------------------------------------------------------
    def _replay(self, feed_arrays: Dict[str, object], fetch_locs):
        self._finalize()
        ssa = self._ssa
        n_slots = self._n_slots
        feed_leaf_idx = {}
        for fname in feed_arrays:
            kind, idx = self._locate(self._feed_vars[fname])
            if kind != "leaf":
                raise KeyError(f"feed target {fname!r} is not a leaf")
            feed_leaf_idx[fname] = idx

        def run(feeds, leaf_arrays):
            leaves = list(leaf_arrays)
            for fname, arr in feeds.items():
                leaves[feed_leaf_idx[fname]] = arr
            env: List[object] = [None] * n_slots
            for op in ssa:
                args = []
                for kind, v in op.in_refs:
                    if kind == "slot":
                        args.append(env[v])
                    elif kind == "leaf":
                        args.append(leaves[v])
                    else:
                        args.append(v)
                out = op.primal(*args, **op.kwargs)
                outs = out if isinstance(out, (tuple, list)) else (out,)
                for s, o in zip(op.out_slots, outs):
                    env[s] = o
            result = []
            for kind, idx in fetch_locs:
                result.append(env[idx] if kind == "slot" else leaves[idx])
            return tuple(result)

        key = (tuple(sorted(feed_arrays)), tuple(fetch_locs))
        jitted = self._cache.get(key)
        if jitted is None:
            jitted = jax.jit(run)
            self._cache[key] = jitted
        # leaves read LIVE: parameter updates between runs take effect
        leaf_arrays = [t._data for t in self._leaves]
        return jitted(feed_arrays, leaf_arrays)

    # -- training replay -------------------------------------------------
    def _train_replay(self, feed_arrays: Dict[str, object], fetch_locs):
        """Run the program AS A TRAIN STEP (set up by Optimizer.minimize):
        the recorded forward graph is re-dispatched through apply_op under
        `to_static`, so the autograd tape, the optimizer update, and the
        parameter/accumulator writes all compile into one XLA program —
        the same machinery the eager train loop uses.  (The pure replay
        path cannot train: backward and optimizer math run on raw arrays
        through vjp closures, invisible to the op recorder — reference
        programs instead carry explicit grad/sgd ops in the ProgramDesc.)"""
        self._finalize()
        loss_t, opt = self._train_spec
        loss_kind, loss_idx = self._locate(loss_t)
        feed_names = tuple(sorted(feed_arrays))
        feed_leaf_idx = {}
        for fname in feed_names:
            kind, idx = self._locate(self._feed_vars[fname])
            if kind != "leaf":
                raise KeyError(f"feed target {fname!r} is not a leaf")
            feed_leaf_idx[fname] = idx

        key = (feed_names, tuple(fetch_locs))
        step = self._train_cache.get(key)
        if step is None:
            from ..core import dispatch
            from ..jit import to_static

            ssa = self._ssa
            leaves = self._leaves

            def step_fn(*feed_ts):
                sub = {feed_leaf_idx[nm]: ft
                       for nm, ft in zip(feed_names, feed_ts)}
                env: List[object] = [None] * self._n_slots

                def resolve(kind, v):
                    if kind == "slot":
                        return env[v]
                    if kind == "leaf":
                        return sub.get(v, leaves[v])
                    return v

                # suspend static recording: we are EXECUTING the program,
                # and enable_static leaves the record hook pointed at the
                # current default program
                with dispatch.no_static_record():
                    for op in ssa:
                        args = [resolve(k, v) for k, v in op.in_refs]
                        outs = dispatch.apply_op(
                            op.name, op.primal, args, dict(op.kwargs),
                            n_outs=len(op.out_slots))
                        outs = outs if isinstance(outs, tuple) else (outs,)
                        for s, o in zip(op.out_slots, outs):
                            env[s] = o
                    loss = resolve(loss_kind, loss_idx)
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                return tuple(resolve(k, i) for k, i in fetch_locs)

            step = to_static(step_fn)
            self._train_cache[key] = step

        feed_ts = [Tensor._wrap(feed_arrays[nm]) for nm in feed_names]
        outs = step(*feed_ts)
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        return tuple(o._value() if isinstance(o, Tensor) else o
                     for o in outs)

    def __repr__(self):
        n = len(self._raw) if self._ssa is None else len(self._ssa)
        return f"Program(num_ops={n})"


_default_main = Program()
_default_startup = Program()
_current_main: Program = _default_main
_current_startup: Program = _default_startup


def default_main_program() -> Program:
    return _current_main


def default_startup_program() -> Program:
    return _current_startup


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    """Scope the recording target (reference framework.py
    program_guard)."""
    global _current_main, _current_startup
    old_m, old_s = _current_main, _current_startup
    _current_main = main_program
    if startup_program is not None:
        _current_startup = startup_program
    _sync_hook()   # records only while static mode is enabled
    try:
        yield
    finally:
        _current_main = old_m
        _current_startup = old_s
        _sync_hook()


def _record_hook(name, primal, tensor_args, kwargs, outs):
    _current_main._record(name, primal, tensor_args, kwargs, outs)


def _taint_shape(t, dims):
    """Shape reads during recording: wrap feed-derived dims in SymbolicDim
    so attrs computed from them are detectable (the documented reshape
    footgun).  Placeholders taint their declared None axes; derived
    tensors taint dims carrying a feed's distinctive dummy size — the
    taint names WHICH feeds it derives from, so the run-time check only
    fires for contradicting feeds."""
    prog = _current_main
    if not prog._sym_feeds:
        return dims
    name = getattr(t, "name", "")
    axes = prog._sym_feeds.get(name)
    if axes is not None and t is prog._feed_vars.get(name):
        return [SymbolicDim(d, {name}) if i in axes else d
                for i, d in enumerate(dims)]
    if prog._is_descendant(t):
        return [SymbolicDim(d, prog._sym_dummy[d])
                if d in prog._sym_dummy else d for d in dims]
    return dims


def _install_hook():
    dispatch_mod._static_record_hook = _record_hook
    tensor_mod._shape_taint_hook = _taint_shape


def _remove_hook():
    dispatch_mod._static_record_hook = None
    tensor_mod._shape_taint_hook = None


def _sync_hook():
    """Hook active only while static mode is on."""
    import paddle_tpu as paddle

    if getattr(paddle, "_static_mode", False):
        _install_hook()
    else:
        _remove_hook()


def data(name, shape, dtype=None, lod_level=0):
    """Declare a feed placeholder (reference static.data): a zero tensor
    registered with the current Program; Executor.run feeds override it.

    `None`/-1 dims are recorded at size 1 and may be fed at any size.
    Ops whose attributes derive from such a dim at build time
    (reshape/flatten with computed targets) bake the build-time dummy —
    detected via SymbolicDim taint; Executor.run raises on a
    contradicting feed rather than replaying wrong numbers.
    """
    dt = dtype_mod.convert_dtype(dtype) if dtype else \
        dtype_mod.get_default_dtype()
    sym_axes = [i for i, s_ in enumerate(shape)
                if s_ is None or int(s_) < 0]
    # None dims record at a DISTINCTIVE dummy size (not 1: size-1 dims are
    # everywhere — keepdim axes, singleton channels — and would false-flag
    # the shape-bake guard).  The FIRST None axis of every feed shares ONE
    # dummy: it is the batch axis in practice, and `pred - y` style ops
    # combining two feeds' batch dims must broadcast at record time (a
    # per-feed batch dummy made x:[None,4] minus y:[None,1] a record-time
    # shape error).  Additional None axes cycle through odd primes so
    # their dim VALUE still identifies the deriving feed.
    concrete = []
    sym_val = {}
    first_none = sym_axes[0] if sym_axes else None
    for i, s_ in enumerate(shape):
        if i in sym_axes:
            v = _SYM_SIZE_POOL[0] if i == first_none \
                else _next_sym_size(_current_main)
            sym_val[i] = v
            concrete.append(v)
        else:
            concrete.append(int(s_))
    t = Tensor._wrap(jnp.zeros(concrete, dt), stop_gradient=True)
    t.name = name
    # declared shape kept on the Program (None dims export symbolically)
    _current_main._register_data(name, t, declared_shape=shape)
    if sym_axes:
        _current_main._sym_feeds[name] = sym_axes
        for v in sym_val.values():
            _current_main._sym_dummy.setdefault(v, []).append(name)
        _current_main._add_descendant(t)
    return t


_SYM_SIZE_POOL = (61, 67, 71, 73, 79, 83, 89, 97, 101, 103, 107, 109, 113)


def _next_sym_size(prog) -> int:
    # pool[0] is reserved as the shared batch dummy (data() above)
    for v in _SYM_SIZE_POOL[1:]:
        if v not in prog._sym_dummy:
            return v
    return _SYM_SIZE_POOL[
        1 + len(prog._sym_dummy) % (len(_SYM_SIZE_POOL) - 1)]


class Scope:
    """Minimal scope (reference framework Scope): name -> Tensor."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, Tensor._wrap(jnp.zeros(())))

    def find_var(self, name):
        return self._vars.get(name)


_global_scope = Scope()


def global_scope():
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope):
    global _global_scope
    old = _global_scope
    _global_scope = scope
    try:
        yield
    finally:
        _global_scope = old


def cpu_places(device_count=None):
    from ..core.device import CPUPlace

    n = device_count or 1
    return [CPUPlace() for _ in range(n)]


def cuda_places(device_ids=None):
    raise RuntimeError("cuda_places: no CUDA devices in the TPU build; "
                       "this build executes on TPU/CPU via XLA")


class Executor:
    """Replay executor (reference `fluid/executor.py:625`): `run`
    substitutes feeds into the recorded program and returns fetched
    arrays. Fetch targets may be Tensors or variable names."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program=None, feed=None, fetch_list=None,
            return_numpy=True, **kwargs):
        prog = program or _current_main
        if isinstance(prog, CompiledProgram):
            prog = prog._program
        if isinstance(prog, _LoadedProgram):
            feed_arrays = {k: jnp.asarray(np.asarray(v))
                           for k, v in (feed or {}).items()}
            outs = prog.run(feed_arrays)
            picked = [outs[i] for i in (fetch_list
                                        or range(len(outs)))]
            if return_numpy:
                return [np.asarray(o) for o in picked]
            return [Tensor._wrap(o) for o in picked]
        feed = feed or {}
        fetch_list = fetch_list or []
        feed_arrays = {}
        for k, v in feed.items():
            if k not in prog._feed_vars:
                raise KeyError(f"feed target {k!r} was not declared with "
                               "static.data in this program")
            want = prog._feed_vars[k]._data
            arr = jnp.asarray(np.asarray(v)).astype(want.dtype)
            if prog._baked_shape_ops:
                baked_here = sorted({n for n, fs in prog._baked_shape_ops
                                     if k in fs or "<unknown>" in fs})
                axes = prog._sym_feeds.get(k, ()) if baked_here else ()
                for ax in axes:
                    if ax < arr.ndim and arr.shape[ax] != want.shape[ax]:
                        raise RuntimeError(
                            f"feed {k!r} has size {arr.shape[ax]} at its "
                            f"None-declared axis {ax}, but ops "
                            f"{baked_here} baked an attribute computed "
                            f"from the build-time dummy size "
                            f"{want.shape[ax]} — the replay would be "
                            "silently wrong.  Declare the real size in "
                            "static.data, or avoid computing shape "
                            "attributes from a None dim (reference "
                            "programs re-infer these at run time)")
            feed_arrays[k] = arr
        prog._finalize()
        fetch_locs = tuple(prog._locate(t) for t in fetch_list)
        if prog._train_spec is not None:
            outs = prog._train_replay(feed_arrays, fetch_locs)
        else:
            outs = prog._replay(feed_arrays, fetch_locs)
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return [Tensor._wrap(o) for o in outs]

    def close(self):
        pass


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    t = Tensor._wrap(jnp.full(tuple(int(s) for s in shape), value,
                              dtype_mod.convert_dtype(dtype)))
    t.persistable = persistable
    if name:
        t.name = name
    return t


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """Reference static.gradients: grads of targets w.r.t. inputs via
    the eager tape (ops recorded under static mode also ran eagerly, so
    the tape exists)."""
    from ..core.autograd import grad as _grad

    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    return _grad(list(targets), list(inputs),
                 grad_outputs=target_gradients, allow_unused=True,
                 retain_graph=True)


append_backward = gradients  # closest analog: produce grads explicitly


def name_scope(prefix=None):
    return contextlib.nullcontext()


@contextlib.contextmanager
def device_guard(device=None):
    yield


class BuildStrategy:
    """Config stub (reference BuildStrategy): knobs are XLA's job."""

    def __init__(self):
        self.memory_optimize = None
        self.enable_inplace = None
        self.fuse_all_optimizer_ops = False
        self.fuse_elewise_add_act_ops = False


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 10


class CompiledProgram:
    """Pass-through (reference compiler.py CompiledProgram): replay is
    already jit-compiled; with_data_parallel is a no-op wrapper."""

    def __init__(self, program, build_strategy=None):
        self._program = program

    def with_data_parallel(self, *a, **k):
        return self

    def __getattr__(self, name):
        return getattr(self._program, name)


ParallelExecutor = CompiledProgram


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase='both'):
    """Debug print op (reference fluid.layers.Print)."""
    arr = input._value() if isinstance(input, Tensor) else input
    jax.debug.print((message or "") + " {}", arr)
    return input


class ExponentialMovingAverage:
    """EMA of trainable parameters (reference
    `fluid/optimizer.py ExponentialMovingAverage`): update() after each
    step; apply()/restore() swap shadow weights in and out."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = float(decay)
        self._shadow = {}
        self._backup = {}
        self._tracked = []
        self._step = 0

    def update(self, parameters=None):
        if parameters is None:
            raise ValueError("pass parameters=model.parameters()")
        self._step += 1
        # bias-limited dynamic decay like the reference
        d = min(self._decay, (1 + self._step) / (10 + self._step))
        tracked = []
        for p in parameters:
            key = p.name or f"param_{id(p)}"
            prev = self._shadow.get(key)
            arr = p._value().astype(jnp.float32)
            self._shadow[key] = arr if prev is None else \
                d * prev + (1 - d) * arr
            tracked.append((p, key))
        self._tracked = tracked

    @contextlib.contextmanager
    def apply(self, executor=None, need_restore=True):
        for p, key in self._tracked:
            self._backup[key] = p._value()
            p._set_data(self._shadow[key].astype(p._value().dtype))
        try:
            yield
        finally:
            if need_restore:
                self.restore()

    def restore(self, executor=None):
        for p, key in self._tracked:
            if key in self._backup:
                p._set_data(self._backup.pop(key))


def accuracy(input, label, k=1, correct=None, total=None):
    from ..metric import accuracy as _acc

    return _acc(input, label, k=k)


def auc(input, label, curve='ROC', num_thresholds=4095, topk=1,
        slide_steps=1):
    from ..metric import Auc

    m = Auc(curve=curve, num_thresholds=min(num_thresholds, 4095))
    preds = np.asarray(input.numpy() if isinstance(input, Tensor)
                       else input)
    if preds.ndim == 1 or preds.shape[-1] == 1:
        preds = np.stack([1 - preds.reshape(-1),
                          preds.reshape(-1)], axis=1)
    m.update(preds, np.asarray(label.numpy()
                               if isinstance(label, Tensor) else label))
    val = m.accumulate()
    return (Tensor._wrap(jnp.asarray(val, jnp.float32)),) * 3


# -- inference model serialization (reference fluid/io.py
# save_inference_model/load_inference_model; format here: serialized
# StableHLO via jax.export + a pickle sidecar with feed/fetch meta) -----

class _LoadedProgram:
    """Deserialized inference program: runnable by Executor.run with
    feed={name: array}, fetch_list=the returned fetch handles."""

    def __init__(self, exported, feed_names, n_fetch):
        self._exported = exported
        self._feed_names = list(feed_names)
        self._n_fetch = n_fetch

    def run(self, feed):
        args = [feed[n] for n in self._feed_names]
        return self._exported.call(*args)


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """Freeze the program for deployment: parameters are baked into the
    exported StableHLO; only `feed_vars` stay as runtime inputs."""
    import pickle

    prog = program or default_main_program()
    prog._finalize()
    feed_names = [getattr(t, "name", None) or str(i)
                  for i, t in enumerate(feed_vars)]
    for t, n in zip(feed_vars, feed_names):
        if n not in prog._feed_vars:
            raise KeyError(f"feed var {n!r} was not declared with "
                           "static.data")
    fetch_locs = tuple(prog._locate(t) for t in fetch_vars)
    feed_locs = [prog._locate(prog._feed_vars[n]) for n in feed_names]
    leaf_arrays = [t._data for t in prog._leaves]
    ssa = prog._ssa
    n_slots = prog._n_slots

    def infer(*feed_arrays):
        leaves = list(leaf_arrays)
        for (kind, idx), arr in zip(feed_locs, feed_arrays):
            leaves[idx] = arr
        env = [None] * n_slots
        for op in ssa:
            args = [env[v] if kind == "slot"
                    else (leaves[v] if kind == "leaf" else v)
                    for kind, v in op.in_refs]
            out = op.primal(*args, **op.kwargs)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for s, o in zip(op.out_slots, outs):
                env[s] = o
        return tuple(env[idx] if kind == "slot" else leaves[idx]
                     for kind, idx in fetch_locs)

    # None/-1 declared dims export as SYMBOLIC dims so the frozen model
    # accepts any size there (jax shape polymorphism)
    shapes = []
    n_sym = 0
    for n in feed_names:
        t = prog._feed_vars[n]
        declared = prog._declared_shapes.get(n, list(t._data.shape))
        parts = []
        symbolic = False
        for s in declared:
            if s is None or int(s) < 0:
                parts.append(f"_sdim{n_sym}")
                n_sym += 1
                symbolic = True
            else:
                parts.append(str(int(s)))
        if symbolic:
            dims = jax_export.symbolic_shape(", ".join(parts))
            shapes.append(jax.ShapeDtypeStruct(tuple(dims),
                                               t._data.dtype))
        else:
            shapes.append(jax.ShapeDtypeStruct(t._data.shape,
                                               t._data.dtype))
    exported = jax_export.export(jax.jit(infer))(*shapes)
    with open(path_prefix + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    with open(path_prefix + ".pdiparams", "wb") as f:
        pickle.dump({"feed_names": feed_names,
                     "n_fetch": len(fetch_vars)}, f)


def load_inference_model(path_prefix, executor=None, **kwargs):
    """Returns (program, feed_target_names, fetch_targets) — run with
    `Executor.run(program, feed={...}, fetch_list=fetch_targets)`."""
    import pickle

    with open(path_prefix + ".pdmodel", "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(path_prefix + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    prog = _LoadedProgram(exported, meta["feed_names"], meta["n_fetch"])
    fetch_targets = list(range(meta["n_fetch"]))
    return prog, meta["feed_names"], fetch_targets


def serialize_program(feed_vars, fetch_vars, program=None):
    """Bytes = pickled {hlo, feed_names, n_fetch}; deserialize_program
    rebuilds a runnable _LoadedProgram."""
    import os
    import pickle
    import tempfile

    prog = program or default_main_program()
    with tempfile.TemporaryDirectory() as d:
        save_inference_model(os.path.join(d, "m"), feed_vars, fetch_vars,
                             program=prog)
        with open(os.path.join(d, "m.pdmodel"), "rb") as f:
            hlo = f.read()
        with open(os.path.join(d, "m.pdiparams"), "rb") as f:
            meta = pickle.load(f)
    return pickle.dumps({"hlo": hlo, **meta})


def deserialize_program(data):
    import pickle

    blob = pickle.loads(data)
    exported = jax_export.deserialize(blob["hlo"])
    return _LoadedProgram(exported, blob["feed_names"], blob["n_fetch"])


# -- program state save/load (reference static/io.py
# save/load_program_state, serialize/deserialize_persistables) ---------

def save_to_file(path, content):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path):
    with open(path, "rb") as f:
        return f.read()


def serialize_persistables(feed_vars, fetch_vars, program=None):
    """Pickle the live leaf (parameter) arrays of the program."""
    import pickle

    prog = program or default_main_program()
    prog._finalize()
    state = {i: np.asarray(t._data) for i, t in enumerate(prog._leaves)}
    return pickle.dumps(state)


def deserialize_persistables(program, data, executor=None):
    import pickle

    state = pickle.loads(data)
    program._finalize()
    for i, arr in state.items():
        if i < len(program._leaves):
            t = program._leaves[i]
            t._set_data(jnp.asarray(arr).astype(t._data.dtype))


def save_program_state(dirname=None, program=None):
    prog = program or default_main_program()
    prog._finalize()
    return {i: np.asarray(t._data) for i, t in enumerate(prog._leaves)}


def load_program_state(state_or_dirname=None, var_list=None):
    """Reference loads a params dir; here program state round-trips as
    in-memory dicts (save_program_state -> set_program_state) or through
    serialize/deserialize_persistables for on-disk bytes. A directory
    path raises instead of silently returning the live state."""
    if isinstance(state_or_dirname, dict) or state_or_dirname is None:
        return state_or_dirname if state_or_dirname is not None \
            else save_program_state()
    raise NotImplementedError(
        "load_program_state from a directory is not supported: persist "
        "state with serialize_persistables/save_to_file and restore via "
        "deserialize_persistables, or pass the dict from "
        "save_program_state")


def set_program_state(program, state):
    program._finalize()
    for i, arr in state.items():
        if isinstance(i, int) and i < len(program._leaves):
            t = program._leaves[i]
            t._set_data(jnp.asarray(arr).astype(t._data.dtype))


def normalize_program(program, feed_vars, fetch_vars):
    """Reference: prune to the feed->fetch subgraph. The SSA replay
    already executes only recorded ops; returned unchanged."""
    return program


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Reference static py_func: host-python op inside a program. Eager
    recording runs the function directly; a custom backward wraps it as
    a PyLayer."""
    from ..autograd import PyLayer

    xs = x if isinstance(x, (list, tuple)) else [x]
    if backward_func is None:
        return func(*xs)

    class _PyFunc(PyLayer):
        @staticmethod
        def forward(ctx, *args):
            ctx.save_for_backward(*args)
            return func(*args)

        @staticmethod
        def backward(ctx, *grads):
            return backward_func(*ctx.saved_tensor(), *grads)

    return _PyFunc.apply(*xs)


# reference static Variable is the graph-mode tensor handle; here the
# Tensor facade plays both roles, so isinstance checks against
# static.Variable hold for everything static.data / ops return
Variable = Tensor


def xpu_places(device_ids=None):
    raise RuntimeError("xpu_places: no XPU devices in the TPU build")


def npu_places(device_ids=None):
    raise RuntimeError("npu_places: no NPU devices in the TPU build")


def mlu_places(device_ids=None):
    raise RuntimeError("mlu_places: no MLU devices in the TPU build")


class IpuStrategy:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU support is not part of the TPU "
                                  "build")


class IpuCompiledProgram:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU support is not part of the TPU "
                                  "build")


def ipu_shard_guard(*a, **k):
    raise NotImplementedError("IPU support is not part of the TPU build")


def set_ipu_shard(*a, **k):
    raise NotImplementedError("IPU support is not part of the TPU build")
