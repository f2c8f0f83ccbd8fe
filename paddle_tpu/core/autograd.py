"""Eager define-by-run autograd over jax.vjp.

Reference parity: the eager autograd engine (``paddle/fluid/eager`` —
``GradNodeBase`` grad_node_info.h:161, ``egr::RunBackward`` backward.cc:532).
TPU-native design: instead of generated per-op C++ grad nodes, every
differentiable op call records ONE tape node holding the ``jax.vjp`` closure of
its pure-jax primal.  ``backward()`` is a reverse-topological sweep that feeds
cotangents through the stored vjp closures and accumulates leaf grads —
semantically the queue-based BFS of the reference's RunBackward, without any
codegen.  Under ``to_static`` tracing the same tape runs on jax tracers, so a
whole imperative train step (forward + backward + optimizer) compiles to one
XLA program.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_state = _GradState()


def is_grad_enabled() -> bool:
    return _state.enabled


@contextlib.contextmanager
def no_grad():
    """Context manager / decorator disabling grad recording (paddle.no_grad)."""
    prev = _state.enabled
    _state.enabled = False
    try:
        yield
    finally:
        _state.enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = _state.enabled
    _state.enabled = True
    try:
        yield
    finally:
        _state.enabled = prev


def set_grad_enabled(mode: bool):
    _state.enabled = bool(mode)


_NO_SCOPE = contextlib.nullcontext()   # an eager node's backward opens none


class TapeNode:
    """One recorded differentiable op (reference: GradNodeBase + captured
    TensorWrappers).  Holds the vjp closure (residuals live inside it), strong
    refs to differentiable input Tensors and to output Tensors (cycle is
    collected by the python GC once user refs drop)."""

    __slots__ = ("vjp_fn", "primal_fn", "input_arrays", "inputs", "outputs",
                 "name", "released", "materialize", "input_edges", "scope",
                 "__weakref__")

    def __init__(self, vjp_fn, inputs, outputs, name="", materialize=True,
                 primal_fn=None, input_arrays=None, scope=None):
        self.vjp_fn = vjp_fn
        # the name-scope path the op was recorded under while ``to_static``
        # traced (``gpt/layers/3/attn``; None in eager mode): its backward
        # runs under ``transpose(<that path>)``, so the compiled program's
        # op names give a layer its backward as they give it its forward
        self.scope = scope
        # pure function of the diff inputs' ARRAYS (non-diff args baked),
        # kept so grad(create_graph=True) can replay the subgraph as one
        # differentiable jax function — the stored vjp closure alone bakes
        # the primals in, which would silently zero d²/dprimal² terms
        self.primal_fn = primal_fn
        # the diff inputs' arrays AT RECORD TIME: replay must agree with
        # the first-order path even if a leaf was in-place mutated after
        # the forward (vjp residuals captured the old values; reading
        # t._value() at grad time would silently use the new ones)
        self.input_arrays = input_arrays
        self.inputs: List[Any] = inputs  # Tensors (diff inputs only)
        self.outputs: List[Any] = outputs  # Tensors produced
        self.name = name
        self.released = False
        # False (PyLayer set_materialize_grads): outputs with no incoming
        # cotangent pass None to the vjp instead of materialized zeros
        self.materialize = materialize
        # in-place safety (reference: DenseTensor inplace_version,
        # dense_tensor.h:177, and torch-style recorded edges): snapshot
        # each input's producing node; backward raises if the tensor's
        # grad routing changed (an in-place op consumed it afterwards),
        # which would silently send cotangents through the wrong vjp
        self.input_edges = [getattr(t, "_grad_node", None)
                            for t in inputs]

    def release(self):
        self.vjp_fn = None
        self.primal_fn = None
        self.input_arrays = None
        self.released = True


def _toposort(root: TapeNode) -> List[TapeNode]:
    """Iterative DFS post-order over the node graph rooted at ``root``."""
    order: List[TapeNode] = []
    seen = set()
    stack = [(root, False)]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for t in node.inputs:
            n = t._grad_node
            if n is not None and id(n) not in seen and not n.released:
                stack.append((n, False))
    return order


def backward(tensors, grad_tensors=None, retain_graph: bool = False):
    """Run reverse-mode accumulation from ``tensors`` (reference:
    egr::RunBackward, eager/backward.cc:532).

    Leaf tensors (no grad node, stop_gradient=False) receive ``.grad``
    accumulation; intermediate cotangents flow through vjp closures.
    """
    from .tensor import Tensor  # local import to avoid cycle

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    # Cotangent buffer keyed by tensor id (reference: GradTensorHolder).
    cot: Dict[int, Any] = {}
    keep: Dict[int, Any] = {}  # keep tensors alive while their id is a key

    roots: List[TapeNode] = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            g_arr = jnp.ones(t.shape, dtype=t.dtype)
        else:
            g_arr = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        node = t._grad_node
        if node is None:
            if not t.stop_gradient:
                t._accumulate_grad(g_arr)
            continue
        _accum(cot, keep, t, g_arr)
        roots.append(node)

    if not roots:
        return

    # Merge toposorts of all roots.
    order: List[TapeNode] = []
    seen = set()
    for r in roots:
        for n in _toposort(r):
            if id(n) not in seen:
                seen.add(id(n))
                order.append(n)
    # _toposort returns inputs-before-outputs (post-order); reverse sweep needs
    # outputs first.  A node may appear before its consumer across roots, so
    # re-sort globally: consumers must run before producers.  Post-order DFS of
    # each root already guarantees that within a root; across roots we process
    # in reverse of the merged order which preserves it because any shared
    # producer was appended before its consumer in that root's post-order.
    for node in reversed(order):
        if node.released:
            raise RuntimeError(
                "Trying to backward through the graph a second time "
                "(set retain_graph=True if you need to)."
            )
        cts = []
        any_ct = False
        for out in node.outputs:
            c = cot.pop(id(out), None)
            keep.pop(id(out), None)
            if c is None:
                if node.materialize:
                    c = jnp.zeros(out.shape, dtype=out.dtype)
            else:
                any_ct = True
            cts.append(c)
        if not any_ct:
            continue
        for t, edge in zip(node.inputs, node.input_edges):
            if getattr(t, "_grad_node", None) is not edge:
                raise RuntimeError(
                    f"a tensor consumed by op '{node.name}' was later "
                    "modified by an in-place operation, so its backward "
                    "routing is no longer valid; clone() it before the "
                    "in-place op")
        with (_NO_SCOPE if node.scope is None
              else jax.named_scope(f"transpose({node.scope})")):
            in_cts = node.vjp_fn(tuple(cts) if len(cts) > 1 else cts[0])
            for t, g in zip(node.inputs, in_cts):
                if g is None:
                    continue
                if t._grad_node is None:
                    if not t.stop_gradient:
                        t._accumulate_grad(g)
                else:
                    _accum(cot, keep, t, g)
        if not retain_graph:
            node.release()


def _accum(cot: dict, keep: dict, t, g):
    prev = cot.get(id(t))
    cot[id(t)] = g if prev is None else prev + g
    keep[id(t)] = t


def _grad_create_graph(outputs, inputs, grad_outputs, allow_unused):
    """``paddle.grad(..., create_graph=True)``: higher-order-capable grads.

    The stored per-node vjp closures bake the primal values in, so
    differentiating THROUGH them would silently drop every d²y/dx² term
    that flows via the primals.  Instead the recorded subgraph between
    ``inputs`` and ``outputs`` is REPLAYED as one pure jax function of
    the input arrays (each TapeNode keeps its primal_fn for exactly
    this), and its jax.vjp runs through the normal op dispatch — the
    returned grads therefore carry a fresh tape node and are themselves
    differentiable to any order.  Implies retain_graph (nothing is
    released).  Reference: eager double-grad tests
    (test_imperative_double_grad.py) / GradNodeBase higher-order path."""
    from .dispatch import apply_op
    from .tensor import Tensor

    # collect the full ancestry (forward topological order)
    order: List[TapeNode] = []
    seen = set()
    for t in outputs:
        n = getattr(t, "_grad_node", None)
        if n is None:
            continue
        if n.released:
            raise RuntimeError(
                "Trying to backward through the graph a second time "
                "(set retain_graph=True if you need to).")
        for nd in _toposort(n):
            if id(nd) not in seen:
                seen.add(id(nd))
                order.append(nd)
    for nd in order:
        for t in nd.inputs:
            up = getattr(t, "_grad_node", None)
            if up is not None and up.released:
                raise RuntimeError(
                    "Trying to backward through the graph a second time "
                    "(set retain_graph=True if you need to).")

    in_ids = {id(t) for t in inputs}
    # prune to nodes DOWNSTREAM of a requested input: anything upstream
    # of every cut point contributes nothing to the grads (its outputs
    # are either seeds or record-time constants), so it is neither
    # replayed nor required to have a replayable primal
    live_ids = set(in_ids)
    live: List[TapeNode] = []
    for nd in order:
        if any(id(t) in live_ids for t in nd.inputs):
            live.append(nd)
            live_ids.update(id(o) for o in nd.outputs)
    for nd in live:
        if nd.primal_fn is None:
            raise NotImplementedError(
                f"create_graph=True through op '{nd.name}' (a PyLayer) "
                "is not supported: it has no replayable primal")

    # connectivity for allow_unused: every live node is an ancestor of
    # the outputs (order is the outputs' ancestry) and seed-crossing
    # paths still flow, so consumption by a live node means connected
    out_ids = {id(o) for o in outputs}
    consumed_by_live = {id(t2) for nd in live for t2 in nd.inputs}
    reachable = [id(t) in consumed_by_live or id(t) in out_ids
                 for t in inputs]
    if not allow_unused and not all(reachable):
        raise RuntimeError(
            "One of the differentiated tensors appears unused; pass "
            "allow_unused=True to return None for it.")

    # record-time arrays for every node input (first-order backward uses
    # the vjp residuals captured at forward time; replay must agree even
    # if a leaf was mutated in place since)
    recorded: Dict[int, Any] = {}
    for nd in order:
        if nd.input_arrays is not None:
            for t, a in zip(nd.inputs, nd.input_arrays):
                recorded.setdefault(id(t), a)

    def replay(*in_arrays):
        seeds = {id(t): a for t, a in zip(inputs, in_arrays)}
        env: Dict[int, Any] = dict(seeds)
        for nd in live:
            args = [env.get(id(t), recorded.get(id(t), t._value()))
                    for t in nd.inputs]
            outs = nd.primal_fn(*args)
            outs = outs if isinstance(outs, tuple) else (outs,)
            for o, a in zip(nd.outputs, outs):
                if id(o) in seeds:
                    # a requested input that is ALSO produced in-graph:
                    # both grads must flow — d/dseed sees the direct
                    # cotangent, d/dupstream flows through the producer.
                    # value: a + seed - stop_grad(seed) == a (the seed is
                    # the recorded value of this very tensor)
                    s = seeds[id(o)]
                    env[id(o)] = a + (s - jax.lax.stop_gradient(s))
                else:
                    env[id(o)] = a
        return tuple(env.get(id(t), recorded.get(id(t), t._value()))
                     for t in outputs)

    n_in = len(inputs)
    cts = []
    for t, g in zip(outputs,
                    grad_outputs or [None] * len(outputs)):
        if g is None:
            cts.append(Tensor._wrap(jnp.ones(t.shape, dtype=t.dtype),
                                    stop_gradient=True))
        else:
            cts.append(g if isinstance(g, Tensor)
                       else Tensor._wrap(jnp.asarray(g)))

    def hi_primal(*arrs):
        xs, ct_arrs = arrs[:n_in], arrs[n_in:]
        _, vjp = jax.vjp(replay, *xs)
        grads = vjp(tuple(ct_arrs))
        # single-output primals must return a bare array: the tape's
        # backward feeds a matching bare cotangent to this node's vjp
        return grads if n_in > 1 else grads[0]

    res = apply_op("grad_replay", hi_primal, [*inputs, *cts],
                   n_outs=n_in)
    res = res if isinstance(res, tuple) else (res,)
    return [r if ok else None for r, ok in zip(res, reachable)]


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph: Optional[bool] = None,
    create_graph: bool = False,
    allow_unused: bool = False,
):
    """Functional grad API (paddle.grad).  Returns grads of outputs w.r.t.
    inputs without touching ``.grad`` of other leaves."""
    from .tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if create_graph:
        return _grad_create_graph(outputs, inputs, grad_outputs,
                                  allow_unused)
    # Save/restore raw grad payloads so we can reuse the accumulation path.
    saved = [t._grad for t in inputs]
    saved_sg = [t.stop_gradient for t in inputs]
    for t in inputs:
        t._grad = None
        t.stop_gradient = False
    try:
        backward(outputs, grad_outputs, retain_graph=bool(retain_graph))
        res = []
        for t, s in zip(inputs, saved):
            g = t._grad
            if g is None and not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; "
                    "pass allow_unused=True to return None for it."
                )
            res.append(Tensor._wrap(g, stop_gradient=True) if g is not None else None)
        return res
    finally:
        for t, s, sg in zip(inputs, saved, saved_sg):
            t._grad = s
            t.stop_gradient = sg
