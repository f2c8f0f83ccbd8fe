"""Op dispatch: pure-jax primal + tape recording.

Reference parity: the generated ``*_final_state_dygraph_function`` layer
(eager_gen.py:858) — forward compute, AMP cast, grad-node construction — and
the phi kernel dispatch (kernel_factory.h:271).  TPU-native design: every op
is a pure function on jax arrays; XLA is the kernel library, so there is no
registry/dispatch-by-place.  ``apply_op`` runs the primal (through jax.vjp if
any differentiable input requires grad) and records one TapeNode.
"""
from __future__ import annotations

import weakref
from typing import Any, Callable, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.source_info_util import current_name_stack

from . import dtype as dtype_mod
from .autograd import TapeNode, is_grad_enabled
from . import tensor as tensor_mod
from .tensor import Tensor
from .flags import get_flag

_CHECK_NAN_OPS_SKIP = {"isnan", "isinf", "isfinite", "nan_to_num"}


def _unwrap(x):
    if isinstance(x, Tensor):
        return x._value()
    return x


def _is_diff_dtype(arr) -> bool:
    try:
        return dtype_mod.is_floating_point(np.dtype(arr.dtype)) or dtype_mod.is_complex(
            np.dtype(arr.dtype)
        )
    except Exception:
        return False


# AMP autocast hook — installed by paddle_tpu.amp (reference: eager
# amp_auto_cast.h).  Signature: fn(op_name, tensor_args) -> tensor_args.
_amp_cast_hook = None

# Static-graph recording hook — installed by paddle_tpu.static while a
# Program is being built (reference: LayerHelper.append_op into the
# default ProgramDesc).  Signature:
# fn(op_name, primal, tensor_args, kwargs, out_tensors) -> None.
_static_record_hook = None

# Name of the most recently dispatched op — read by the fault-tolerance
# watchdog when a step stalls, so the hang report names the op that was
# in flight (a blocked collective shows up here as its dispatching op).
_last_op_name: str = None


def last_dispatched_op():
    return _last_op_name


def no_static_record():
    """Context manager suspending static-Program recording — for code
    that EXECUTES ops while a program records (composite control-flow
    internals, Executor train replay): the sub-dispatches must not leak
    into the program as stray top-level ops."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        global _static_record_hook
        h = _static_record_hook
        _static_record_hook = None
        try:
            yield
        finally:
            _static_record_hook = h

    return _cm()


def apply_op(
    name: str,
    primal: Callable,
    tensor_args: Sequence[Any],
    kwargs: dict = None,
    n_outs: int = 1,
):
    """Execute op ``primal(*arrays, **kwargs)`` over Tensor/array args.

    - non-Tensor args are passed through as-is (static attrs go in kwargs)
    - records a TapeNode via jax.vjp over the *differentiable Tensor* inputs
    - returns Tensor (or tuple of Tensors if n_outs > 1)
    """
    kwargs = kwargs or {}
    global _last_op_name
    _last_op_name = name
    if _amp_cast_hook is not None:
        tensor_args = _amp_cast_hook(name, tensor_args)

    from .custom_kernel import get_kernel_override

    _override = get_kernel_override(name)
    if _override is not None:
        primal = _override

    arrays = [_unwrap(a) for a in tensor_args]

    diff_idx: List[int] = []
    if is_grad_enabled():
        for i, a in enumerate(tensor_args):
            if (
                isinstance(a, Tensor)
                and not a.stop_gradient
                and _is_diff_dtype(arrays[i])
            ):
                diff_idx.append(i)

    if not diff_idx:
        out = primal(*arrays, **kwargs)
        outs_w = _wrap_outs(name, out, n_outs, stop_gradient=True)
        if _static_record_hook is not None:
            _static_record_hook(name, primal, tensor_args, kwargs,
                                outs_w if isinstance(outs_w, tuple)
                                else (outs_w,))
        return outs_w

    def _primal_on_diff(*diff_arrays):
        full = list(arrays)
        for j, i in enumerate(diff_idx):
            full[i] = diff_arrays[j]
        return primal(*full, **kwargs)

    outs, vjp_fn = jax.vjp(_primal_on_diff, *[arrays[i] for i in diff_idx])
    out_tensors = _wrap_outs(name, outs, n_outs, stop_gradient=False)
    outs_list = list(out_tensors) if isinstance(out_tensors, tuple) else [out_tensors]
    node = TapeNode(
        vjp_fn,
        inputs=[tensor_args[i] for i in diff_idx],
        outputs=outs_list,
        name=name,
        primal_fn=_primal_on_diff,
        input_arrays=[arrays[i] for i in diff_idx],
        scope=(None if tensor_mod._trace_hook is None
               else str(current_name_stack())),
    )
    for t in outs_list:
        t._grad_node = node
    node_ref = weakref.ref(node)
    for i in diff_idx:
        t = tensor_args[i]
        lst = t._consumers
        if lst is None:
            lst = t._consumers = []
        lst.append(node_ref)
        # amortized prune: long-lived tensors (parameters) would otherwise
        # accumulate one dead weakref per consuming op forever
        n = len(lst)
        if n >= 64 and (n & (n - 1)) == 0:
            t._consumers = [r for r in lst if r() is not None]
    if _static_record_hook is not None:
        _static_record_hook(name, primal, tensor_args, kwargs,
                            tuple(outs_list))
    return out_tensors


def _wrap_outs(name, out, n_outs, stop_gradient):
    if get_flag("check_nan_inf") and name not in _CHECK_NAN_OPS_SKIP:
        _check_nan_inf(name, out)
    if n_outs == 1 and not isinstance(out, (tuple, list)):
        return Tensor._wrap(out, stop_gradient=stop_gradient)
    outs = tuple(Tensor._wrap(o, stop_gradient=stop_gradient) for o in out)
    return outs


def _check_nan_inf(name, out):
    """FLAGS_check_nan_inf parity (reference: details/nan_inf_utils_detail.cc
    for the host scan; .cu for the in-graph scan — see core/error_guard)."""
    outs = out if isinstance(out, (tuple, list)) else (out,)
    for o in outs:
        if isinstance(o, jax.core.Tracer):
            # compiled path: arm an in-graph sentinel; the trace runtime
            # raises after the step (error_guard.raise_on_error)
            from . import error_guard

            error_guard.set_error_if_nonfinite(name, o)
            continue
        try:
            a = np.asarray(o)
        except Exception:
            continue
        if a.dtype.kind in "fc" and not np.isfinite(a).all():
            raise FloatingPointError(f"Operator {name} output contains NaN/Inf")


