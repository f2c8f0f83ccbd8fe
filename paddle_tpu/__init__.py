"""paddle_tpu: a TPU-native deep learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas.

Usage mirrors the reference's python surface::

    import paddle_tpu as paddle
    paddle.device.set_device("tpu")
    x = paddle.to_tensor([[1., 2.], [3., 4.]])
    y = paddle.matmul(x, x)
    y.sum().backward()
"""
from __future__ import annotations

__version__ = "0.1.0"

# Multi-controller bootstrap MUST precede any XLA backend use, and package
# import touches the backend — so when the launcher's env contract
# (distributed/launch) is present, wire up jax.distributed here, first.
import os as _os

if int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 \
        and _os.environ.get("PADDLE_MASTER"):
    import jax as _jax

    # A failure here (unreachable coordinator, timeout) must propagate:
    # in a PADDLE_TRAINERS_NUM>1 env a worker that silently degraded to
    # single-process would see process_index()==0 and impersonate rank 0
    # — training unsynchronized and clobbering the real rank 0's
    # checkpoint shards.  Fail fast and let the launcher's restart path
    # retry with a fresh coordinator.
    if not _jax.distributed.is_initialized():
        _jax.distributed.initialize(
            coordinator_address=_os.environ["PADDLE_MASTER"],
            num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
            process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")))

from .core import dtype as _dtype_mod
from .core.dtype import (
    bfloat16, float16, float32, float64, int8, int16, int32, int64,
    uint8, uint16, uint32, uint64, bool_, complex64, complex128,
    float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, to_tensor
from .core.autograd import no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad
from .core.rng import seed, get_rng_state, set_rng_state, Generator
from .core.flags import get_flags, set_flags, define_flag
from .core import device
from .core.device import (  # noqa: F401
    set_device, get_device, is_compiled_with_tpu, CPUPlace, TPUPlace, Place,
    CUDAPlace, CUDAPinnedPlace, NPUPlace,
)

from .ops import *  # noqa: F401,F403 — the paddle.* op surface
from .ops.logic import is_tensor

# Subsystem imports.  Every listed module must exist — a broken subpackage
# should fail the import loudly, not silently drop off the namespace
# (round-2 review: the try/except-ImportError pattern hid breakage).
from . import (  # noqa: F401
    nn, optimizer, amp, io, jit, vision, metric, distributed, autograd,
    framework, profiler, incubate, hapi, static, text, utils, inference,
    distribution, fft, signal, regularizer, hub, version, sparse, onnx,
    serving, obs,
)

__version__ = version.full_version

from .framework.io import save, load  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi import callbacks  # noqa: F401

# paddle.disable_static/enable_static parity: this framework is always
# "dygraph" at the API level; to_static compiles whole programs via XLA.
_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True
    from .static import program as _sp

    _sp._install_hook()


def disable_static():
    global _static_mode
    _static_mode = False
    from .static import program as _sp

    _sp._remove_hook()


def in_dynamic_mode():
    return not _static_mode


def is_grad_enabled_():
    return is_grad_enabled()


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary

    return _summary(net, input_size, dtypes=dtypes, input=input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops

    return _flops(net, input_size, custom_ops=custom_ops,
                  print_detail=print_detail)


from . import sysconfig  # noqa: F401,E402
from .batch import batch  # noqa: F401,E402


# build-capability predicates (reference framework.py): this build targets
# TPU via XLA — never CUDA/XPU/NPU binaries.
def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_cinn():
    return False


def get_cudnn_version():
    return None


class _DtypeInfo:
    def __init__(self, np_info):
        self.min = float(np_info.min) if hasattr(np_info, "min") else None
        self.max = float(np_info.max)
        self.dtype = str(np_info.dtype)
        if hasattr(np_info, "eps"):
            self.eps = float(np_info.eps)
            self.tiny = float(np_info.tiny)
            self.smallest_normal = float(np_info.tiny)
            self.resolution = float(np_info.resolution)
        else:
            self.bits = int(np_info.bits)


def iinfo(dtype):
    """Integer dtype limits (reference pybind iinfo)."""
    import numpy as _np

    info = _np.iinfo(_dtype_mod.convert_dtype(dtype))
    out = _DtypeInfo(info)
    out.min = int(info.min)
    out.max = int(info.max)
    out.bits = int(info.bits)
    return out


# reference top-level odds and ends ---------------------------------------
from .nn.layer_base import ParamAttr  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402

# dtype aliases exported at top level (paddle.bool etc. come from core.dtype
# via the star import; `dtype` is the metatype name in the reference pybind)
import numpy as _np  # noqa: E402

dtype = _np.dtype   # the metatype: isinstance(x.dtype, paddle.dtype)
bool = _dtype_mod.convert_dtype("bool")  # noqa: A001


def reverse(x, axis, name=None):
    """Reference paddle.reverse (fluid-era alias of flip)."""
    from .ops.manipulation import flip

    return flip(x, axis)


def create_parameter(shape, dtype, name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Top-level parameter factory (reference
    python/paddle/tensor/creation.py create_parameter)."""
    from .nn import layer_base

    helper = layer_base.Layer()
    p = helper.create_parameter(shape, attr=attr, dtype=dtype,
                                is_bias=is_bias,
                                default_initializer=default_initializer)
    if name:
        p.name = name
    return p


def disable_signal_handler():
    """Reference parity no-op: paddle installs C++ signal handlers that
    this build never installs (XLA/jax own the runtime)."""


def get_cuda_rng_state():
    """CUDA RNG surface: no CUDA in the TPU build — empty state list
    (shape-compatible with reference callers that save/restore it)."""
    return []


def set_cuda_rng_state(state_list):
    if state_list:
        raise RuntimeError(
            "set_cuda_rng_state: no CUDA devices in the TPU build")


def finfo(dtype):
    """Float dtype limits (reference pybind finfo)."""
    import numpy as _np
    import ml_dtypes as _mld  # jax dependency, provides bfloat16 finfo

    dt = _dtype_mod.convert_dtype(dtype)
    try:
        info = _np.finfo(dt)
    except (TypeError, ValueError):
        info = _mld.finfo(dt)
    return _DtypeInfo(info)
