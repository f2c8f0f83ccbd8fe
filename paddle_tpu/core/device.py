"""Device / Place abstraction.

Reference parity: ``paddle/phi/common/place.h`` Place classes and the python
``paddle.device`` module (set_device/get_device).  On TPU there is one device
kind that matters; CPU is the host/test backend.  A Place wraps a jax.Device.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax


class Place:
    """Device identity: a backend kind + ordinal (reference: phi::Place)."""

    __slots__ = ("kind", "index")

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_cpu_place(self):
        return self.kind == "cpu"

    @property
    def jax_device(self) -> jax.Device:
        return _jax_device_for(self.kind, self.index)


class CPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("cpu", index)


class TPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("tpu", index)


_current_place: Optional[Place] = None


@functools.lru_cache(maxsize=None)
def _devices_by_kind(kind: str):
    # jax.devices(<platform>) raises RuntimeError when that backend is not
    # present in this process; "no such devices" is the answer, not an error
    try:
        return jax.devices(kind)
    except RuntimeError:
        return []


def _jax_device_for(kind: str, index: int) -> jax.Device:
    devs = _devices_by_kind(kind)
    if not devs:
        raise RuntimeError(f"no {kind} devices available")
    return devs[index % len(devs)]


def _default_place() -> Place:
    d = jax.devices()[0]
    kind = "tpu" if d.platform == "tpu" else "cpu"
    return Place(kind, 0)


def set_device(device: str) -> Place:
    """paddle.device.set_device('tpu') / 'cpu' / 'tpu:0'."""
    global _current_place
    if ":" in device:
        kind, idx = device.split(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = kind.lower()
    if kind in ("gpu", "cuda", "xpu", "npu"):
        # Accelerator alias: on this framework the accelerator is the TPU.
        kind = "tpu"
    if kind not in ("cpu", "tpu"):
        raise ValueError(f"unsupported device {device!r}")
    _current_place = Place(kind, idx)
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.index}"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def is_compiled_with_tpu() -> bool:
    return len(_devices_by_kind("tpu")) > 0


def device_count(kind: Optional[str] = None) -> int:
    if kind is None:
        kind = current_place().kind
    return len(_devices_by_kind(kind))


# ---------------------------------------------------------------------------
# Memory statistics (reference: paddle/fluid/memory/stats.cc surfaced as
# paddle.device.cuda.max_memory_allocated etc.).  On TPU the allocator is
# XLA's (BFC on HBM); PJRT exposes its counters via Device.memory_stats().
# ---------------------------------------------------------------------------

def _resolve_device(device=None) -> jax.Device:
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, Place):
        return _jax_device_for(device.kind, device.index or 0)
    return jax.devices()[0]


def memory_stats(device=None) -> dict:
    """Raw allocator counters for one device (PJRT memory_stats; {} when
    the backend exposes none, e.g. CPU)."""
    return _resolve_device(device).memory_stats() or {}


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (reference:
    paddle.device.cuda.memory_allocated)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes allocated (reference: cuda.max_memory_allocated)."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (reference:
    cuda.memory_reserved); 0 when the backend doesn't expose pool
    counters (counters like bytes_limit describe CAPACITY, not
    reservations, and must not be reported here)."""
    return int(memory_stats(device).get("pool_bytes", 0))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_pool_bytes", s.get("pool_bytes", 0)))


def synchronize(device=None):
    """Block until all queued work on the device finished (reference:
    paddle.device.cuda.synchronize)."""
    import jax.numpy as jnp

    d = _resolve_device(device)
    jax.device_put(jnp.zeros(()), d).block_until_ready()


class _AcceleratorNamespace:
    """paddle.device.tpu.* — the accelerator-scoped stats API (the
    reference's paddle.device.cuda.* shape)."""

    memory_stats = staticmethod(memory_stats)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    synchronize = staticmethod(synchronize)

    @staticmethod
    def device_count() -> int:
        return len(_devices_by_kind("tpu"))


tpu = _AcceleratorNamespace()
# source compatibility for reference code reaching for .cuda on an
# accelerator: same counters, backed by the TPU/PJRT allocator
cuda = tpu


class CUDAPlace(Place):
    """API-compat CUDA place (reference phi/common/place.h GPUPlace).
    This build targets TPU via XLA; constructing one is allowed (so
    ported code parses), and placing tensors on it fails in device
    resolution with the standard no-gpu-devices error."""

    def __init__(self, device_id=0):
        super().__init__("gpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("gpu_pinned", 0)


class NPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("npu", device_id)


class XPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("xpu", device_id)


class NPUPlaceAlias(Place):
    pass


class MLUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("mlu", device_id)


class IPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("ipu", device_id)


# -- capability predicates (reference device/__init__.py): this build
# targets TPU via XLA, so every vendor-specific predicate is False and
# vendor device enumeration returns the XLA device list ---------------

def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def get_cudnn_version():
    return None


def get_all_device_type():
    """Device types visible to XLA (reference returns Place types)."""
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu",)]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [s for s in get_available_device()
            if not s.startswith("cpu")]
