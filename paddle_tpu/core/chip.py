"""The attached accelerator: what it is, its published peaks, and where
its compiled programs are cached.

Everything that measures on the device (``chip_smoke.py``, ``bench.py``)
starts here, so that a result can never come from a CPU, an unrecognised
chip or an assumed peak without saying so:

- :data:`CHIP_PEAKS` is the repo's ONE table of hardware peaks, keyed by
  ``jax.devices()[0].device_kind``.  A kind that is not in it is an
  error, never a default.
- :func:`attached_chip` reads the device this process holds and refuses
  anything that is not a TPU from that table.
- :func:`place_compile_cache` points JAX's persistent compilation cache
  at the one place a later process can find it again.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Tuple

import jax

__all__ = ["ChipPeaks", "CHIP_PEAKS", "chip_peaks", "attached_chip",
           "place_compile_cache"]


class ChipPeaks(NamedTuple):
    """Published per-chip peaks (what a measured rate is divided by)."""

    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float


#: keyed by ``device_kind`` exactly as JAX reports it.
CHIP_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 200e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; an unknown kind raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: expected "
            f"one of {sorted(CHIP_PEAKS)} (add the chip to "
            f"paddle_tpu.core.chip.CHIP_PEAKS with its source)") from None


def attached_chip() -> Tuple[dict, ChipPeaks]:
    """``({"platform", "kind", "count"}, peaks)`` of the accelerator this
    process holds, as JAX reports it.  Raises unless the platform is
    ``tpu`` and the kind is in :data:`CHIP_PEAKS` — there is no CPU mode
    of a measurement."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"a TPU is required, but JAX found platform {d.platform!r} "
            f"(device_kind {d.device_kind!r}, {len(devices)} device(s))")
    peaks = chip_peaks(d.device_kind)
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}, peaks)


def place_compile_cache() -> str:
    """Decide where this process keeps compiled programs; call it before
    the first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read
    by JAX itself and nothing is touched.  Otherwise the cache goes to
    ``<checkout>/.xla_cache``: a fixed path, because the path is part of
    what a later process has to present to find the entries again."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
