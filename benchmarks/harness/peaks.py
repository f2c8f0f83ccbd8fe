"""The benchmark's own table of chip peaks, keyed by ``device_kind`` exactly
as JAX reports it.  A copy on purpose: a PR to the program must not be able to
move the yardstick.  A kind that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9,
                    "hbm_bytes": 16e9},
}


def attached(chips: int):
    """``(device dict, peaks)`` of the accelerator this process holds, or
    ``SystemExit`` with a non-zero code: no CPU mode of a measurement."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" or d.device_kind not in PEAKS:
        raise SystemExit(
            f"benchmark: a TPU from the peaks table {sorted(PEAKS)} is "
            f"required, JAX found platform {d.platform!r} kind "
            f"{d.device_kind!r} x{len(devices)}")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devices)}")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devices)}, PEAKS[d.device_kind])


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the backend reports."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())
