"""The device a run measures: the chip check, the peaks table, memory.

A run that finds no TPU, or fewer chips than its cell asks for, raises
before any work; there is no CPU fallback for a device metric.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes: float  # bytes/s per chip
    source: str


# Keyed by jax.Device.device_kind. A device that is not here is an error.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes=819e9,
        source=(
            'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
            "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"
        ),
    ),
}


def peaks_for(kind: str) -> Peaks:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[kind]


def check_devices(devices, chips: int) -> dict:
    """The device record for ``chips`` TPU chips; raises otherwise."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"the benchmark runs on a TPU; JAX found platform {dev.platform!r} ({dev.device_kind})"
        )
    if len(devices) < chips:
        raise RuntimeError(f"the cell asks for {chips} chips; JAX sees {len(devices)}")
    peaks_for(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def require_tpu(chips: int) -> dict:
    """Open the TPU backend (never the CPU) and check it holds ``chips``."""
    import jax

    jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise RuntimeError(f"the benchmark runs on a TPU; JAX could not open one: {e}") from e
    return check_devices(devices, chips)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where unreported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
