"""The device a run measures, and the clocks set-up is read from."""

from __future__ import annotations

import os


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_tpu(jax, chips: int) -> dict:
    """The run's device as JAX reports it; raises unless JAX's devices are
    TPUs and there are at least ``chips`` of them."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX finds no device: {e}") from None
    first = devices[0]
    if first.platform != "tpu":
        raise NoAccelerator(
            f"JAX's first device is {first.platform} ({first.device_kind}); "
            f"this benchmark measures only on a TPU")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips and JAX finds {len(devices)}")
    return describe(devices[:chips])


def describe(devices) -> dict:
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where the backend
    reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), so that set-up
    counts the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22 of proc(5), 1-based
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
