"""The device as JAX reports it, in the process that holds the chip.

A run that finds no accelerator, or fewer chips than its cell asks for,
raises NoAccelerator: a number from the CPU is a different result, not a
slower one. The CPU rehearsal tests pass allow_cpu=True.
"""

from __future__ import annotations


class NoAccelerator(RuntimeError):
    pass


def require(chips: int, allow_cpu: bool = False) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] == "cpu" and not allow_cpu:
        raise NoAccelerator(f"JAX finds no accelerator ({info})")
    if info["count"] < chips and not allow_cpu:
        raise NoAccelerator(f"cell needs {chips} chips, JAX finds {info}")
    return info


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the first `chips` devices, or
    None where the backend keeps no such count (the CPU)."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
