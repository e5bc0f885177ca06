"""The device a run measures: a TPU with as many chips as the cell asks
for, or no run at all (no CPU fallback)."""
from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def require(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r} devices, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
