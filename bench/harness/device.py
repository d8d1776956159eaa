"""The chips a cell runs on, their published peaks, and device readings.

A run measures the accelerator or nothing: it finds no TPU, fewer chips
than the cell asks for, or a ``device_kind`` missing from ``PEAK_GBPS``,
and it fails without a result.
"""
from __future__ import annotations

# Peak HBM bandwidth of one chip in GB/s, keyed by ``jax.Device.device_kind``.
# TPU v5e: 16 GB of HBM at 819 GB/s, Google Cloud documentation, "TPU v5e"
# (system architecture).
PEAK_GBPS = {
    "TPU v5 lite": 819.0,
}


class NoChip(RuntimeError):
    """The run found no accelerator it can measure."""


def require(chips: int) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in PEAK_GBPS:
        raise NoChip(f"device kind {kind!r} has no published peak in "
                     "PEAK_GBPS")
    return devs[:chips]


def describe(devs: list) -> dict:
    """The result's ``device`` entry; ``memory_peak_bytes`` is the peak on
    the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}
