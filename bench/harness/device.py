"""The chip the run is on: a TPU with enough chips, its peaks, its memory."""
from __future__ import annotations

import json
from pathlib import Path

from bench.harness.registry import BenchError

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; any other platform, or fewer chips,
    is an error (there is no CPU fallback)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found platform {platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks for ``device_kind``; unknown is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


def device_info(devices: list) -> dict:
    """The result's ``device`` block; ``memory_peak_bytes`` is the peak on
    the fullest chip as the backend reports it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
