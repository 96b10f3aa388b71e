"""What the run stands on: the chip, its published peaks, its memory."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """The run cannot measure: no TPU, too few chips, or interpreted
    kernels."""


def require_tpu(chips: int, interpret: bool) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` when JAX runs on
    another platform, sees fewer chips, or would interpret Pallas kernels
    (``interpret``, as the program resolves it)."""
    import jax
    if "PALLAS_INTERPRET" in os.environ:
        raise NoChip("PALLAS_INTERPRET is set; the benchmark runs compiled "
                     "kernels only")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips needed, JAX sees {len(devs)}")
    if interpret:
        raise NoChip("Pallas kernels would run in interpret mode")
    return devs[:chips]


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}")
    return table[device_kind]


def record(devices) -> dict:
    """The result line's ``device`` object, without the memory peak."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
