"""The device a run measures: the TPU check, its peaks, its memory.

A run that finds no TPU, fewer chips than its cell asks for, or a
``device_kind`` that ``bench/peaks.json`` does not list is refused before
any work: a number is never measured on, or scaled by, the wrong device.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class Refused(RuntimeError):
    """The run cannot measure this cell on this machine."""


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in {path.name}; "
                      f"known: {sorted(table)}")
    return table[kind]


def check_devices(devices, chips: int, path: Path = PEAKS) -> dict:
    """The peaks of the cell's chips, or ``Refused``."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else None
        raise Refused(f"no TPU: JAX's first device is {plat!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return peaks_for(devices[0].device_kind, path)


def device_record(devices, chips: int) -> dict:
    """The result line's ``device``: platform, kind, count and the peak
    bytes in use on the fullest chip of the cell."""
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}
