"""The device a run measures: found, named, and looked up in the table of
peaks.  A run that finds no accelerator, or fewer chips than its cell
asks for, stops before it measures anything."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


class NoDevice(RuntimeError):
    pass


def require(chips: int) -> dict:
    """The device record of the result line; raises NoDevice off a TPU
    or with fewer than `chips` chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"this benchmark measures a TPU; JAX found "
                       f"{d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips; JAX found "
                       f"{len(devs)}")
    peaks(d.device_kind)
    return describe(devs[:chips])


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks_ = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_) if peaks_ else 0
