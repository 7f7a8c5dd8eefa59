"""The attached device as JAX reports it, and the table of peaks."""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX attached no accelerator, or fewer chips than the cell asks."""


def attached() -> Dict:
    import jax

    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def demand_tpu(chips: int) -> Dict:
    """The device block of the result line, or NoChip. Called before
    anything is built: a measurement never falls back to the CPU."""
    info = attached()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX attached "
                     f"{info['count']} x {info['platform']} "
                     f"({info['kind']})")
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of this kind. An unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]
