"""The chip the run is on: JAX must see a TPU, and its peaks must be known."""
from __future__ import annotations

import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(n: int) -> List:
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform if devs else 'no'} "
                     "devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX reports {len(devs)}")
    return devs[:n]


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")
          ) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(path)}")
    return table[device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
