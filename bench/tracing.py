"""From a profiler trace to the numbers the per-layer metrics read.

The benchmark writes its own host spans (``jax.profiler.TraceAnnotation``)
around its calls into the engine: ``bench.window`` around the traced
slice, ``bench.generate``, ``engine.submit`` and ``engine.step`` inside
it.  The device's operations come from the trace's device planes
(``/device:TPU:<id>``, line ``XLA Ops``); the reduction reads only the
planes of the devices the cell ran on.  Everything here works on plain
``(name, start_ns, duration_ns)`` tuples, so it is checked on synthesised
traces without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

WINDOW = "bench.window"
HOST_SPANS = ("bench.generate", "engine.submit", "engine.step")
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    window: Tuple[float, float]                       # ns
    ops: Dict[str, List[Event]] = field(default_factory=dict)   # per device
    spans: List[Event] = field(default_factory=list)


def load(logdir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ev = [(e.name, e.start_ns, e.duration_ns)
                  for line in plane.lines if line.name == OPS_LINE
                  for e in line.events]
            ops[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.duration_ns))
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in the trace")
    return Trace(window=window, ops=ops, spans=spans)


def union(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of ``events`` clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s < hi and s + d > lo)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out if e > s]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(events, lo, hi))


_SORT = re.compile(r"(^|[\s)])sort\(")


def op_name(event_name: str) -> str:
    """The trace names a TPU op by its HLO text (``%fusion.49 = f32[4194304,4]
    {...} fusion(...)``); keep the instruction name and its result shape."""
    lhs, _, rhs = event_name.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return (lhs.lstrip("%") + " " + shape).strip()


def is_sort(event_name: str) -> bool:
    """An HLO sort instruction (not an op that merely reads a sort's
    result)."""
    lhs, _, rhs = event_name.partition(" = ")
    return lhs.lstrip("%").startswith("sort") or bool(_SORT.search(rhs))


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) between busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The host span that overlaps an idle gap most, or ``host.other``."""
    best, name = 0.0, "host.other"
    for n, s, d in spans:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, name = ov, n
    return name


def plane(device_id: int) -> str:
    """The trace's plane of the device JAX numbers ``device_id``."""
    return f"/device:TPU:{device_id}"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the devices used
    busy_s_per_device: List[float]      # in the order the devices were given
    sort_s: float
    op_s: Dict[str, float]              # every op, mean over the devices used
    device_ops: List[Tuple[str, float]]       # top 10 of op_s
    idle_gaps: List[Tuple[str, float]]        # 10 longest


def reduce(tr: Trace, device_ids: Sequence[int]) -> Reduced:
    """The numbers of the traced slice on the devices the cell ran on;
    other devices' planes are left out."""
    lo, hi = tr.window
    names = [plane(i) for i in device_ids]
    if not names:
        raise RuntimeError("no device to read the trace of")
    missing = [n for n in names if n not in tr.ops]
    if missing:
        raise RuntimeError(f"the trace holds no plane {', '.join(missing)}")
    busy, sort = [], 0.0
    per_op: Dict[str, float] = {}
    all_gaps: List[Tuple[str, float]] = []
    for dev in names:
        ev = tr.ops[dev]
        b = union(ev, lo, hi)
        busy.append(sum(e - s for s, e in b))
        sort += busy_ns([x for x in ev if is_sort(x[0])], lo, hi)
        for n, s, d in ev:
            ov = min(hi, s + d) - max(lo, s)
            if ov > 0:
                per_op[op_name(n)] = per_op.get(op_name(n), 0.0) + ov
        all_gaps += [(name_gap(g, tr.spans), g[1] - g[0])
                     for g in gaps(b, lo, hi)]
    k = len(names)
    op_s = {n: v / k / 1e9 for n, v in per_op.items()}
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(all_gaps, key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / k / 1e9,
                   busy_s_per_device=[b / 1e9 for b in busy],
                   sort_s=sort / k / 1e9, op_s=op_s, device_ops=top,
                   idle_gaps=[(n, v / 1e9) for n, v in longest])
