#!/usr/bin/env python3
"""The fused step split by named scope, and idle gaps named by the
program's own host spans.

    python bench/scopes.py --workload <cell> --seed <n> [--seconds 3]
        [--keep DIR]

The program labels its device work with ``jax.named_scope``
(serving/fused.py, core/parallel.py): ``pool.gather``, ``pool.scatter``,
``fc`` with ``fc.sort`` / ``fc.scan`` / ``fc.store`` / ``fc.record_gather``
inside, and ``md.kitnet``.  A scope lives only in the HLO ``op_name``
metadata of the compiled step (``jit(step)/vmap(fc)/vmap(fc.store)/
scatter``): a TPU trace's ``XLA Ops`` events carry an instruction's HLO
text and times, not its metadata.  So each op is looked up by instruction
name in the compiled step's HLO text and given the innermost known scope
of its ``op_name`` (``hlo_op_names`` says how ops the compiler left
without one get theirs); an op with no known scope is ``unscoped``.

The engine writes host spans (``engine.dispatch``, with
``engine.slot_collisions`` inside, and ``engine.drain``), on the same clock
as the device ops; an idle gap of the device is named by the innermost
span open over most of it.

The reduction (``scope_of``, ``hlo_op_names``, ``op_scopes``,
``self_ns``, ``split``) is what ``tracing.py`` would call to carry these
numbers into the benchmark's own traced runs.  ``load``,
``innermost_span`` and the command below stand in for ``tracing.load``,
``tracing.name_gap`` and ``harness.Run.window`` until those carry the
program's spans and scopes; they go then.

The command sets a cell up as ``harness.py`` does (with the compile
cache keyed on metadata too, so the scoped step is what runs), then
alternates untraced and traced windows of ``--seconds`` each (``PAIRS``
of them; traced with the Python tracer off).  Each window follows a
second of unmeasured feed (a traced one is followed by another), and
its packets per second run from drain to drain, so no window cuts a
step.  It prints one JSON object: each window's packets per second; for
each traced window the split of the step by scope (device self time per
fused step, and as shares of busy time), the step program's median run,
the unscoped ops above 1 ms a step, the longest idle gaps by span, the
host time of the engine's spans and the count of Python-tracer events;
and the cost of the three spans with no trace active.  It runs on a TPU
only and exits 3 without one.  Runs of ``run.py`` never call it.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import tracing  # noqa: E402

Event = tracing.Event

SCOPES = ("pool.gather", "pool.scatter", "fc", "fc.sort", "fc.scan",
          "fc.store", "fc.record_gather", "md.kitnet")
UNSCOPED = "unscoped"
PROGRAM_SPANS = ("engine.dispatch", "engine.slot_collisions",
                 "engine.drain")
LAYERS = {"pool": ("pool.gather", "pool.scatter"),
          "fc": tuple(s for s in SCOPES if s.split(".")[0] == "fc"),
          "md": ("md.kitnet",)}
# the scope of each layer's scatters (its store-back)
STORES = {"fc": "fc.store", "pool": "pool.scatter"}

MODULES_LINE = "XLA Modules"
PAIRS = 3                          # untraced and traced windows a command

_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost known scope of an HLO ``op_name`` path, matched on a
    path component with or without transform wrappers (``vmap(fc.store)``,
    ``jit(fc)``); ``unscoped`` where no component is a known scope.  Of
    merged metadata (``a;b``) the first path counts."""
    found = UNSCOPED
    for part in op_name.split(";", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in scopes:
            found = part
    return found


# ---------------------------------------------------------------------------
# HLO text: instruction name -> op_name
# ---------------------------------------------------------------------------
_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) ")
# ops that only move or re-view data: without metadata of their own they
# are counted with what they move
MOVES = frozenset({"bitcast", "copy", "copy-start", "copy-done",
                   "get-tuple-element", "reshape", "transpose"})


@dataclass(frozen=True)
class _Instr:
    dtype: str                      # element type of an array result
    opcode: str
    op_name: str
    operands: Tuple[str, ...]
    calls: Optional[str]


def _computations(hlo_text: str):
    """``({computation: {instruction: _Instr}}, {computation: root},
    entry)`` of a module's HLO text (instructions in text order)."""
    comps: Dict[str, Dict[str, _Instr]] = {}
    roots: Dict[str, str] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(2)
            comps[cur] = {}
            if m.group(1):
                entry = cur
            continue
        m = _INSTR.match(line)
        if not m or cur is None:
            continue
        rhs = m.group(3)
        code = _OPCODE.search(rhs)
        head = rhs.split(", metadata=", 1)[0]
        op, calls = _OP_NAME.search(rhs), _CALLS.search(rhs)
        comps[cur][m.group(2)] = _Instr(
            dtype=rhs.split("[", 1)[0],
            opcode=code.group(1) if code else "",
            op_name=op.group(1) if op else "",
            operands=tuple(_OPERAND.findall(head)),
            calls=calls.group(1) if calls else None)
        if m.group(1):
            roots[cur] = m.group(2)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    return comps, roots, entry


def hlo_op_names(hlo_text: str) -> Dict[str, Tuple[str, bool]]:
    """``{instruction: (op_name, bare_scatter)}`` for the entry computation
    of a compiled module's HLO text; ``op_name`` is ``""`` where none can
    be found.

    An instruction without an ``op_name`` of its own (the TPU compiler
    leaves some without) is given one from the data it works on: a fusion
    takes its root's, else the one nearest the root inside it; a copy,
    bitcast, reshape or the like (``MOVES``) takes that of the operand it
    moves.  ``bare_scatter`` marks a fusion whose root is a float scatter
    (a table store) the compiler left without an ``op_name`` (the TPU
    compiler rewrites batched scatters and drops their metadata): its
    ``op_name`` then comes from the scatter's inputs, not from the store.
    """
    comps, roots, entry = _computations(hlo_text)
    memo: Dict[str, Tuple[str, bool]] = {}

    def fused(comp: str) -> Tuple[str, bool]:
        root = comps[comp][roots[comp]]
        if root.op_name:
            return root.op_name, False
        near = next((i.op_name for i in reversed(list(comps[comp].values()))
                     if i.op_name), "")
        return near, root.opcode == "scatter" and root.dtype.startswith("f")

    def at(name: str) -> Tuple[str, bool]:
        if name in memo:
            return memo[name]
        memo[name] = ("", False)                # guards against cycles
        ins = comps[entry][name]
        if ins.op_name:
            out = (ins.op_name, False)
        elif ins.calls in comps:
            out = fused(ins.calls)
        elif ins.opcode in MOVES and ins.operands \
                and ins.operands[0] in comps[entry]:
            out = at(ins.operands[0])
        else:
            out = ("", False)
        memo[name] = out
        return out

    return {name: at(name) for name in comps[entry]}


def instruction(event_name: str) -> str:
    """The instruction name of a TPU op event (``%fusion.49 = ...``)."""
    return event_name.partition(" = ")[0].lstrip("%")


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def self_ns(events: Sequence[Event], lo: float, hi: float
            ) -> Dict[str, float]:
    """Busy time of [lo, hi) by event name: each instant in which some
    event runs goes to the latest-started one running then (an inner,
    nested event before its parent).  The values add up to
    ``tracing.busy_ns(events, lo, hi)``."""
    ev = sorted((s, s + d, n) for n, s, d in events if s < hi and s + d > lo)
    cuts = sorted({min(max(t, lo), hi) for s, e, _ in ev for t in (s, e)})
    out: Dict[str, float] = {}
    heap: List[Tuple[float, int, float, str]] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ev) and ev[i][0] <= a:
            heapq.heappush(heap, (-ev[i][0], i, ev[i][1], ev[i][2]))
            i += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            n = heap[0][3]
            out[n] = out.get(n, 0.0) + (b - a)
    return out


def innermost_span(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The host span an idle gap is spent in: each instant of the gap goes
    to the innermost span open then (the latest-started, as in
    ``self_ns``), and the gap takes the name that holds most of it;
    ``host.other`` where no span overlaps it."""
    own = self_ns(spans, gap[0], gap[1])
    return max(own, key=own.get) if own else "host.other"


@dataclass
class Split:
    window_s: float
    busy_s: float                              # mean over the devices
    steps: int                                 # fused batches in the slice
    scope_s: Dict[str, float]                  # device self time by scope
    op_s: Dict[str, Tuple[str, float]]         # op -> (scope, seconds)
    idle_gaps: List[Tuple[str, float]]         # 10 longest
    span_s: Dict[str, List[float]]             # program span durations

    def per_step_ms(self, seconds: float) -> Optional[float]:
        return 1e3 * seconds / self.steps if self.steps else None

    def layer_ms(self) -> Dict[str, Optional[float]]:
        """Device ms per fused step: each layer, ``fc.store`` alone,
        ``unscoped``, and the whole step (the same denominator as
        ``step_device_ms.sat``)."""
        out = {k: self.per_step_ms(sum(self.scope_s.get(s, 0.0) for s in v))
               for k, v in LAYERS.items()}
        out["fc_store"] = self.per_step_ms(self.scope_s.get("fc.store", 0.0))
        out[UNSCOPED] = self.per_step_ms(self.scope_s.get(UNSCOPED, 0.0))
        out["step"] = self.per_step_ms(self.busy_s)
        return out

    def span_ms(self, name: str) -> Optional[float]:
        """Mean host ms of a program span in the slice."""
        d = self.span_s.get(name)
        return 1e3 * statistics.fmean(d) if d else None


def split(tr: tracing.Trace, op_scope: Dict[str, str], steps: int) -> Split:
    """Split a traced slice: ``tr.ops`` are ``(instruction, start, dur)``
    per device, ``op_scope`` maps an instruction to its scope (missing:
    ``unscoped``), ``tr.spans`` are host spans (benchmark and program)."""
    lo, hi = tr.window
    if not tr.ops:
        raise RuntimeError("the trace holds no device plane")
    k = len(tr.ops)
    busy = 0.0
    scope_s: Dict[str, float] = {}
    op_s: Dict[str, List] = {}
    gaps: List[Tuple[str, float]] = []
    for ev in tr.ops.values():
        b = tracing.union(ev, lo, hi)
        busy += sum(e - s for s, e in b)
        for op, ns in self_ns(ev, lo, hi).items():
            sc = op_scope.get(op, UNSCOPED)
            scope_s[sc] = scope_s.get(sc, 0.0) + ns / k / 1e9
            op_s.setdefault(op, [sc, 0.0])[1] += ns / k / 1e9
        gaps += [(innermost_span(g, tr.spans), (g[1] - g[0]) / 1e9)
                 for g in tracing.gaps(b, lo, hi)]
    spans: Dict[str, List[float]] = {}
    for n, s, d in tr.spans:
        if n in PROGRAM_SPANS and lo <= s < hi:
            spans.setdefault(n, []).append(d / 1e9)
    return Split(window_s=(hi - lo) / 1e9, busy_s=busy / k / 1e9,
                 steps=steps, scope_s=scope_s,
                 op_s={o: (v[0], v[1]) for o, v in op_s.items()},
                 idle_gaps=sorted(gaps, key=lambda g: -g[1])[:10],
                 span_s=spans)


def load(logdir: str) -> Tuple[tracing.Trace, List[Event], int]:
    """Read the one ``.xplane.pb`` under ``logdir``: the device ops by
    instruction name and the benchmark's and the program's host spans (a
    ``Trace``), the device's program runs (``XLA Modules``), and the
    number of Python-tracer events (``$file:line function``)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {len(paths)}")
    names = set(tracing.HOST_SPANS) | set(PROGRAM_SPANS)
    ops: Dict[str, List[Event]] = {}
    runs: List[Event] = []
    spans: List[Event] = []
    python_events = 0
    window = None
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
                if line.name == tracing.OPS_LINE:
                    ops[plane.name] = [(instruction(n), s, d)
                                       for n, s, d in ev]
                elif line.name == MODULES_LINE:
                    runs += ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tracing.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in names:
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith("$"):
                        python_events += 1
    if window is None:
        raise RuntimeError(f"no {tracing.WINDOW} span in the trace")
    return tracing.Trace(window=window, ops=ops, spans=spans), runs, \
        python_events


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: scope}`` of a compiled step's HLO text.  A bare
    scatter (see ``hlo_op_names``) counts as the store-back scope of the
    layer its inputs belong to (``STORES``): every float scatter the
    program writes under ``fc`` is an ``fc.store`` (tests/test_engine.py
    holds the program to it)."""
    out = {}
    for n, (op, bare) in hlo_op_names(hlo_text).items():
        sc = scope_of(op)
        out[n] = STORES.get(sc.split(".")[0], sc) if bare else sc
    return out


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------
def _drive(r, seconds: float) -> Tuple[int, List[Tuple[float, int]]]:
    """The saturating feed for ``seconds``: the batches dispatched, and
    (time, packets drained) as each ``step()`` returns, which is just
    after a drain."""
    t0, batches, marks = time.perf_counter(), 0, []
    while time.perf_counter() - t0 < seconds:
        r.feed()
        batches += r.step()
        marks.append((time.perf_counter(),
                      r.engine.stats()["aggregate"]["pkts_processed"]))
    return batches, marks


def _window(r, seconds: float, traced: bool, lead_s: float = 1.0) -> Dict:
    """Drive the saturating feed for ``seconds`` with ``lead_s`` of it
    unmeasured before and, when ``traced``, after, and trace the whole
    with the Python tracer off; the measured part is the ``bench.window``
    span.  The device's record leaves out the ops in flight when a trace
    starts and when it stops, so the slice keeps clear of both."""
    import jax
    logdir = tempfile.mkdtemp(prefix="bench-scopes-") if traced else None
    if logdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    _drive(r, lead_s)
    if logdir:
        span = jax.profiler.TraceAnnotation(tracing.WINDOW)
        span.__enter__()
    batches, marks = _drive(r, seconds)
    (ta, pa), (tb, pb) = marks[0], marks[-1]
    # drain to drain: no part of a step is cut at either end
    out = {"pps": (pb - pa) / (tb - ta) if tb > ta else None,
           "batches": batches, "traced": traced}
    if logdir:
        span.__exit__(None, None, None)
        _drive(r, lead_s)
        jax.profiler.stop_trace()
        out["logdir"] = logdir
    return out


def _step_hlo(eng) -> str:
    """Compiled HLO text of the engine's fused tenant step at the shapes
    the window ran (one lane per tenant in the batch)."""
    import jax.numpy as jnp
    import numpy as np
    lanes = min(eng.max_batch, len(eng.pool.live))
    pk = {k: jnp.zeros((lanes, eng.chunk),
                       jnp.float32 if k in ("ts", "length") else jnp.uint32)
          for k in ("src", "dst", "sport", "dport", "proto", "length", "ts")}
    ids = jnp.arange(lanes, dtype=jnp.int32)
    return eng._tenant_step().lower(
        eng.pool.stacked, ids, eng.net, np.float32(eng.threshold),
        jnp.zeros(lanes, jnp.int32), pk).compile().as_text()


def _span_cost_us(n: int = 100_000) -> float:
    """Host microseconds of the engine's three spans per dispatch with no
    trace active (two ``TraceAnnotation``s and a nested one)."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("engine.dispatch"):
            with TraceAnnotation("engine.slot_collisions"):
                pass
        with TraceAnnotation("engine.drain"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def _report(sp: Split, tr: tracing.Trace, runs: List[Event],
            op_scope: Dict[str, str]) -> Dict:
    lo, hi = tr.window
    # whole runs of the step program inside the slice: a step's device
    # time without the slice's cut steps at either end
    whole: Dict[str, List[float]] = {}
    for n, s, d in runs:
        if lo <= s and s + d <= hi:
            whole.setdefault(n, []).append(d / 1e6)
    step = max(whole.values(), key=sum, default=[])
    layer = sp.layer_ms()
    scoped = 1.0 - sp.scope_s.get(UNSCOPED, 0.0) / sp.busy_s \
        if sp.busy_s else None
    big = sorted(((o, s, 1e3 * v / sp.steps) for o, (s, v) in sp.op_s.items()
                  if s == UNSCOPED and sp.steps and 1e3 * v / sp.steps > 1.0),
                 key=lambda x: -x[2])
    return {"steps": sp.steps, "window_s": sp.window_s, "busy_s": sp.busy_s,
            "idle_pct": 100.0 * (1.0 - sp.busy_s / sp.window_s),
            "layer_ms": layer,
            "scope_ms": {s: sp.per_step_ms(v)
                         for s, v in sorted(sp.scope_s.items())},
            "scope_pct": {s: 100.0 * v / sp.busy_s
                          for s, v in sorted(sp.scope_s.items())},
            "step_runs_ms": statistics.median(step) if step else None,
            "step_runs": len(step),
            "scoped_pct": None if scoped is None else 100.0 * scoped,
            "unscoped_ops_over_1ms": [[o, ms] for o, _, ms in big],
            "idle_gaps": [list(g) for g in sp.idle_gaps],
            "span_ms": {n: sp.span_ms(n) for n in PROGRAM_SPANS},
            "span_count": {n: len(sp.span_s.get(n, ())) for n in
                           PROGRAM_SPANS},
            "ops_not_in_hlo": sum(1 for ev in tr.ops.values()
                                  for n, _, _ in ev if n not in op_scope)}


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--keep", default=None,
                    help="copy the step's HLO text and the trace of each "
                         "traced window into this directory")
    args = ap.parse_args(argv)

    from bench import device, harness, spec
    cell = spec.cell(args.workload)
    try:
        devs = device.chips(cell.chips)
    except device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    import jax
    # the cache key leaves metadata out by default: a step cached from a
    # build without the scopes would be served, and its ops carry none
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    r = harness.Run(cell, args.seed, tracing_on=False)
    r.setup()
    windows = []
    for _ in range(PAIRS):
        windows.append(_window(r, args.seconds, False))
        windows.append(_window(r, args.seconds, True))
    hlo = _step_hlo(r.engine)
    scopes = op_scopes(hlo)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, "step.hlo"), "w") as f:
            f.write(hlo)
    for i, w in enumerate(windows):
        if "logdir" in w:
            logdir = w.pop("logdir")
            tr, runs, w["python_events"] = load(logdir)
            if args.keep:
                path, = glob.glob(os.path.join(logdir, "plugins", "profile",
                                               "*", "*.xplane.pb"))
                shutil.copy(path, os.path.join(args.keep,
                                               f"window{i}.xplane.pb"))
            shutil.rmtree(logdir, ignore_errors=True)
            w["split"] = _report(split(tr, scopes, w["batches"]), tr, runs,
                                 scopes)
    out = {"workload": args.workload, "seed": args.seed,
           "device": {"kind": devs[0].device_kind,
                      "count": len(devs)},
           "pps_untraced": [w["pps"] for w in windows if not w["traced"]],
           "pps_traced": [w["pps"] for w in windows if w["traced"]],
           "span_cost_us": _span_cost_us(),
           "windows": windows}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
