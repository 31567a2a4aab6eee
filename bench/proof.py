#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program against the plain
reference over a dozen seeds, the bfloat16 control in its place, and the
program with its alarm threshold moved.

    python bench/proof.py --workload link-backbone-sat --seconds 10 \
        --seeds 101,102,103 [--control-seeds 101,102,103] \
        [--fault-seeds 101,102,103 --threshold-scales 1.2,0.8]

One process, one run of the cell per seed as ``run.py`` makes it, at the
cell's own size and window.  Prints one JSON line per run with every
number ``check`` compares; for the control seeds also the numbers the
bfloat16 reference reads on the same stream.  A fault run builds the
engine with its threshold scaled while the reference keeps the true one.
The limit of a number lies between the largest sound reading and the
smallest reading of the control or a fault.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@contextlib.contextmanager
def threshold_scaled(k: float):
    """Every ``DetectionEngine`` built inside alarms above ``k`` times the
    threshold it is given."""
    from repro.serving.engine import DetectionEngine
    orig = DetectionEngine.__init__

    def init(self, net, threshold, *a, **kw):
        orig(self, net, threshold * k, *a, **kw)

    DetectionEngine.__init__ = init
    try:
        yield
    finally:
        DetectionEngine.__init__ = orig


def _ints(text: str):
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--threshold-scales", default="1.2")
    args = ap.parse_args()
    from bench import device, harness, spec
    cell = spec.cell(args.workload)
    devs = device.chips(cell.chips)
    harness.enable_compile_cache()
    ctl = set(_ints(args.control_seeds))
    runs = [(s, 1.0) for s in _ints(args.seeds)] + \
        [(s, float(k)) for k in args.threshold_scales.split(",") if k
         for s in _ints(args.fault_seeds)]
    for seed, k in runs:
        t0 = time.perf_counter()
        with harness.placed(cell, devs):
            with threshold_scaled(k) if k != 1.0 else \
                    contextlib.nullcontext():
                r = harness.Run(cell, seed, False)
                r.setup()
            r.m["setup_s"] = time.perf_counter() - t0
            r.window(args.seconds)
            r.collect(devs)
        t1 = time.perf_counter()
        line = {"seed": seed, "setup_s": r.m["setup_s"],
                "window_s": r.m["window_s"]}
        if k != 1.0:
            line["threshold_scale"] = k
        line["program"] = r.check()
        line["check_s"] = time.perf_counter() - t1
        if seed in ctl and k == 1.0:
            line["control"] = r.check(prec="bf16")
        print(json.dumps(line), flush=True)
        del r
    return 0


if __name__ == "__main__":
    sys.exit(main())
