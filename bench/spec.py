"""Finds everything by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); every metric has a reader
``bench/metrics/<metric>.py`` with ``read(m) -> float | None``.  Adding a
configuration, a mix, a cell or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]          # the metric entries this cell reports
    per_layer: List[Dict]


def load_benchmark(path: str = os.path.join(CHECKOUT, "BENCHMARK.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def _json(kind: str, name: str) -> Dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def config(name: str) -> Dict:
    return _json("configs", name)


def mix(name: str) -> Dict:
    return _json("traffic", name)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without ``workloads`` is read in every cell that
    # reports the end-to-end metric it moves
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config(w["config"]),
                mix=mix(w["traffic"]), end_to_end=e2e, per_layer=layer)


def reader(metric: str) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
