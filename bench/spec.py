"""Finds everything by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); a mix names its shape
(``bench/shapes/<shape>.py`` with ``pools(mix, n_tenants, rng)``); every
metric has a reader ``bench/metrics/<metric>.py`` with
``read(m) -> float | None``.  Adding a configuration, a mix, a shape, a cell
or a metric is adding files and entries.

A cell on more than one chip runs under its configuration's ``placement``:
the logical placement rules (``{"tenants": "data"}``) bound over a mesh of
the cell's chips, whose one axis is ``MESH_AXIS``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
MESH_AXIS = "data"


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
    chips, cfg = int(w["chips"]), config(w["config"])
    placement = cfg.get("placement")
    if chips > 1 and not placement:
        raise ValueError(f"cell {name!r} asks for {chips} chips, but its "
                         f"configuration {w['config']!r} states no placement")
    if placement and set(placement.values()) - {MESH_AXIS}:
        raise ValueError(f"configuration {w['config']!r}: a placement rule "
                         f"may name only the mesh axis {MESH_AXIS!r}")
    return Cell(name=name, chips=chips, config=cfg, mix=mix(w["traffic"]),
                end_to_end=e2e, per_layer=layer)


def _function(kind: str, name: str, attr: str) -> Callable:
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind}/{name}.py")
    sp = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return getattr(mod, attr)


def reader(metric: str) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _function("metrics", metric, "read")


def shape(name: str) -> Callable:
    """The ``pools`` function of ``bench/shapes/<name>.py``."""
    return _function("shapes", name, "pools")
