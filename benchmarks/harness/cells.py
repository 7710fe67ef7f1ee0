"""What a cell is made of, found by name: its entry in BENCHMARK.json,
its configuration, its traffic mix, its own parameters and the per-layer
metrics that list it. A later PR adds a cell by adding files and one
``workloads`` entry; nothing here names a cell, a mix or a metric.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
ROOT = os.path.normpath(os.path.join(BENCH_DIR, ".."))


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    # the cell's own parameters (benchmarks/cells/<cell>.json, optional):
    # what was found for this pair of configuration and mix, such as the
    # offered rate a sweep settled on
    params: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)  # layer metric declarations


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bdir = os.path.join(root, os.path.relpath(BENCH_DIR, ROOT))
    params_path = os.path.join(bdir, "cells", f"{name}.json")
    listed = lambda m: "workloads" not in m or name in m["workloads"]  # noqa: E731
    per_layer = []
    for m in bench["per_layer"]:
        if listed(m):
            # the declaration is the entry; the metric's own file says how
            # to read it from what a run collects
            reader = _load(os.path.join(bdir, "layer_metrics", f"{m['name']}.json"))["reader"]
            per_layer.append({**m, "reader": reader})
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load(os.path.join(root, cfg_entry["file"])),
        traffic=_load(os.path.join(bdir, "traffic", f"{entry['traffic']}.json")),
        params=_load(params_path) if os.path.exists(params_path) else {},
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=per_layer,
    )


def generator_for(traffic: dict):
    """The module that drives this kind of traffic, found by the kind's
    name under ``benchmarks/generators/``."""
    return importlib.import_module(f"benchmarks.generators.{traffic['kind']}")
