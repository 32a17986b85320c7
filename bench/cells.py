"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Everything particular to one configuration, traffic mix or per-layer
metric is a file found by its name, so a cell added to the benchmark is
new files and new entries, never an edit here:

* ``configs[].file``                 the configuration (sizes, settings),
* ``bench/traffic/<traffic>.json``   the traffic mix's parameters,
* ``bench/limits/<config>.json``     the limits of the correctness check,
* ``bench/metrics/<metric>.py``      a reader with ``read(record)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT

    def readers(self) -> Dict[str, Callable[[Dict], object]]:
        return {m["name"]: reader(m["name"], self.root)
                for m in self.per_layer}


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``, with its files read."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(root, "bench", "traffic",
                                   w["traffic"] + ".json")),
        limits=_json(os.path.join(root, "bench", "limits",
                                  w["config"] + ".json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
        root=root)


def reader(metric: str, root: str = ROOT) -> Callable[[Dict], object]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
