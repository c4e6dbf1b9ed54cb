"""Finds a cell's files by the names in ``BENCHMARK.json``.

- configuration: the ``file`` of its ``configs`` entry;
- traffic mix: ``bench/traffic/<traffic>.json``;
- per-layer metric: ``bench/metrics/<name>.py``, loaded by path (metric
  names hold dots), whose ``read(record)`` returns a number or ``None``.

A later change adds a configuration, a mix, a metric or a cell by adding
files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list


def load(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return Cell(name=workload, config=config, mix=mix, chips=w["chips"],
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def metric_reader(root: str, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
