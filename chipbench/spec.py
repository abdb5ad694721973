"""Finding a cell's files by the names in ``BENCHMARK.json``.

- a workload names a configuration and a traffic mix;
- a configuration's ``file`` is given in ``BENCHMARK.json``; its
  ``served_path`` names a driver, ``chipbench/served_paths/<name>.py``;
- a traffic mix is ``chipbench/traffic/<name>.json``;
- a metric is read by ``chipbench/end_to_end/<name>.py`` or
  ``chipbench/layer_metrics/<name>.py``, each with one ``read(record)``.

Nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List, Optional

HERE = "chipbench"
GROUPS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


@dataclass
class Cell:
    root: str
    workload: dict
    config: dict
    mix: dict
    bench: dict

    def metrics(self, group: str) -> List[dict]:
        """The metrics of ``group`` this cell reports."""
        name = self.workload["name"]
        return [
            m for m in self.bench[group]
            if "workloads" not in m or name in m["workloads"]
        ]


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = _json(
        os.path.join(root, HERE, "traffic", cell["traffic"] + ".json")
    )
    return Cell(root, cell, config, mix, bench)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: str, group: str, metric: str
                ) -> Callable[[object], Optional[float]]:
    path = os.path.join(root, HERE, GROUPS[group], metric + ".py")
    return _module(path, f"chipbench_metric_{group}_{metric}").read


def load_driver(root: str, served_path: str):
    path = os.path.join(root, HERE, "served_paths", served_path + ".py")
    return _module(path, f"chipbench_path_{served_path}").Driver
