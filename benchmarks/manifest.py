"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here lists a configuration, a traffic mix or a metric: a later PR adds
one by adding its files and its manifest entries. Under the benchmark's root:

- ``configs/<config>.json``: the sizes as run, the program overrides, the name
  of the family adapter and of the plain reference beside it;
- ``traffic/<mix>.json``: program overrides and the environment's parameters;
- ``limits/<workload>.json``: the limit of each number ``correct`` compares;
- ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))


class Manifest:
    def __init__(self, manifest_path: Optional[str] = None, root: str = ROOT):
        self.root = root
        path = manifest_path or os.path.join(os.path.dirname(root), "BENCHMARK.json")
        with open(path) as f:
            self.data = json.load(f)

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def _json(self, *parts: str) -> Dict[str, Any]:
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def config(self, workload: Dict[str, Any]) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == workload["config"]:
                with open(os.path.join(os.path.dirname(self.root), c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {workload['config']!r} in BENCHMARK.json")

    def traffic(self, workload: Dict[str, Any]) -> Dict[str, Any]:
        return self._json("traffic", workload["traffic"] + ".json")

    def limits(self, workload: Dict[str, Any]) -> Dict[str, Any]:
        """The cell's limits file: ``limits`` and, optionally, ``not_compared``."""
        return self._json("limits", workload["name"] + ".json")

    def reference(self, config: Dict[str, Any]):
        return load_module(os.path.join(self.root, "configs", config["reference"]))

    def metrics_for(self, workload: Dict[str, Any], group: str) -> List[Dict[str, Any]]:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [
            m for m in self.data[group]
            if "workloads" not in m or workload["name"] in m["workloads"]
        ]

    def reader(self, metric_name: str) -> Callable:
        return load_module(os.path.join(self.root, "metrics", metric_name + ".py")).read


def load_module(path: str):
    """Import a file whose name need not be a Python identifier."""
    name = "benchfile_" + "".join(c if c.isalnum() else "_" for c in os.path.basename(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
