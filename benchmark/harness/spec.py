"""BENCHMARK.json and the files it names, resolved by name.

A cell names a configuration and a traffic mix; each lives in a file of
its own under `benchmark/`, found by that name:

  configs/<config>.json     the deployment (its `workload` names the
                            generator and plain checker in
                            workloads/<workload>.py)
  traffic/<traffic>.json    the mix (its `driver` names
                            drivers/<driver>.py)
  metrics/<metric>.py       one reader per per-layer metric

so a later cell, mix or metric is new files and entries, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]        # benchmark/
ROOT = HERE.parent                                 # the checkout


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: Path = ROOT):
        self.root = root
        self.here = root / "benchmark"
        self.doc = json.loads((root / "BENCHMARK.json").read_text())
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.configs[name]["file"])
                          .read_text())

    def cell_config(self, cell: dict) -> dict:
        """The cell's configuration with its mix's `generator`
        parameters over it."""
        return {**self.config(cell["config"]),
                **self.traffic(cell["traffic"]).get("generator", {})}

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic" / f"{name}.json").read_text())

    def workload_module(self, cfg: dict):
        return load_module(self.here / "workloads" / f"{cfg['workload']}.py")

    def driver_module(self, traffic: dict):
        return load_module(self.here / "drivers" / f"{traffic['driver']}.py")

    def metric_module(self, name: str):
        """metrics/<name>.py, else, for a name split by the end-to-end
        metric it moves (`device_idle_share.serve`), the shared reader
        metrics/<name up to its last dot>.py."""
        path = self.here / "metrics" / f"{name}.py"
        if not path.is_file() and "." in name:
            path = self.here / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
        return load_module(path)

    def end_to_end(self, cell: str) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        """The per-layer metrics read in this cell's traced run: those
        that list it, and those without a list whose `moves` metric
        the cell reports."""
        mine = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if cell in m.get("workloads", ()) or (
                    "workloads" not in m and m["moves"] in mine)]
