"""Finds everything a cell needs by the names in `BENCHMARK.json`:

- `bench/configs/<config>.json`: the configuration as it is run; its
  `reference` key names the plain reference `bench/references/<ref>.py` and
  the adapter `bench/adapters/<ref>.py` that puts it into the program;
- `bench/traffic/<traffic>.json`: the traffic mix's parameters;
- `bench/cells/<workload>.json`: the cell's offered rate, engine layout,
  correctness sample and limits;
- `bench/metrics/<metric>.py`: one reader per metric, `read(ctx)`.

A later cell, mix, configuration or metric is new files and new entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One `workloads` entry of BENCHMARK.json and the files it names."""

    def __init__(self, name: str, root: Path = ROOT):
        bench_json = root / "BENCHMARK.json"
        if not bench_json.is_file():
            raise FileNotFoundError(f"{bench_json} not found")
        self.bench = json.loads(bench_json.read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
        self.root = root
        self.bdir = root / "bench"
        self.workload = cells[name]
        self.name = name
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config = json.loads((root / conf["file"]).read_text())
        self.mix = json.loads(
            (self.bdir / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.layout = json.loads(
            (self.bdir / "cells" / f"{name}.json").read_text())
        self.chips = int(self.workload["chips"])

    def reference(self) -> ModuleType:
        return load_module(self.bdir / "references"
                           / f"{self.config['reference']}.py")

    def adapter(self) -> ModuleType:
        return load_module(self.bdir / "adapters"
                           / f"{self.config['reference']}.py")

    def metrics(self, traced: bool) -> List[Dict]:
        """This cell's metrics: end-to-end untraced, per-layer traced."""
        group = self.bench["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bdir / "metrics" / f"{metric}.py")
