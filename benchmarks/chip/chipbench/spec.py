"""Find a cell's configuration, traffic mix and metrics by name.

`Bench(root)` reads `BENCHMARK.json` at the checkout's root; every other
file is looked up under the benchmark's own directory (`bench_dir`), so a
later cell, mix, configuration or metric is added by adding files and
entries, never by editing code.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A name that BENCHMARK.json or a file under the benchmark lacks."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    def __init__(self, root: pathlib.Path,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir or BENCH_DIR)
        self.spec = load_json(self.root / "BENCHMARK.json")
        self._readers: Dict[str, Callable] = {}

    # ------------------------------------------------------------- lookups
    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = load_json(self.root / c["file"])
                cfg.setdefault("name", name)
                return cfg
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.bench_dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no traffic mix file {path}")
        mix = load_json(path)
        mix.setdefault("name", name)
        return mix

    def metrics(self, cell: str, *, trace: bool) -> List[dict]:
        """The metrics a run of `cell` reports: the end-to-end ones
        without `--trace`, the per-layer ones with it."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        if metric not in self._readers:
            path = self.bench_dir / "metrics" / f"{metric}.py"
            if not path.is_file():
                raise SpecError(f"no reader {path} for metric {metric!r}")
            modname = "chipbench_metric_" + "".join(
                c if c.isalnum() else "_" for c in metric)
            spec = importlib.util.spec_from_file_location(modname, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._readers[metric] = mod.read
        return self._readers[metric]
