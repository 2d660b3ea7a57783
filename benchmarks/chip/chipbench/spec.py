"""Find a cell's configuration, traffic mix, reference and metrics by name.

`Bench(root)` reads `BENCHMARK.json` at the checkout's root; every other
file is looked up under the benchmark's own directory (`bench_dir`), so a
later cell, mix, configuration, architecture or metric is added by adding
files and entries, never by editing code.

A configuration names its reference (`"reference": "<name>"`), the file
references/<name>.py that holds everything particular to its
architecture. The harness reaches it only through the configuration:

  Shape              `Shape.of(config["model"])`, the published sizes
  logits(params, shape, kv_dtype, tokens, rows)
                     the plain float32 model's logits (chipbench/check.py)
  PROGRAM_FIELDS     {program ArchConfig field: model key} that must agree
  derived_fields(arch)
                     {model key: the program's value} of what the program
                     states only through other fields (run.program_arch)
  WEIGHTS            {leaf name: weights.Rule}, how each leaf is drawn
  projections(shape) (K, N) of each weight matmul of one layer
  matmul_params(shape)
                     parameters one token multiplies, the active ones of a
                     mixture of experts (chipbench/costs.py)
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType
from typing import Callable, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class SpecError(ValueError):
    """A name that BENCHMARK.json or a file under the benchmark lacks."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """The Python file at `path` as a module, loaded once a process for
    each file (named by its path, so that files of one name in two
    benchmark directories stay apart)."""
    path = path.resolve()
    modname = "chipbench_file_" + hashlib.sha256(
        str(path).encode()).hexdigest()[:16]
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod       # dataclasses look it up
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return sys.modules[modname]


class Bench:
    def __init__(self, root: pathlib.Path,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir or BENCH_DIR)
        self.spec = load_json(self.root / "BENCHMARK.json")

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
                if "reference" not in cfg:
                    raise SpecError(f"config {name!r} names no reference")
                self.reference(cfg["reference"])
                return cfg
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def reference(self, name: str) -> ModuleType:
        """The module references/<name>.py."""
        if not isinstance(name, str) or not NAME.fullmatch(name):
            raise SpecError(f"reference {name!r} is not a name")
        path = self.bench_dir / "references" / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"no reference {path} for {name!r}")
        return load_module(path)

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
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"no reader {path} for metric {metric!r}")
        return load_module(path).read
