"""Shared set-up of the chip benchmark's CPU tests: the harness on the
import path, and a temporary tree that holds a tiny cell beside the real
ones, so a whole run can be driven on the CPU at a size a test can hold."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
DATA = BENCH / "tests" / "data"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run():
    """benchmarks/chip/run.py as a module of its own name."""
    if "chipbench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("chipbench_run",
                                                      BENCH / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chipbench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chipbench_run"]


def tiny_tree(tmp: pathlib.Path, *, config: str = "tiny-dense.json",
              limits=None) -> pathlib.Path:
    """A checkout-like tree: BENCHMARK.json plus a copy of the benchmark's
    directory, with the tiny configuration and mix added as files and the
    cell `tiny-chat` added as an entry. Returns the tree's root."""
    root = tmp / "tree"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = json.loads((DATA / config).read_text())
    if limits is not None:
        cfg["check"]["limits"] = limits
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for mix in ("tiny-chat.json", "tiny-batch.json", "tiny-sessions.json"):
        shutil.copy(DATA / mix, bench_dir / "traffic")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny", "source": cfg["source"],
        "file": "benchmarks/chip/configs/tiny.json", "reduced": [],
        "why": "CPU tests"})
    for cell, like in (("tiny-chat", "smollm-chat"),
                       ("tiny-batch", "chatglm3-int8-batch"),
                       ("tiny-sessions", None)):
        spec["workloads"].append({
            "name": cell, "config": "tiny", "traffic": cell, "chips": 1,
            "why": "CPU tests"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_tiny(root: pathlib.Path, capsys, *extra: str, seed: int = 7,
             seconds: float = 1.5, cell: str = "tiny-chat"):
    """Drive a whole run of a tiny cell on the CPU; returns (exit code,
    the result line as a dict or None, standard output)."""
    run = load_run()
    code = run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", str(seconds), *extra], root=root,
                    bench_dir=root / "benchmarks" / "chip", allow_cpu=True)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return code, (json.loads(lines[-1]) if lines else None), out
