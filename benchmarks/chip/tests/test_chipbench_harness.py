"""Whole runs of the harness on the CPU at a tiny size: it finds a new
configuration, mix, cell and metric by name alone; it refuses to measure
without a chip; and the comparison that decides `correct` fails the
lower-precision control and a timed path broken underneath."""

import json

import numpy as np
import pytest

from chipbench_testlib import REPO, load_run, run_tiny, tiny_tree
from chipbench.spec import Bench


def test_no_chip_no_result(capsys):
    """Without a TPU the command exits non-zero and prints no result."""
    run = load_run()
    code = run.main(["--workload", "smollm-chat", "--seed", "1",
                     "--seconds", "1"])
    out = capsys.readouterr().out
    assert code != 0
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_new_files_are_found_by_name(tmp_path, capsys):
    """A configuration, a mix, a cell and a per-layer metric added as files
    and entries, with no edit to the harness, run and report."""
    root = tiny_tree(tmp_path)
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "metrics" / "queue_tokens.tiny.py").write_text(
        '"""Prompt tokens admitted in the window."""\n\n\n'
        "def read(run):\n"
        "    return run.window.delta('prefill_tokens')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "queue_tokens.tiny", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p95_ms", "workloads": ["tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Bench(root, bench_dir)
    assert bench.cell("tiny-chat")["traffic"] == "tiny-chat"
    assert bench.config("tiny")["model"]["d_model"] == 64
    assert bench.traffic("tiny-chat")["loop"] == "open"
    names = [m["name"] for m in bench.metrics("tiny-chat", trace=True)]
    assert "queue_tokens.tiny" in names
    assert "queue_tokens.tiny" not in [
        m["name"] for m in bench.metrics("smollm-chat", trace=True)]

    code, res, _ = run_tiny(root, capsys, seed=2**40)
    assert code == 0 and res is not None
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert res["attempted"] > 5 and res["failed"] == 0
    assert res["correct"] is True
    assert res["device"]["platform"] == "cpu"


def test_closed_loop_opens_on_a_full_batch(tmp_path, capsys):
    """A closed loop of more clients than slots: the window opens once the
    batch has filled, and every slot decodes in it."""
    root = tiny_tree(tmp_path)
    code, res, out = run_tiny(root, capsys, cell="tiny-batch", seconds=1.0)
    assert code == 0 and res is not None
    assert set(res["metrics"]) == {"setup_s", "output_tokens_per_s"}
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["correct"] is True
    counters = [ln for ln in out.splitlines()
                if ln.startswith("window counters:")][0]
    steps = float(counters.split("decode_steps ")[1].split(",")[0])
    occupancy = float(counters.split("occupancy_sum ")[1].split(",")[0])
    assert steps > 0 and occupancy / steps > 0.5


def test_sessions_hit_the_prefix_cache(tmp_path, capsys):
    """Sessions re-send their history behind shared system prompts: the
    window's admissions hit cached pages, and the check covers a request
    served from them."""
    root = tiny_tree(tmp_path)
    code, res, out = run_tiny(root, capsys, cell="tiny-sessions",
                              seconds=2.0)
    assert code == 0 and res is not None and res["correct"] is True
    counters = [ln for ln in out.splitlines()
                if ln.startswith("window counters:")][0]
    hits = float(counters.split("prefix_hit_tokens ")[1].split(",")[0])
    assert hits > 0


def test_same_seed_same_work(tmp_path, capsys):
    root = tiny_tree(tmp_path)
    a = run_tiny(root, capsys, seed=2**33 + 5)[1]
    b = run_tiny(root, capsys, seed=2**33 + 5)[1]
    assert a["attempted"] == b["attempted"]
    assert a["check"]["max_gap"] == b["check"]["max_gap"]


def test_int8_control_is_not_correct(tmp_path, capsys):
    """The control: the program's own int8 weight and KV path in place of
    bf16, through a whole run, reads past the limit that the program's
    run meets. At the 2-layer tiny size the two cannot be told apart, so
    this runs 8 layers of width 256 (on five seeds on the CPU the program
    read a mean gap of 0.00008-0.00027, the control 0.0006-0.0018)."""
    root = tiny_tree(tmp_path, config="tiny-wide.json")
    code, res, _ = run_tiny(root, capsys, seed=10, seconds=3.0)
    assert code == 0 and res["correct"] is True
    code, ctl, _ = run_tiny(root, capsys, "--control", seed=10, seconds=3.0)
    assert code == 0 and ctl["correct"] is False
    assert ctl["check"]["mean_gap"]["value"] > \
        3 * res["check"]["mean_gap"]["value"]


def _break_decode(monkeypatch, fault):
    """Wrap the engine's decode program so that its tokens come out wrong
    where they are produced."""
    run = load_run()
    build = run.build_engine

    def broken(*a, **kw):
        eng = build(*a, **kw)
        dec = eng._decode_jit

        def wrapped(*args):
            toks, cache = dec(*args)
            return fault(toks, eng.cfg.vocab_size), cache

        wrapped.lower = dec.lower          # the set-up's kernel check
        eng._decode_jit = wrapped
        return eng

    monkeypatch.setattr(run, "build_engine", broken)


@pytest.mark.parametrize("fault", [
    pytest.param(lambda t, v: (t + 1) % v, id="token-altered"),
    pytest.param(lambda t, v: t.at[::2].set(0), id="half-the-batch-left-out"),
])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                          fault):
    root = tiny_tree(tmp_path)
    _break_decode(monkeypatch, fault)
    code, res, _ = run_tiny(root, capsys)
    assert code == 0 and res is not None
    assert res["correct"] is False
    assert res["check"]["max_gap"]["value"] > \
        res["check"]["max_gap"]["limit"]


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = Bench(REPO)
    for c in spec["configs"]:
        cfg = bench.config(c["name"])
        assert cfg["source"] == c["source"]
        for key, pub in cfg["model_keys"].items():
            if pub is not None:
                assert cfg["config"][pub] == cfg["model"][key], key
    for w in spec["workloads"]:
        bench.traffic(w["traffic"])
        bench.config(w["config"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))
    assert np.all([len(w["why"]) <= 200 for w in spec["workloads"]])
