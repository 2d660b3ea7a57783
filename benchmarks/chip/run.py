#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration
(configs/<name>.json), a traffic mix (traffic/<name>.json) and its chips.
One process, which starts no other: set-up makes the weights on the device
from the seed, builds the program's serving engine and warms the cell's
own programs (from the compile cache at `bench-out/jax-cache` after the
first run); then the traffic runs for `--seconds` in the measured window;
then the engine is freed and a sample of what it served is compared with
the plain float32 reference (chipbench/check.py).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer ones), `device`, with `--trace 1` a
`breakdown`, and last the numbers compared with their limits (`check`).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

Options for measuring the benchmark itself (not used by its runs):
  --control       a whole run with the configuration's lower-precision
                  control in the program's place; it must read false.
                  `check.control` in the configuration says which:
                  kind "program" switches on the program's own path
                  (`wdtype`, `kv`); kind "weights" serves the weights
                  drawn on a coarser grid (`weights`, e.g. "int4" for an
                  int8 deployment) while the reference reads the weights
                  as the configuration states them
  --sweep r1,..   open-loop rates in turn, each on a fresh engine:
                  backlog and tails, to find the knee
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import check, serving, trace, traffic, weights  # noqa: E402
from chipbench.reference import Shape  # noqa: E402
from chipbench.spec import Bench, load_json  # noqa: E402
from chipbench.stats import percentile  # noqa: E402

CACHE_DIR = ROOT / "bench-out" / "jax-cache"
TRACE_DIR = ROOT / "bench-out" / "trace"
TRACE_S = 4.0            # traced part of the window, from its start
KERNELS = ("decode_attention", "flash_attention_paged", "int8_matmul")
# the published numbers each program field must equal
PROGRAM_FIELDS = {"n_layers": "n_layers", "d_model": "d_model",
                  "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
                  "head_dim": "head_dim", "d_ff": "d_ff",
                  "vocab_size": "vocab", "tie_embeddings": "tie_embeddings",
                  "qkv_bias": "qkv_bias", "rope_theta": "rope_theta"}


class BenchError(RuntimeError):
    """The run cannot be measured as the cell states it."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ set-up
def enable_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    the environment says; every program cached however fast it compiled."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_facts(chips: int, allow_cpu: bool) -> Dict[str, object]:
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def program_arch(cfg: Dict):
    """The program's ArchConfig for this configuration, checked against
    the published numbers it must serve."""
    from repro.configs import get_config
    prog = cfg["program"]
    arch = dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("overrides", {}))
    model = cfg["model"]
    wrong = {f: (getattr(arch, f), model[k]) for f, k in
             PROGRAM_FIELDS.items() if getattr(arch, f) != model[k]}
    rope = int(arch.head_dim * arch.rope_fraction)
    if rope != model["rope_dims"]:
        wrong["rope_dims"] = (rope, model["rope_dims"])
    if wrong:
        raise BenchError(f"program config {prog['arch']!r} departs from "
                         f"the published one: {wrong}")
    return arch


def check_published(cfg: Dict) -> None:
    """Each model number equals the published config's key it names."""
    pub = cfg["config"]
    for name, key in cfg.get("model_keys", {}).items():
        if key is not None and pub[key] != cfg["model"][name]:
            raise BenchError(f"model.{name}={cfg['model'][name]} but the "
                             f"published {key}={pub[key]}")


def build_engine(model, params, serve: Dict, wdtype: Optional[str],
                 kv_dtype: str):
    """The program's engine as the configuration states it; `wdtype` int8
    switches on its own int8 weight path (a control). Weights made in int8
    come as the program's quantized leaves, with `wdtype` None."""
    from repro.serve.engine import ServeEngine
    return ServeEngine(model, params=params, n_slots=serve["n_slots"],
                       max_len=serve["max_len"],
                       page_size=serve["page_size"],
                       n_pages=serve["n_pages"],
                       chunk_pages=serve["chunk_pages"], wdtype=wdtype,
                       kv_dtype=kv_dtype)


def warm(engine, rng: np.random.Generator, vocab: int) -> None:
    """Run every program the window will: chunked prefill over several
    chunks, decode, a prefix hit whose tail page is copied on write, and
    slot release."""
    ps = engine.page_size
    base = rng.integers(0, vocab, 3 * ps + 5, dtype=np.int32)
    engine.submit(base, max_new_tokens=3)
    engine.run_to_completion()
    engine.submit(base[:2 * ps], max_new_tokens=2)
    engine.run_to_completion()


def engine_kernels(engine) -> Dict[str, List[str]]:
    """Pallas kernels in the engine's compiled decode and chunk programs,
    lowered at the shapes its loop feeds them."""
    n = engine.n_slots
    progs = {
        "decode": engine._decode_jit.lower(
            engine.params, {"tokens": jnp.zeros((n, 1), jnp.int32)},
            engine._cache, jnp.zeros((n,), bool)),
        "chunk": engine._chunk_jit.lower(
            engine.params,
            {"tokens": jnp.zeros((1, engine.chunk_tokens), jnp.int32),
             "start": jnp.zeros((1,), jnp.int32),
             "length": jnp.zeros((1,), jnp.int32),
             "page_row": jnp.zeros((engine.pages_per_seq,), jnp.int32)},
            engine._cache)}
    found = {}
    for name, lowered in progs.items():
        text = lowered.compile().as_text()
        found[name] = sorted({k for line in text.splitlines()
                              if "tpu_custom_call" in line
                              for k in KERNELS if k in line})
    return found


def memory_peak() -> Optional[int]:
    peaks = []
    for d in jax.devices():
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Events:
    """JAX's monitoring events in this process, by name: backend compiles,
    and also programs loaded from the persistent cache, which a count of
    compiles alone would miss."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counts: Dict[str, int] = collections.Counter()
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *args, **kw) -> None:
        self.counts[event] += 1

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before.get(k, 0) for k, v in self.counts.items()
                if v != before.get(k, 0)}


# ----------------------------------------------------------------- the run
@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read (metrics/<name>.py)."""
    cell: Dict
    config: Dict
    mix: Dict
    chips: int
    device: Dict
    setup_s: float
    window: serving.Window
    records: List[serving.Record]
    measured: List[serving.Record]
    trace: Optional[trace.Reduced]


def measured_records(records, window, closed: bool):
    """Open loop: the requests due in the window. Closed loop: those that
    emitted a token in it."""
    if closed:
        return [r for r in records if any(
            window.t0 <= t < window.t1 for t in r.stamps)]
    return [r for r in records if window.t0 <= r.item.due < window.t1]


def outcome(records, measured, closed: bool):
    """(attempted, failed). Open loop: the requests due in the window, and
    those that never finished or were refused. Closed loop: the requests
    that worked in the window plus the refused ones; work still in flight
    when the window closes is not a failure."""
    if closed:
        failed = sum(1 for r in records if r.error)
        return len(measured) + failed, failed
    failed = sum(1 for r in measured if r.req is None or not r.req.done)
    return len(measured), failed


def serve_once(cell: Dict, cfg: Dict, mix: Dict, model, seed: int,
               seconds: float, *, traced: bool, control: bool,
               events: Events, setup_clock: float):
    """Weights, engine, warm-up, window. Returns the engine's results and
    the weights as the configuration states them (for the reference)."""
    prec = cfg["precision"]
    if prec["weights"] not in ("bf16", "int8"):
        raise BenchError(f"weights are made in bf16 or int8, not "
                         f"{prec['weights']}")
    drawn, wd, kv = prec["weights"], None, prec["kv"]
    if control:
        ctl = cfg["check"]["control"]
        if ctl["kind"] == "program":
            wd, kv = ctl["wdtype"], ctl["kv"]
        elif ctl["kind"] == "weights":
            drawn = ctl["weights"]
        else:
            raise BenchError(f"unknown control kind {ctl['kind']!r}")
    params = weights.make_params(model, seed, drawn)
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    engine = build_engine(model, params, cfg["serve"], wd, kv)
    st = jax.devices()[0].memory_stats() or {}
    log(f"bytes in use: weights {in_use}, with the engine's pool "
        f"{st.get('bytes_in_use')} (nominal pool {engine.kv_cache_bytes()})")
    warm(engine, traffic.seed_rng(seed, 99), cfg["model"]["vocab"])
    found = engine_kernels(engine)
    for prog, need in cfg["kernels"].items():
        need = list(need) + (["int8_matmul"] if wd == "int8" else [])
        missing = [k for k in need if k not in found[prog]]
        if missing:
            raise BenchError(f"the {prog} program lacks kernels {missing}; "
                             f"it holds {found[prog]}")
    log(f"kernels in compiled programs: {found}")
    setup_s = time.perf_counter() - setup_clock
    ev0 = dict(events.counts)
    trace_dir = TRACE_DIR / cell["name"]
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
    horizon = mix.get("lead_s", 0.0) + 2 * seconds + 30.0
    source = traffic.Source(mix, seed, vocab=cfg["model"]["vocab"],
                            horizon_s=horizon)
    records, window, late, stalls = serving.drive(
        engine, source, seconds=seconds, lead_s=mix.get("lead_s", 0.0),
        opens=mix["window_opens"], trace_s=TRACE_S if traced else 0.0,
        trace_start=lambda: jax.profiler.start_trace(str(trace_dir)),
        trace_stop=jax.profiler.stop_trace)
    served_events = events.since(ev0)
    peak = memory_peak()
    del engine
    gc.collect()
    if drawn != prec["weights"]:
        del params
        gc.collect()
        params = weights.make_params(model, seed, prec["weights"])
    return dict(params=params, records=records, window=window,
                lateness=late, stalls=stalls, events=served_events,
                setup_events=ev0,
                peak=peak, setup_s=setup_s,
                trace_dir=trace_dir if traced else None)


def report_lines(run: RunData, out: Dict) -> None:
    """Earlier lines: what the metrics leave out."""
    late = out["lateness"]
    log(f"generator lateness s: p50 {percentile(late, 50)} p99 "
        f"{percentile(late, 99)} max {max(late) if late else None} over "
        f"{len(late)} submissions")
    ev = out["events"]
    log(f"JAX events in set-up: {json.dumps(out['setup_events'])}")
    log(f"compiles inside the window: {ev.get(Events.COMPILE, 0)}; JAX "
        f"events while serving: {json.dumps(ev)}")
    st = out["stalls"]
    log(f"steps over {serving.SLOW_STEP_S} s (start, s, CPU s, counters "
        f"moved): "
        f"{st.steps}; collections over {serving.SLOW_GC_S} s (start, s, "
        f"generation): {st.gcs}; window [{run.window.t0}, {run.window.t1})")
    m = run.measured
    ttft = [r.stamps[0] - r.item.due for r in m if r.stamps]
    gaps = [b - a for r in m for a, b in zip(r.stamps, r.stamps[1:])]
    toks = sum(1 for r in run.records for t in r.stamps
               if run.window.t0 <= t < run.window.t1)
    log(f"window {run.window.seconds} s: {len(m)} requests, {toks} tokens "
        f"({toks / run.window.seconds} tokens/s); ttft_s p50 "
        f"{percentile(ttft, 50)} p95 {percentile(ttft, 95)} p99 "
        f"{percentile(ttft, 99)}; itl_s over {len(gaps)} gaps p50 "
        f"{percentile(gaps, 50)} p95 {percentile(gaps, 95)} p99 "
        f"{percentile(gaps, 99)}")
    w = run.window
    log("window counters: " + ", ".join(
        f"{k} {w.delta(k)}" for k in serving.COUNTERS))


def one_run(args, bench: Bench, cell, cfg, mix, model, dev,
            events: Events) -> int:
    out = serve_once(cell, cfg, mix, model, args.seed, args.seconds,
                     traced=bool(args.trace), control=args.control,
                     events=events, setup_clock=T_START)
    closed = mix["loop"] == "closed"
    measured = measured_records(out["records"], out["window"], closed)
    red = None
    if out["trace_dir"] is not None:
        red = trace.reduce(trace.load(str(out["trace_dir"])), KERNELS)
    run = RunData(cell=cell, config=cfg, mix=mix, chips=cell["chips"],
                  device=dev, setup_s=out["setup_s"], window=out["window"],
                  records=out["records"], measured=measured, trace=red)
    report_lines(run, out)
    t_ref = time.perf_counter()
    picked = check.sample(measured, traffic.seed_rng(args.seed, 11),
                          cfg["check"]["sample_tokens"])
    read = check.readings(check.gaps(out["params"], Shape.of(cfg["model"]),
                                     cfg["precision"]["kv"], picked))
    log(f"reference check{' of the control' if args.control else ''}: "
        f"{len(picked)} requests, {time.perf_counter() - t_ref} s; "
        f"readings {json.dumps(read)}")
    attempted, failed = outcome(out["records"], measured, closed)
    limits = cfg["check"]["limits"]
    correct = failed == 0 and check.judge(read, limits)
    metrics = {}
    for m in bench.metrics(cell["name"], trace=bool(args.trace)):
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=out["peak"])
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in red.device_ops],
            "idle_gaps": [list(x) for x in red.idle_gaps]}
    compared = {k: {"value": read.get(k), "limit": v}
                for k, v in limits.items()}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    result["check"] = compared
    log(json.dumps(result))
    for k, v in compared.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    return 0


def sweep(args, cell, cfg, mix, model, events: Events) -> int:
    """Open-loop rates in turn: where does the backlog start to grow?"""
    for i, rate in enumerate(args.sweep):
        m = dict(mix, rate_per_s=rate)
        out = serve_once(cell, cfg, m, model, args.seed + i, args.seconds,
                         traced=False, control=False, events=events,
                         setup_clock=time.perf_counter())
        w = out["window"]
        meas = measured_records(out["records"], w, False)
        ttft = [r.stamps[0] - r.item.due for r in meas if r.stamps]
        thirds = [[r.stamps[0] - r.item.due for r in meas if r.stamps and
                   w.t0 + k * w.seconds / 3 <= r.item.due
                   < w.t0 + (k + 1) * w.seconds / 3] for k in range(3)]
        tpot = [(r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)
                for r in meas if len(r.stamps) > 1]
        toks = sum(1 for r in out["records"] for t in r.stamps
                   if w.t0 <= t < w.t1)
        unfinished = sum(1 for r in meas if not r.req.done)
        log(json.dumps({
            "sweep_rate": rate, "requests": len(meas),
            "unfinished": unfinished, "tokens_per_s": toks / w.seconds,
            "ttft_p50": percentile(ttft, 50), "ttft_p95": percentile(ttft, 95),
            "ttft_p50_by_third": [percentile(t, 50) for t in thirds],
            "tpot_p95": percentile(tpot, 95),
            "occupancy": w.delta("occupancy_sum") / max(
                1.0, w.delta("decode_steps")),
            "prefix_hit_tokens": w.delta("prefix_hit_tokens"),
            "prefill_tokens": w.delta("prefill_tokens"),
            "slow_steps": out["stalls"].steps, "slow_gcs": out["stalls"].gcs,
            "setup_s": out["setup_s"], "peak": out["peak"]}))
        del out
        gc.collect()
    return 0


def main(argv: Optional[Sequence[str]] = None, *, root=None,
         bench_dir=None, allow_cpu: bool = False) -> int:
    """`root`/`bench_dir` point the harness at another tree, and
    `allow_cpu` skips the look for a chip: for the CPU tests only."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep",
                    type=lambda s: [float(x) for x in s.split(",")])
    args = ap.parse_args(argv)
    bench = Bench(root or ROOT, bench_dir)
    if args.seconds is None:
        args.seconds = float(bench.spec["run_seconds"])
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    try:
        dev = device_facts(cell["chips"], allow_cpu)
        if not allow_cpu:
            enable_cache()
        peaks = load_json(bench.bench_dir / "peaks.json")["devices"]
        if dev["kind"] not in peaks and not allow_cpu:
            raise BenchError(f"no peaks for device kind {dev['kind']!r}")
        events = Events()
        check_published(cfg)
        from repro.models import ExecOptions, build_model
        model = build_model(program_arch(cfg),
                            ExecOptions(attn_impl="reference"))
        if args.sweep:
            return sweep(args, cell, cfg, mix, model, events)
        return one_run(args, bench, cell, cfg, mix, model, dev, events)
    except BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
