"""Benchmark harness — one section per paper table/figure + framework benches.

Run: PYTHONPATH=src python -m benchmarks.run [--only table3,fig2,...] [--json]
Prints `name,value,unit` rows per section (CSV-ish, grep-friendly).

`--json` additionally writes one BENCH_<section>.json per executed section
(serve tokens/s, prefill compile counts, sweep wall-times, ...) so the perf
trajectory is tracked across PRs — each file is a flat {metric: number} dict.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def env_fingerprint() -> float:
    """Stable numeric id of the benchmarking machine class (kept numeric so
    BENCH_*.json stays a flat {metric: number} dict). benchmarks.compare
    enforces absolute (machine-dependent) gates only when the baseline and
    the fresh run share this id; same-run ratio metrics gate regardless."""
    tag = f"{platform.machine()}|{platform.processor()}|{os.cpu_count()}"
    return float(zlib.crc32(tag.encode()) & 0xFFFFFF)


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


# --------------------------------------------------------------------- table1
def bench_table1():
    """Table I parameters + the derived per-scenario link cost of one
    MobileNetV2 activation transfer (0.57 MB)."""
    from repro.core import ucie as ucie_mod
    from repro.core.scenarios import SCENARIOS, SCENARIO_ORDER
    print("\n## Table I — scenario parameters + derived link cost")
    metrics = {}
    for name in SCENARIO_ORDER:
        s = SCENARIOS[name]
        if s.is_monolithic:
            print(f"table1,{name},latency_us=0,bw=inf,transfer_ms=0")
            continue
        cfg = ucie_mod.UCIeConfig(
            bandwidth_gbps=s.link_bandwidth_gbps, latency_us=s.link_latency_us,
            streaming=s.prefetch_overlap, compression_ratio=s.compression_ratio)
        t_us, e_mj, wire = ucie_mod.transfer(jnp.float32(0.57e6), cfg)
        metrics[f"{name}_transfer_ms"] = float(t_us) / 1e3
        print(f"table1,{name},latency_us={s.link_latency_us},"
              f"bw_gbps={s.link_bandwidth_gbps},transfer_ms="
              f"{float(t_us)/1e3:.3f},wire_MB={float(wire)/1e6:.2f},"
              f"energy_mJ={float(e_mj):.3f}")
    return metrics


# --------------------------------------------------------------------- table3
def bench_table3():
    from repro.core import perf_model as pm
    from repro.core.scenarios import SCENARIOS, SCENARIO_ORDER
    from repro.core.workloads import WORKLOADS
    mnv2 = WORKLOADS["mobilenetv2"]
    print("\n## Table III — MobileNetV2 INT8 batch=1 (paper → reproduced)")
    paper = {"monolithic": (4.7, 213, 1284), "basic_chiplet": (4.8, 208, 1026),
             "ai_optimized": (4.1, 244, 860), "poor_integration": (6.2, 163, 1776)}
    us, _ = _timeit(lambda: pm.predict(SCENARIOS["ai_optimized"], mnv2, 1))
    metrics = {"model_eval_us": us}
    for name in SCENARIO_ORDER:
        r = pm.predict(SCENARIOS[name], mnv2, 1)
        p = paper[name]
        metrics[f"{name}_latency_ms"] = float(r.latency_ms)
        metrics[f"{name}_throughput_ips"] = float(r.throughput_ips)
        print(f"table3,{name},lat_ms={float(r.latency_ms):.2f}(paper {p[0]}),"
              f"thpt={float(r.throughput_ips):.0f}(paper {p[1]}),"
              f"power_mW={float(r.power_mw):.0f}(paper {p[2]}),"
              f"tops_w={float(r.tops_per_w):.3f}")
    b = pm.predict(SCENARIOS["basic_chiplet"], mnv2, 1)
    a = pm.predict(SCENARIOS["ai_optimized"], mnv2, 1)
    print(f"table3,improvements,lat=-{100*(1-float(a.latency_ms)/float(b.latency_ms)):.1f}%"
          f"(paper -14.7%),thpt=+{100*(float(a.throughput_ips)/float(b.throughput_ips)-1):.1f}%"
          f"(paper +17.3%),power=-{100*(1-float(a.power_mw)/float(b.power_mw)):.1f}%"
          f"(paper -16.2%),topsw=+{100*(float(a.tops_per_w)/float(b.tops_per_w)-1):.1f}%"
          f"(paper +40.1%)")
    print(f"table3,model_eval_us,{us:.1f}")
    return metrics


# ----------------------------------------------------------------------- fig2
def bench_fig2():
    from repro.core import perf_model as pm
    from repro.core.scenarios import SCENARIOS, SCENARIO_ORDER
    from repro.core.workloads import WORKLOADS, WORKLOAD_ORDER
    mnv2 = WORKLOADS["mobilenetv2"]
    print("\n## Fig 2(b) — throughput scaling, batch 1→32")
    batches = [1, 2, 4, 8, 16, 32]
    grid = pm.predict_grid([SCENARIOS[s] for s in SCENARIO_ORDER], [mnv2],
                           batches)
    for i, s in enumerate(SCENARIO_ORDER):
        vals = ",".join(f"{float(v):.0f}" for v in grid.throughput_ips[i, 0])
        print(f"fig2b,{s},ips@[1-32]=[{vals}]")
    print("\n## Fig 2(d) — per-workload latency (ms)")
    for w in WORKLOAD_ORDER:
        row = {s: float(pm.predict(SCENARIOS[s], WORKLOADS[w], 1).latency_ms)
               for s in SCENARIO_ORDER}
        print(f"fig2d,{w}," + ",".join(f"{k}={v:.2f}" for k, v in row.items()))
    print("\n## Fig 2(e) — AI-optimized vs basic chiplet (%)")
    for w in WORKLOAD_ORDER:
        b = pm.predict(SCENARIOS["basic_chiplet"], WORKLOADS[w], 1)
        a = pm.predict(SCENARIOS["ai_optimized"], WORKLOADS[w], 1)
        print(f"fig2e,{w},lat=-{100*(1-float(a.latency_ms)/float(b.latency_ms)):.1f}%,"
              f"thpt=+{100*(float(a.throughput_ips)/float(b.throughput_ips)-1):.1f}%,"
              f"power=-{100*(1-float(a.power_mw)/float(b.power_mw)):.1f}%,"
              f"topsw=+{100*(float(a.tops_per_w)/float(b.tops_per_w)-1):.1f}%")
    print("\n## Fig 2(f) — sub-5 ms real-time capability (AI-optimized)")
    for w in WORKLOAD_ORDER:
        r = pm.predict(SCENARIOS["ai_optimized"], WORKLOADS[w], 1)
        print(f"fig2f,{w},lat_ms={float(r.latency_ms):.2f},"
              f"meets_5ms={bool(r.realtime_ok)}")


# ------------------------------------------------------------------------ soc
def bench_soc():
    """Time-stepped simulator: per-scenario detail + the vmapped full sweep
    (all scenarios × an arrival-rate grid in ONE jitted call)."""
    from repro.core import build_soc, simulate, simulate_batch
    from repro.core.scenarios import SCENARIOS, SCENARIO_ORDER
    from repro.core.workloads import WORKLOADS
    mnv2 = WORKLOADS["mobilenetv2"]
    metrics = {}
    print("\n## Time-stepped SoC simulator (I1+I2+I3+I4 composed)")
    for s in ("basic_chiplet", "ai_optimized"):
        soc = build_soc(SCENARIOS[s])
        t0 = time.perf_counter()
        out = simulate(soc, mnv2, arrival_rate_ips=200.0, duration_ms=200.0)
        jax.block_until_ready(out["throughput_ips"])
        dt = time.perf_counter() - t0
        metrics[f"{s}_throughput_ips"] = float(out["throughput_ips"])
        print(f"soc,{s},thpt={float(out['throughput_ips']):.0f}ips,"
              f"E/inf={float(out['energy_mj_per_inf']):.2f}mJ,"
              f"peakT={float(out['peak_temp_c']):.1f}C,"
              f"migrations={int(out['migrations'])},sim_wall_s={dt:.2f}")

    # --- vmapped sweep: scenarios × arrival rates, one compiled program -----
    socs = [build_soc(SCENARIOS[s]) for s in SCENARIO_ORDER]
    rates = jnp.asarray([25., 50., 100., 150., 200., 300., 500., 1000.])
    t0 = time.perf_counter()
    grid = simulate_batch(socs, mnv2, rates, duration_ms=200.0)
    jax.block_until_ready(grid["throughput_ips"])
    compile_s = time.perf_counter() - t0
    # best-of-5: a single ~40 ms sweep sits on the scheduler-noise floor,
    # which would flake the CI regression gate
    sweep_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        grid = simulate_batch(socs, mnv2, rates, duration_ms=200.0)
        jax.block_until_ready(grid["throughput_ips"])
        sweep_s = min(sweep_s, time.perf_counter() - t0)
    metrics["sweep_points"] = int(len(socs) * rates.shape[0])
    metrics["sweep_wall_s"] = sweep_s
    metrics["sweep_compile_s"] = compile_s
    print(f"soc,sweep,{len(socs)}x{rates.shape[0]}_points,"
          f"wall_s={sweep_s:.2f}(first={compile_s:.2f}),one_jitted_call")
    for i, s in enumerate(SCENARIO_ORDER):
        # max sustainable load still meeting the paper's 5 ms deadline
        lat = np.asarray(grid["latency_ms"][i])
        ok = np.where(lat <= 5.0)[0]
        knee = float(rates[ok[-1]]) if ok.size else 0.0
        metrics[f"{s}_max_rate_5ms"] = knee
        print(f"soc,sweep,{s},max_rate_sub5ms={knee:.0f}ips")
    return metrics


# ------------------------------------------------------------------------ dse
def bench_dse():
    """Beyond-paper: vmapped design-space sweep + gradient co-design."""
    from repro.core import perf_model as pm
    from repro.core.scenarios import AI_OPTIMIZED
    from repro.core.workloads import MOBILENET_V2
    print("\n## Design-space exploration (vmapped sweep; gradient co-design)")
    base = AI_OPTIMIZED.as_vector()
    n = 4096
    key = jax.random.key(0)
    cand = base[None, :] * jax.random.uniform(key, (n, base.shape[0]),
                                              minval=0.8, maxval=1.2)
    wv = MOBILENET_V2.as_vector()

    @jax.jit
    def sweep(c):
        return jax.vmap(lambda v: pm.predict_vec(v, wv, jnp.float32(1.0))
                        .tops_per_w)(c)

    us, eff = _timeit(sweep, cand)
    best = int(jnp.argmax(eff))
    metrics = {"sweep_candidates": n, "sweep_wall_us": us,
               "best_tops_w": float(eff[best])}
    print(f"dse,sweep,{n}_candidates,{us:.0f}us_total,"
          f"{us/n*1e3:.1f}ns_per_design,best_tops_w={float(eff[best]):.3f}")

    # projected gradient ascent within ±25 % engineering margins of the
    # published design point (the feasible interposer/process box)
    lo, hi = base * 0.75, base * 1.25

    @jax.jit
    def step(v):
        g = jax.grad(lambda v: -pm.predict_vec(v, wv, jnp.float32(1.0))
                     .tops_per_w)(v)
        # co-designable knobs: link latency/bw, power envelope, efficiency,
        # compression ratio — the interposer/process design space
        mask = jnp.zeros_like(v).at[jnp.asarray([0, 1, 2, 4, 10])].set(1.0)
        v = v - 0.05 * g * mask * jnp.abs(v)
        return jnp.clip(v, jnp.minimum(lo, hi), jnp.maximum(lo, hi))

    v = base
    e0 = float(pm.predict_vec(v, wv, jnp.float32(1.0)).tops_per_w)
    for _ in range(200):
        v = step(v)
    e1 = float(pm.predict_vec(v, wv, jnp.float32(1.0)).tops_per_w)
    metrics["codesign_tops_w"] = e1
    print(f"dse,grad_codesign,tops_w {e0:.4f}->{e1:.4f} within +/-25% design"
          f" box (lat/bw/power/eff/compression tuned by gradient)")
    return metrics


# ---------------------------------------------------------------------- serve
def bench_serve():
    """Serving fast path: tokens/s and prefill compile count with pow2 prompt
    bucketing on vs off.

    NOTE: `no_bucketing` is not the seed engine — it keeps the donated
    decode, jitted paste, cache-only prefill and one-sync step; the delta
    isolates the bucketing win (the compile-count collapse) only."""
    from repro.configs import get_config
    from repro.models import ExecOptions, build_model
    from repro.serve.engine import ServeEngine
    print("\n## Serve engine (continuous batching, smollm smoke config)")
    cfg = get_config("smollm-360m").smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(0))

    def prompts(n_req=12):
        out = []
        for i in range(n_req):
            n = 5 + (i * 7) % 23          # many distinct lengths
            out.append(np.asarray(jax.random.randint(
                jax.random.key(i), (n,), 0, cfg.vocab_size), np.int32))
        return out

    metrics = {}
    # chunked_prefill=False on both legs: this pair isolates the BUCKETING
    # win (compile-count collapse); the chunked scheduler is measured by its
    # own section below
    for tag, bucketed in (("fast", True), ("no_bucketing", False)):
        eng = ServeEngine(model, n_slots=4, max_len=64, params=params,
                          bucket_prompts=bucketed, chunked_prefill=False)
        ps = prompts()
        t0 = time.perf_counter()
        for p in ps:
            eng.submit(p, max_new_tokens=8)
        stats = eng.run_to_completion()
        dt = time.perf_counter() - t0
        tps = stats.tokens_out / dt
        metrics[f"{tag}_tokens_per_s"] = tps
        metrics[f"{tag}_prefill_compiles"] = stats.prefill_compiles
        print(f"serve,{tag},tokens_per_s={tps:.1f},"
              f"prefill_compiles={stats.prefill_compiles},"
              f"decode_steps={stats.decode_steps},"
              f"mean_occupancy={stats.summary().get('mean_occupancy', 0):.2f}")

    # steady-state decode throughput (slots full, compiles amortized);
    # max_new is sized so the timed window is several seconds — short windows
    # put this metric at the mercy of scheduler noise and flake the CI gate
    def steady_tps(eng):
        # best 25-step window (exact: counts emitted tokens): whole-run
        # means inherit scheduler-noise spikes and flake the CI gate
        for p in prompts(4):
            eng.submit(p, max_new_tokens=120)
        eng.step()                         # admit + warm the decode jit
        tps, steps = 0.0, 0
        while True:
            tok0 = eng.stats.tokens_out
            t0 = time.perf_counter()
            ran = 0
            while ran < 25 and eng.step():
                ran += 1
            steps += ran
            if ran:
                tps = max(tps, (eng.stats.tokens_out - tok0)
                          / (time.perf_counter() - t0))
            if ran < 25:
                break
        return tps, steps

    # f32 and int8 steady runs are INTERLEAVED (f32, int8, f32, int8; best
    # of each): their ratio is gated unconditionally, and minutes-apart legs
    # on a shared box would see different neighbor load — measured swings of
    # 0.8-1.8x on the same code when the legs ran back-to-back sections
    steady = {"f32": 0.0, "int8": 0.0}
    int8_steady_kw = dict(wdtype="int8", kv_dtype="int8")
    for _ in range(2):
        for tag, kw in (("f32", {}), ("int8", int8_steady_kw)):
            tps, _ = steady_tps(ServeEngine(model, n_slots=4, max_len=160,
                                            params=params, **kw))
            steady[tag] = max(steady[tag], tps)
    metrics["decode_tokens_per_s"] = steady["f32"]
    print(f"serve,decode_steady,tokens_per_s={steady['f32']:.1f}")

    # ---- paged KV pool vs dense worst-case rows (PR 2) --------------------
    # Long-context engine (max_len=512) over short-prompt traffic: the dense
    # engine reserves n_slots × max_len rows; the paged pool is sized to the
    # workload's live tokens (pages reserved at admission) and must stay
    # token-exact while holding a fraction of the memory.
    max_len, ps = 512, 16
    for tag, kw in (("dense_longctx", dict(paged=False)),
                    ("paged_longctx", dict(page_size=ps, n_pages=1 + 4 * 3))):
        eng = ServeEngine(model, n_slots=4, max_len=max_len, params=params,
                          **kw)
        ps_list = prompts()
        t0 = time.perf_counter()
        for p in ps_list:
            eng.submit(p, max_new_tokens=8)
        stats = eng.run_to_completion()
        dt = time.perf_counter() - t0
        kv_mib = eng.kv_cache_bytes() / 2**20
        metrics[f"{tag}_tokens_per_s"] = stats.tokens_out / dt
        metrics[f"{tag}_kv_mib"] = kv_mib
        if eng.paged:
            metrics["paged_peak_kv_rows"] = stats.peak_pages_in_use * ps
            metrics["dense_equiv_kv_rows"] = 4 * max_len
        print(f"serve,{tag},tokens_per_s={stats.tokens_out / dt:.1f},"
              f"kv_mib={kv_mib:.2f},"
              + (f"peak_rows={stats.peak_pages_in_use * ps},"
                 f"dense_rows={4 * max_len}" if eng.paged else ""))
    shrink = metrics["paged_longctx_kv_mib"] / metrics["dense_longctx_kv_mib"]
    metrics["paged_kv_shrink"] = shrink
    print(f"serve,paged_vs_dense,kv_mem_ratio={shrink:.3f}"
          f" (pool scales with live tokens, not n_slots*max_len)")

    # ---- end-to-end INT8 decode path (PR 3) -------------------------------
    # Same long-context paged pool, weights AND KV int8. Byte shrink is
    # measured against an equally-paged bf16 pool (deterministic memory
    # math); the tokens/s ratio vs the f32 engine and the greedy token
    # divergence vs the f32 paged run are the quality/perf guards. On CPU
    # the jnp dequant reference does extra work per step, so the ratio gates
    # loosely — on TPU the int8_matmul + fused-dequant kernels are the point.
    from repro.models.quantized import token_divergence
    # page_size=32 (the engine default), NOT the longctx section's 16: int8
    # pools tile at 32 sublanes, so 16-row pages would silently densify on
    # TPU instead of running the fused-dequant kernel this section times
    int8_kw = dict(page_size=32, n_pages=1 + 4 * 3)
    eng_bf = ServeEngine(model, n_slots=4, max_len=max_len, params=params,
                         kv_dtype="bf16", **int8_kw)
    metrics["bf16_kv_mib"] = eng_bf.kv_cache_bytes() / 2**20
    f32_out = {}
    for tag, kw in (("f32", {}), ("int8", dict(wdtype="int8",
                                               kv_dtype="int8"))):
        eng = ServeEngine(model, n_slots=4, max_len=max_len, params=params,
                          **int8_kw, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts()]
        t0 = time.perf_counter()
        stats = eng.run_to_completion()
        dt = time.perf_counter() - t0
        if tag == "f32":
            f32_out = {i: r.out_tokens for i, r in enumerate(reqs)}
        else:
            metrics["int8_kv_mib"] = eng.kv_cache_bytes() / 2**20
            metrics["int8_kv_shrink"] = (eng.kv_cache_bytes()
                                         / eng_bf.kv_cache_bytes())
            divs = [token_divergence(f32_out[i], r.out_tokens)
                    for i, r in enumerate(reqs)]
            metrics["int8_token_divergence"] = sum(divs) / len(divs)
    tps8 = steady["int8"]
    metrics["int8_decode_tokens_per_s"] = tps8
    metrics["int8_vs_f32_decode_ratio"] = tps8 / metrics["decode_tokens_per_s"]
    print(f"serve,int8,decode_tokens_per_s={tps8:.1f},"
          f"kv_shrink_vs_bf16={metrics['int8_kv_shrink']:.3f},"
          f"vs_f32_ratio={metrics['int8_vs_f32_decode_ratio']:.2f},"
          f"token_divergence={metrics['int8_token_divergence']:.3f}")

    # ---- chunked page-granular prefill vs monolithic (PR 4) ---------------
    # Mixed long/short traffic against a long-context paged engine: the
    # monolithic engine stalls the whole decode batch on every long prefill
    # (head-of-line blocking, counted in chunk-equivalents beyond the
    # one-chunk budget); the chunked engine runs at most one chunk per tick,
    # so its stall count is 0 by construction and its padding waste is
    # capped at one chunk per prompt. Both stall and pad-waste are
    # DETERMINISTIC tick/token counts — machine-free, gated tight.
    def mixed_traffic(eng):
        rng2 = np.random.default_rng(7)
        eng.submit(np.asarray(rng2.integers(0, cfg.vocab_size, 12),
                              np.int32), max_new_tokens=24)
        eng.step()                      # a short request is already decoding
        for i in range(10):
            n = 200 + 17 * i if i % 3 == 0 else 8 + 3 * i   # 4 long, 6 short
            eng.submit(np.asarray(rng2.integers(0, cfg.vocab_size, n),
                                  np.int32), max_new_tokens=8)
        t0 = time.perf_counter()
        stats = eng.run_to_completion()
        return stats, time.perf_counter() - t0

    for tag, kw in (("monolithic", dict(chunked_prefill=False)),
                    ("chunked", {})):
        eng = ServeEngine(model, n_slots=4, max_len=512, params=params,
                          page_size=16, **kw)
        stats, dt = mixed_traffic(eng)
        s = stats.summary()
        metrics[f"{tag}_prefill_stall_ticks"] = stats.decode_stall_ticks
        metrics[f"{tag}_pad_waste"] = s["pad_waste_ratio"]
        metrics[f"{tag}_mixed_tokens_per_s"] = stats.tokens_out / dt
        print(f"serve,{tag}_prefill,stall_ticks={stats.decode_stall_ticks},"
              f"pad_waste={s['pad_waste_ratio']:.3f},"
              f"tokens_per_s={stats.tokens_out / dt:.1f},"
              f"chunks={stats.prefill_chunks}")
    print(f"serve,chunked_vs_monolithic,stall "
          f"{metrics['monolithic_prefill_stall_ticks']}->"
          f"{metrics['chunked_prefill_stall_ticks']},pad_waste "
          f"{metrics['monolithic_pad_waste']:.3f}->"
          f"{metrics['chunked_pad_waste']:.3f}")

    # ---- retrace sanitizer: steady-state compile budget (PR 10) -----------
    # Re-run the IDENTICAL mixed wave against the still-warm chunked engine
    # under `analysis.sanitizer.watch()`. The first wave exercised every
    # shape the scheduler can produce (chunk, decode, every prefill bucket,
    # paste), so any XLA compile in the second wave is a retrace leak — a
    # shape or dtype smuggled into trace context. All three counts are
    # deterministic trace math, det-gated at zero slack.
    from repro.analysis import sanitizer
    with sanitizer.watch() as wlog:
        mixed_traffic(eng)
    metrics["chunk_compiles"] = eng.stats.chunk_compiles
    metrics["decode_compiles"] = eng.stats.decode_compiles
    metrics["steady_state_retraces"] = wlog.compiles
    print(f"serve,sanitizer,chunk_compiles={eng.stats.chunk_compiles},"
          f"decode_compiles={eng.stats.decode_compiles},"
          f"steady_state_retraces={wlog.compiles},"
          f"host_syncs={wlog.host_syncs}")

    # ---- sharded multi-chiplet serving (PR 5) -----------------------------
    # Device-partitioned paged pool + shard_map decode on a 4-device CPU
    # mesh vs the single-host engine on the SAME traffic, both legs inside
    # one forked process (device count is fixed at jax import, and the
    # same-process pairing keeps the ratio machine-free). Token divergence
    # is a DETERMINISTIC parity gate (must stay 0); the occupancy imbalance
    # is deterministic tick math on fixed traffic.
    metrics.update(_bench_sharded_serve())
    print(f"serve,sharded,tokens_per_s={metrics['sharded_tokens_per_s']:.1f},"
          f"vs_single_host={metrics['sharded_vs_single_host_ratio']:.2f},"
          f"occupancy_imbalance={metrics['sharded_occupancy_imbalance']:.3f},"
          f"token_divergence={metrics['sharded_token_divergence']:.3f}")

    # ---- chaos serving: fault injection + recovery (PR 6) -----------------
    # Same 4-device mesh, mixed dense×f32 + moe×int8 traffic, a seeded
    # FaultPlan (shard deaths/rejoins + page squeezes) vs a fault-free twin
    # on identical submissions. The headline is the chaos-parity gate:
    # token streams are schedule-independent, so the surviving engine must
    # emit EXACTLY the fault-free tokens (divergence 0, det-gated at zero
    # slack). Preemption/recovery counts are deterministic tick math on the
    # fixed plan — any drift is a scheduler change, never noise.
    metrics.update(_bench_chaos_serve())
    print(f"serve,chaos,token_divergence="
          f"{metrics['chaos_token_divergence']:.3f},"
          f"preemptions={metrics['chaos_preemptions']:.0f},"
          f"recoveries={metrics['chaos_recoveries']:.0f},"
          f"mean_recovery_ticks={metrics['chaos_mean_recovery_ticks']:.1f},"
          f"faults={metrics['chaos_faults_injected']:.0f}")

    # ---- live page migration + elastic rebalancing (PR 9) -----------------
    # Drain leg: a sensor-driven DRAINING shard re-homes its live slots by
    # page moves over the modeled UCIe link instead of re-prefill replay.
    # Every metric is deterministic tick/chunk arithmetic on fixed traffic:
    # divergence vs the fault-free twin must be 0, and the drain-cost ratio
    # — extra prefill chunks of migration over extra chunks of replay — must
    # be 0 (O(bytes) moves recompute NOTHING). Rebalance leg: after the
    # drained shard rejoins empty, threshold-1 elastic moves pull load back;
    # the post-rebalance token imbalance is det-gated strictly below the
    # committed sharded baseline (0.67).
    metrics.update(_bench_migration_serve())
    print(f"serve,migration,token_divergence="
          f"{metrics['migration_token_divergence']:.3f},"
          f"drain_chunk_ratio={metrics['migration_drain_chunk_ratio']:.3f},"
          f"migrations={metrics['migration_count']:.0f},"
          f"pages={metrics['migration_pages_moved']:.0f},"
          f"rebalance_imbalance="
          f"{metrics['rebalance_occupancy_imbalance']:.3f}")

    # ---- per-slot sampling overhead ---------------------------------------
    # sampled decode vs greedy decode, same engine config: the sampler rides
    # the same single decode jit, so the delta is the vmapped sort/cumsum
    eng = ServeEngine(model, n_slots=4, max_len=160, params=params)
    for p in prompts(4):
        eng.submit(p, max_new_tokens=60, sample_params=(0.8, 40, 0.95),
                   seed=11)
    t0 = time.perf_counter()
    stats = eng.run_to_completion()
    dt = time.perf_counter() - t0
    metrics["sampled_tokens_per_s"] = stats.tokens_out / dt
    print(f"serve,sampled,tokens_per_s={stats.tokens_out / dt:.1f},"
          f"temperature=0.8,top_k=40,top_p=0.95")

    # ---- MLA latent-KV vs GQA int8 bytes/token (PR 7) ---------------------
    # deepseek-v2-lite smoke (attn_kind='mla') vs the SAME architecture
    # flipped to paired-KV GQA with an int8 pool — the strongest KV-memory
    # baseline the stack had. The MLA pool holds ONE latent row per token
    # (kv_lora_rank + qk_rope_dim wide, KV-head dim 1, bf16) where GQA
    # stores kv_pad K+V head pairs (+ int8 scale rows). Bytes/token is
    # deterministic pool math (kv_cache_bytes over pool rows, identical
    # page geometry both legs), so MLA-beats-GQA-int8 gates as 'det'; the
    # throughput leg is a clock.
    import dataclasses
    mcfg = get_config("deepseek-v2-lite").smoke()
    mmodel = build_model(mcfg, ExecOptions(attn_impl="reference",
                                           ce_chunk=32, moe_group=32))
    mparams = mmodel.init(jax.random.key(0))
    gcfg = dataclasses.replace(mcfg, attn_kind="gqa")
    gmodel = build_model(gcfg, ExecOptions(attn_impl="reference",
                                           ce_chunk=32, moe_group=32))
    gparams = gmodel.init(jax.random.key(0))
    pool_kw = dict(n_slots=4, max_len=64, page_size=8, n_pages=33)
    eng_mla = ServeEngine(mmodel, params=mparams, kv_dtype="bf16", **pool_kw)
    eng_gqa = ServeEngine(gmodel, params=gparams, kv_dtype="int8", **pool_kw)
    rows = pool_kw["n_pages"] * pool_kw["page_size"]
    bpt_mla = eng_mla.kv_cache_bytes() / rows
    bpt_gqa = eng_gqa.kv_cache_bytes() / rows
    metrics["mla_kv_bytes_per_token"] = bpt_mla
    metrics["gqa_int8_kv_bytes_per_token"] = bpt_gqa
    metrics["mla_vs_gqa_int8_kv_ratio"] = bpt_mla / bpt_gqa
    assert bpt_mla < bpt_gqa, \
        (bpt_mla, bpt_gqa, "MLA latent rows must undercut GQA int8")
    mla_ps = [np.asarray(jax.random.randint(
        jax.random.key(100 + i), (5 + (i * 7) % 23,), 0, mcfg.vocab_size),
        np.int32) for i in range(8)]
    for p in mla_ps:
        eng_mla.submit(p, max_new_tokens=8)
    t0 = time.perf_counter()
    stats = eng_mla.run_to_completion()
    dt = time.perf_counter() - t0
    metrics["mla_tokens_per_s"] = stats.tokens_out / dt
    print(f"serve,mla,kv_bytes_per_token={bpt_mla:.1f},"
          f"gqa_int8_bytes_per_token={bpt_gqa:.1f},"
          f"ratio={bpt_mla / bpt_gqa:.3f},"
          f"tokens_per_s={stats.tokens_out / dt:.1f}")

    # ---- prefix caching + copy-on-write pages (PR 8) ----------------------
    # A warmup request registers a 48-token system prompt in the ref-counted
    # page registry; a wave of requests reusing it must decode
    # TOKEN-IDENTICALLY to a cache-off twin on the same submissions
    # (divergence det-gated at zero across dense/moe/mla × f32/bf16/int8,
    # greedy AND sampled in every wave) while admitting off shared pages:
    # strictly fewer peak pool pages AND fewer ticks to first token. Both
    # headline ratios are tick/page arithmetic on fixed traffic —
    # machine-free, det-gated < 1.
    def prefix_trace(eng, vocab):
        rngp = np.random.default_rng(11)
        sysp = np.asarray(rngp.integers(0, vocab, 48), np.int32)
        reqs = [eng.submit(sysp, max_new_tokens=2)]
        eng.run_to_completion()        # registration happens at finalize
        for i in range(4):             # mixed greedy/sampled wave
            tail = np.asarray(rngp.integers(0, vocab, 4 + 3 * i), np.int32)
            sp = (0.8, 40, 0.95) if i % 2 else None
            reqs.append(eng.submit(np.concatenate([sysp, tail]),
                                   max_new_tokens=6, sample_params=sp,
                                   seed=50 + i))
        eng.run_to_completion()
        eng.assert_accounting()
        ttft_ticks = sum(r.first_token_tick - r.submit_tick
                         for r in reqs[1:])
        return ([list(r.out_tokens) for r in reqs], ttft_ticks,
                eng.stats.peak_pages_in_use, eng.stats)

    qcfg = get_config("qwen2-moe-a2.7b").smoke()
    qmodel = build_model(qcfg, ExecOptions(attn_impl="reference",
                                           ce_chunk=32))
    qparams = qmodel.init(jax.random.key(0))
    div_sum, div_n = 0, 0
    for arch, m_, p_, v_ in (("dense", model, params, cfg.vocab_size),
                             ("moe", qmodel, qparams, qcfg.vocab_size),
                             ("mla", mmodel, mparams, mcfg.vocab_size)):
        for kvd in (None, "bf16", "int8"):
            legs = {}
            for cached in (True, False):
                eng = ServeEngine(m_, n_slots=4, max_len=96, params=p_,
                                  page_size=8, chunk_pages=1, kv_dtype=kvd,
                                  prefix_cache=cached)
                legs[cached] = prefix_trace(eng, v_)
            div_sum += sum(a != b
                           for a, b in zip(legs[True][0], legs[False][0]))
            div_n += len(legs[True][0])
            if arch == "dense" and kvd is None:
                st = legs[True][3]
                metrics["cache_hit_ttft_ratio"] = (legs[True][1]
                                                   / legs[False][1])
                metrics["prefix_pool_pages_ratio"] = (legs[True][2]
                                                      / legs[False][2])
                metrics["prefix_hit_tokens"] = float(st.prefix_hit_tokens)
                metrics["prefix_cow_copies"] = float(st.cow_copies)
    metrics["prefix_token_divergence"] = div_sum / div_n
    print(f"serve,prefix_cache,token_divergence="
          f"{metrics['prefix_token_divergence']:.3f},"
          f"ttft_ratio={metrics['cache_hit_ttft_ratio']:.3f},"
          f"pool_pages_ratio={metrics['prefix_pool_pages_ratio']:.3f},"
          f"hit_tokens={metrics['prefix_hit_tokens']:.0f},"
          f"cow_copies={metrics['prefix_cow_copies']:.0f}")

    # same-run ratio: machine-speed cancels, so the regression gate can hold
    # this tight even across runner generations
    metrics["bucketing_speedup"] = (metrics["fast_tokens_per_s"]
                                    / metrics["no_bucketing_tokens_per_s"])
    return metrics


_SHARDED_BENCH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
import jax, numpy as np
from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.launch.mesh import make_serve_mesh
from repro.serve.engine import ServeEngine
from repro.serve.sharded import ShardedServeEngine

cfg = get_config("smollm-360m").smoke()
model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
params = model.init(jax.random.key(0))

def prompts(n_req=12):
    out = []
    for i in range(n_req):
        n = 5 + (i * 7) % 23
        out.append(np.asarray(jax.random.randint(
            jax.random.key(i), (n,), 0, cfg.vocab_size), np.int32))
    return out

def leg(eng):
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts()]
    t0 = time.perf_counter()
    stats = eng.run_to_completion()
    return reqs, stats.tokens_out / (time.perf_counter() - t0)

single = ServeEngine(model, n_slots=8, max_len=64, params=params, page_size=8)
s_reqs, s_tps = leg(single)
sharded = ShardedServeEngine(model, mesh=make_serve_mesh(4), n_slots=8,
                             max_len=64, params=params, page_size=8)
d_reqs, d_tps = leg(sharded)
sharded.assert_local_page_tables()
div = sum(a.out_tokens != b.out_tokens
          for a, b in zip(s_reqs, d_reqs)) / len(s_reqs)
print("SHARDED_JSON " + json.dumps({
    "sharded_tokens_per_s": d_tps,
    "sharded_vs_single_host_ratio": d_tps / s_tps,
    "sharded_occupancy_imbalance":
        sharded.shard_summary()["occupancy_imbalance"],
    "sharded_token_divergence": div,
}))
"""


def _cpu_child_env():
    """Environment of the forked 4-device benches. They are CPU correctness
    gates by design, pinned to the CPU so that a parent holding a chip never
    collides with a child reaching for it."""
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = f"{src}:{env.get('PYTHONPATH', '')}".rstrip(":")
    return env


def _bench_sharded_serve():
    """Fork the sharded-vs-single-host pair onto a 4-device CPU mesh (the
    forced device count must be set before jax imports, so this can't run
    in the harness process)."""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", _SHARDED_BENCH],
                       env=_cpu_child_env(), capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"sharded serve bench failed:\n{r.stderr[-3000:]}")
    line = [l for l in r.stdout.splitlines()
            if l.startswith("SHARDED_JSON ")][-1]
    return json.loads(line[len("SHARDED_JSON "):])


_CHAOS_BENCH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
import jax, numpy as np
from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.launch.mesh import make_serve_mesh
from repro.serve.faults import chaos_plan
from repro.serve.sharded import ShardedServeEngine

mesh = make_serve_mesh(4)

# Tight pool (12 usable pages/shard) + 2 deaths with long dwell: recovery
# re-prefills displaced requests onto surviving shards, whose requeued (old)
# rids then out-rank decoding slots and trigger free-list preemption. Tuned
# so the plan exercises deaths, rejoins, squeezes AND >=3 preemptions.
PLAN = chaos_plan(2, n_shards=4, n_ticks=56, deaths=2, death_dwell=16,
                  squeezes=8, squeeze_pages=10, squeeze_dwell=14)

def prompts(cfg, n_req):
    out = []
    for i in range(n_req):
        n = 5 + (i * 7) % 23
        out.append(np.asarray(jax.random.randint(
            jax.random.key(i), (n,), 0, cfg.vocab_size), np.int32))
    return out

def leg(model, params, cfg, n_req, max_new, eng_kw, plan):
    eng = ShardedServeEngine(model, mesh=mesh, n_slots=8, max_len=64,
                             params=params, page_size=8, n_pages=13,
                             fault_plan=plan, **eng_kw)
    reqs = [eng.submit(p, max_new_tokens=max_new, seed=100 + i)
            for i, p in enumerate(prompts(cfg, n_req))]
    t0 = time.perf_counter()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    assert all(r.done and not r.timed_out for r in reqs)
    return eng.stats, [list(r.out_tokens) for r in reqs], dt

tot = {"preempt": 0, "recov": 0, "rec_ticks": 0, "faults": 0, "div": 0,
       "n": 0, "toks": 0, "dt": 0.0}
for arch, eng_kw, n_req, max_new in (
        ("smollm-360m", {}, 16, 16),
        ("qwen2-moe-a2.7b", {"wdtype": "int8", "kv_dtype": "int8"}, 8, 8)):
    cfg = get_config(arch).smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(0))
    _, base_toks, _ = leg(model, params, cfg, n_req, max_new, eng_kw, None)
    st, chaos_toks, dt = leg(model, params, cfg, n_req, max_new, eng_kw, PLAN)
    tot["div"] += sum(a != b for a, b in zip(base_toks, chaos_toks))
    tot["n"] += n_req
    tot["preempt"] += st.preemptions
    tot["recov"] += st.recoveries
    tot["rec_ticks"] += st.recovery_ticks_sum
    tot["faults"] += st.faults_injected
    tot["toks"] += st.tokens_out
    tot["dt"] += dt

counts = PLAN.counts()
assert counts["shard_death"] >= 1 and counts["shard_rejoin"] >= 1, counts
assert tot["recov"] >= 1, tot
assert tot["preempt"] >= 3, tot
print("CHAOS_JSON " + json.dumps({
    "chaos_token_divergence": tot["div"] / tot["n"],
    "chaos_preemptions": tot["preempt"],
    "chaos_recoveries": tot["recov"],
    "chaos_mean_recovery_ticks": tot["rec_ticks"] / max(1, tot["recov"]),
    "chaos_faults_injected": tot["faults"],
    "chaos_tokens_per_s": tot["toks"] / tot["dt"],
}))
"""


_MIGRATION_BENCH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, time
import jax, numpy as np
from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.launch.mesh import make_serve_mesh
from repro.serve.faults import FaultEvent, FaultPlan
from repro.serve.sharded import ShardedServeEngine

mesh = make_serve_mesh(4)
cfg = get_config("smollm-360m").smoke()
model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
params = model.init(jax.random.key(1))

def prompt(seed, n):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, cfg.vocab_size), np.int32)

def leg(lens, max_new, **kw):
    eng = ShardedServeEngine(model, mesh=mesh, n_slots=8, params=params,
                             page_size=8, **kw)
    reqs = [eng.submit(prompt(i, n), max_new_tokens=max_new, seed=100 + i)
            for i, n in enumerate(lens)]
    eng.run_to_completion()
    eng.assert_pool_accounting()
    eng.assert_local_page_tables()
    assert all(r.done and not r.timed_out for r in reqs)
    return eng, [list(r.out_tokens) for r in reqs]

# ---- drain leg: migration vs replay vs fault-free on a sensor drain -----
PLAN = FaultPlan(events=(
    FaultEvent(tick=4, kind="sensor_hot", shard=1, delta_c=60.0, ticks=8),))
lens = [5 + (i * 7) % 23 for i in range(5)]
dkw = dict(max_len=64, n_pages=24)
free, free_t = leg(lens, 12, **dkw)
mig, mig_t = leg(lens, 12, fault_plan=PLAN, **dkw)
rep, rep_t = leg(lens, 12, fault_plan=PLAN, migration=False, **dkw)
div = sum(a != b for a, b in zip(free_t, mig_t))
assert mig.stats.migrations >= 1 and mig.stats.recoveries >= 1, \
    mig.stats.summary()
assert mig.stats.prefill_chunks == free.stats.prefill_chunks, \
    (mig.stats.prefill_chunks, free.stats.prefill_chunks)
extra_mig = mig.stats.prefill_chunks - free.stats.prefill_chunks
extra_rep = rep.stats.prefill_chunks - free.stats.prefill_chunks
assert extra_rep > 0, extra_rep

# ---- rebalance leg: drained shard rejoins empty; threshold-1 elastic
#      moves pull live slots back (tokens must not change) ----------------
RPLAN = FaultPlan(events=(
    FaultEvent(tick=4, kind="sensor_hot", shard=0, delta_c=60.0, ticks=8),))
rlens = [9, 12, 15, 18, 11, 14]
rkw = dict(max_len=96, n_pages=36, fault_plan=RPLAN)
still, still_t = leg(rlens, 24, rebalance_threshold=0, **rkw)
rebal, rebal_t = leg(rlens, 24, rebalance_threshold=1, **rkw)
assert still_t == rebal_t, "rebalancing changed tokens"
assert rebal.stats.rebalance_events >= 1, rebal.stats.summary()
imb = rebal.shard_summary()["occupancy_imbalance"]
imb0 = still.shard_summary()["occupancy_imbalance"]
assert imb < imb0 and imb < 0.67, (imb, imb0)

print("MIGRATION_JSON " + json.dumps({
    "migration_token_divergence": div / len(lens),
    "migration_drain_chunk_ratio": extra_mig / max(1, extra_rep),
    "migration_count": float(mig.stats.migrations),
    "migration_pages_moved": float(mig.stats.migrated_pages),
    "migration_wire_bytes": mig.stats.migrated_bytes_compressed,
    "rebalance_occupancy_imbalance": imb,
    "rebalance_events": float(rebal.stats.rebalance_events),
}))
"""


def _bench_migration_serve():
    """Fork the migration bench onto a 4-device CPU mesh: a drain-cost
    triple (fault-free / drain-via-migration / drain-via-replay on
    identical traffic) and a rebalance pair (threshold 0 vs 1 around a
    drain+rejoin). All gated metrics are deterministic replay arithmetic —
    divergence and the drain chunk ratio must be exactly 0, the
    post-rebalance imbalance is fixed tick math."""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", _MIGRATION_BENCH],
                       env=_cpu_child_env(), capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"migration serve bench failed:\n{r.stderr[-3000:]}")
    line = [l for l in r.stdout.splitlines()
            if l.startswith("MIGRATION_JSON ")][-1]
    return json.loads(line[len("MIGRATION_JSON "):])


def _bench_chaos_serve():
    """Fork the chaos-vs-fault-free pair onto a 4-device CPU mesh. The
    FaultPlan is seeded and tick-indexed, the traffic is fixed, and token
    streams are schedule-independent — so every metric except tokens/s is
    exact replay arithmetic: divergence must be 0 and the preemption /
    recovery counts are pinned integers."""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", _CHAOS_BENCH],
                       env=_cpu_child_env(), capture_output=True, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"chaos serve bench failed:\n{r.stderr[-3000:]}")
    line = [l for l in r.stdout.splitlines()
            if l.startswith("CHAOS_JSON ")][-1]
    return json.loads(line[len("CHAOS_JSON "):])


# -------------------------------------------------------------------- kernels
def bench_kernels():
    from repro.kernels import ops, ref
    from repro.kernels.decode_attention import decode_attention as dec_attn
    from repro.models import attention as attn_mod
    print("\n## Pallas kernels (interpret mode on CPU; TPU is the target)")
    metrics = {}
    x = jax.random.normal(jax.random.key(0), (256, 1024), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (1024, 256), jnp.float32)
    wq, s = ops.quantize_weight(w)
    us, out = _timeit(lambda: ops.int8_matmul(x.astype(jnp.bfloat16), wq, s),
                      n=3, warmup=1)
    want = ref.int8_matmul_ref(x, wq, s)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    metrics["int8_matmul_us"] = us
    print(f"kernels,int8_matmul,256x1024x256,{us:.0f}us,rel_err={rel:.4f}")
    q = jax.random.normal(jax.random.key(2), (1, 4, 256, 64), jnp.float32)
    us, out = _timeit(lambda: ops.flash_attention(q, q, q, causal=True),
                      n=3, warmup=1)
    err = float(jnp.max(jnp.abs(out - ref.flash_attention_ref(q, q, q))))
    metrics["flash_attention_us"] = us
    print(f"kernels,flash_attention,B1H4S256D64,{us:.0f}us,err={err:.2e}")
    # decode attention: single query vs ragged cache (the serve hot loop)
    b, kv, g, d, smax = 4, 2, 4, 64, 512
    qd = jax.random.normal(jax.random.key(4), (b, 1, kv, g, d), jnp.float32)
    kc = jax.random.normal(jax.random.key(5), (b, smax, kv, d), jnp.float32)
    vc = jax.random.normal(jax.random.key(6), (b, smax, kv, d), jnp.float32)
    kvl = jnp.asarray([37, 200, 350, 512], jnp.int32)
    us, out = _timeit(
        lambda: dec_attn(qd, kc, vc, kvl, interpret=True), n=3, warmup=1)
    want = attn_mod.decode_attention(qd, kc, vc, kvl, impl="reference")
    err = float(jnp.max(jnp.abs(out - want)))
    metrics["decode_attention_us"] = us
    print(f"kernels,decode_attention,B4KV2G4S512D64,{us:.0f}us,err={err:.2e}")
    gx = jax.random.normal(jax.random.key(3), (1 << 16,), jnp.float32)
    us, (qq, ss, nn) = _timeit(lambda: ops.quantize_blocks(gx), n=3, warmup=1)
    print(f"kernels,quantize_blocks,64Ktokens,{us:.0f}us,"
          f"payload_ratio={float((qq.size + 4*ss.size)/(4*gx.size)):.3f}")
    return metrics


# ------------------------------------------------------------------- roofline
def bench_roofline():
    print("\n## Roofline (from dry-run artifacts, single-pod 256 chips)")
    from repro.launch.roofline import build_table
    table = build_table()
    ok = 0
    for key, row in table.items():
        if row["status"] != "ok":
            print(f"roofline,{key},{row['status']}")
            continue
        ok += 1
        print(f"roofline,{key},bound={row['dominant']},"
              f"compute_s={row['compute_s']:.3f},memory_s={row['memory_s']:.3f},"
              f"collective_s={row['collective_s']:.3f},"
              f"useful={row['useful_ratio']:.2f},"
              f"fraction={row['roofline_fraction']:.2f},"
              f"peak_GiB={row['peak_gib']:.1f}")
    print(f"roofline,cells_ok,{ok}")
    return {"cells_ok": ok}


# -------------------------------------------------------------- ablations
def bench_ablations():
    """Beyond-paper: attribute the AI-optimized gains to each §II mechanism.

    The paper reports the joint effect (−14.7 % latency); the reconstructed
    model lets us toggle I1 (DVFS boost), I2a (prefetch overlap),
    I2b (compression) independently — an ablation the paper doesn't run.
    """
    import dataclasses
    from repro.core import perf_model as pm
    from repro.core.scenarios import AI_OPTIMIZED, BASIC_CHIPLET
    from repro.core.workloads import MOBILENET_V2
    print("\n## Ablations — which mechanism buys what (MobileNetV2, batch 1)")
    basic = pm.predict(BASIC_CHIPLET, MOBILENET_V2, 1)

    variants = {
        "full_ai_optimized": {},
        "no_dvfs_boost(I1)": dict(dvfs_adaptive=False, dvfs_boost_max=0.0),
        "no_prefetch(I2a)": dict(prefetch_overlap=False),
        "no_compression(I2b)": dict(compression_ratio=1.0),
        "silicon_only(no I1+I2)": dict(dvfs_adaptive=False, dvfs_boost_max=0.0,
                                       prefetch_overlap=False,
                                       compression_ratio=1.0),
    }
    for name, kw in variants.items():
        s = dataclasses.replace(AI_OPTIMIZED, **kw)
        r = pm.predict(s, MOBILENET_V2, 1)
        dlat = 100 * (1 - float(r.latency_ms) / float(basic.latency_ms))
        dtw = 100 * (float(r.tops_per_w) / float(basic.tops_per_w) - 1)
        print(f"ablation,{name},lat_ms={float(r.latency_ms):.2f},"
              f"vs_basic_lat=-{dlat:.1f}%,vs_basic_topsw=+{dtw:.1f}%")
    # thermal mechanism (I4) shows up at sustained batch, not batch-1
    grid = pm.predict_grid([AI_OPTIMIZED,
                            dataclasses.replace(AI_OPTIMIZED, name="react",
                                                dvfs_adaptive=False,
                                                dvfs_boost_max=0.0)],
                           [MOBILENET_V2], [32])
    ai32, re32 = float(grid.throughput_ips[0, 0, 0]), float(
        grid.throughput_ips[1, 0, 0])
    print(f"ablation,migration_at_batch32(I4),ai={ai32:.0f}ips,"
          f"reactive={re32:.0f}ips,delta=+{100*(ai32/re32-1):.1f}%")


SECTIONS = {
    "table1": bench_table1,
    "table3": bench_table3,
    "fig2": bench_fig2,
    "soc": bench_soc,
    "dse": bench_dse,
    "serve": bench_serve,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "ablations": bench_ablations,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SECTIONS))
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<section>.json per executed section")
    ap.add_argument("--outdir", default=".", type=pathlib.Path,
                    help="where --json snapshots land (CI writes fresh runs "
                         "to a scratch dir and gates them against the "
                         "committed ones via benchmarks.compare)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(SECTIONS)
    t0 = time.time()
    for n in names:
        metrics = SECTIONS[n]()
        if args.json and metrics:
            metrics["env_id"] = env_fingerprint()
            args.outdir.mkdir(parents=True, exist_ok=True)
            path = args.outdir / f"BENCH_{n}.json"
            path.write_text(json.dumps(metrics, indent=2, sort_keys=True))
            print(f"bench,json,{path}")
    print(f"\nbenchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
