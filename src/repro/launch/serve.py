"""Serving driver: continuous-batching engine over a chosen architecture.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --requests 16 --slots 4 [--wdtype int8] [--kv-dtype int8]

`--smoke` (the default) serves the reduced CPU config; `--no-smoke` serves
the architecture at its published widths.

`--wdtype int8 --kv-dtype int8` is the paper's "AI-optimized" serving
numerics: weight-only int8 projections (Pallas int8_matmul on TPU) plus an
int8 paged KV pool with dequant fused into the decode-attention kernel —
the 15 TOPS INT8 NPU datapath (§II) as the measured configuration.

`--shards N` serves through the sharded multi-chiplet engine instead
(serve/sharded.py): slots and the paged KV pool partition over a 1-D data
mesh of N local devices — one shard per chiplet — with device-local page
tables and one shard_map'd global decode step. Token streams are identical
to the single-host engine. On CPU, force fake devices first:
XLA_FLAGS=--xla_force_host_platform_device_count=N.

On a pod the same engine runs against the mesh-sharded prefill/decode steps
from `launch/steps.py`; on CPU it serves the reduced configs (examples +
tests exercise this path).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ExecOptions, build_model
from repro.serve.engine import ServeEngine


def quantize_params_int8(params):
    """Weight-only int8 QDQ over generic 2-D weights.

    Kept for f32-datapath experiments that only want int8 NUMERICS; real
    int8 serving goes through `ServeEngine(wdtype="int8")`, which stores the
    projections as (int8, scale) and dispatches the Pallas int8_matmul."""
    from repro.kernels import ops as kops

    def qdq(p):
        if p.ndim == 2 and min(p.shape) >= 64:
            q, s = kops.quantize_weight(p.astype(jnp.float32))
            return (q.astype(jnp.float32) * s[None, :]).astype(p.dtype)
        return p

    return jax.tree.map(qdq, params)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-smoke: published "
                         "widths)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--int8", action="store_true",
                    help="shorthand for --wdtype int8 --kv-dtype int8")
    ap.add_argument("--wdtype", choices=["bf16", "int8"], default=None,
                    help="weight datapath (int8 = Pallas int8_matmul on TPU)")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8", "fp8"],
                    default=None,
                    help="KV-cache storage (int8 = fused-dequant decode; "
                         "fp8 = e5m2 cast, dense layout / --page-size 0)")
    ap.add_argument("--page-size", type=int, default=32,
                    help="KV page size (0 = dense per-slot cache)")
    ap.add_argument("--pages", type=int, default=0,
                    help="pool pages incl. the null page (0 = worst case); "
                         "with --shards this is PER-SHARD (each shard owns "
                         "its own pool + local null page)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="monolithic bucketed prefill instead of the "
                         "chunked page-granular default (paged engines)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="force the ref-counted prefix cache on (default: "
                    "auto — on for paged+chunked engines, off under a "
                    "sliding window)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix caching / copy-on-write pages")
    ap.add_argument("--chunk-pages", type=int, default=2,
                    help="prefill chunk size in pages (chunk = "
                         "chunk_pages x page_size tokens)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard slots + KV pages over N local devices "
                         "(sharded multi-chiplet engine; 0 = single-host)")
    ap.add_argument("--migration", dest="migration", action="store_true",
                    default=True,
                    help="live page migration over the modeled UCIe link "
                         "(default on, --shards only): DRAINING shards "
                         "re-home live slots by O(bytes) page moves and "
                         "hot prefixes replicate cross-shard")
    ap.add_argument("--no-migration", dest="migration",
                    action="store_false",
                    help="fall back to re-prefill replay for every "
                         "displaced slot")
    ap.add_argument("--rebalance-threshold", type=int, default=0,
                    help="busy-slot gap that triggers an elastic slot "
                         "migration between shards (0 = rebalancing off; "
                         "drain migration is governed by --migration)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="inject a seeded chaos FaultPlan (serve/faults."
                         "chaos_plan): shard death/rejoin + page squeezes; "
                         "same seed replays the same schedule bit-for-bit")
    ap.add_argument("--fault-rate", type=float, default=1.0,
                    help="chaos intensity multiplier: scales the plan's "
                         "death and page-squeeze counts")
    ap.add_argument("--ttl-ticks", type=int, default=None,
                    help="retire requests older than this many engine ticks "
                         "(graceful timeout instead of unbounded waiting)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-queue cap; submits beyond it raise "
                         "EngineOverloaded (graceful backpressure)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus top-p filter (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(args.seed))
    wdtype = args.wdtype or ("int8" if args.int8 else None)
    kv_dtype = args.kv_dtype or ("int8" if args.int8 else None)
    if cfg.family not in ("dense", "moe", "vlm", "encdec"):
        # recurrent families (ssm/hybrid) have no int8 engine datapath —
        # keep the old behavior: generic QDQ for int8 NUMERICS, f32 compute
        if wdtype == "int8":
            params = quantize_params_int8(params)
            wdtype = None
        kv_dtype = None if kv_dtype in ("int8", "bf16", "fp8") else kv_dtype
    if kv_dtype == "fp8" and args.page_size != 0:
        ap.error("--kv-dtype fp8 is dense-layout only (paged e5m2 pools are "
                 "a recorded follow-on); pass --page-size 0")
    fault_plan = None
    if args.fault_seed is not None:
        from repro.serve.faults import chaos_plan
        n_shards = args.shards or 1
        # single-host engines honor only the page events ("shard 0" of a
        # one-shard fleet), so chaos there is squeezes only
        # spread events over the run's expected tick span (decode ticks ≈
        # requests × new_tokens / slots, plus prefill) so they actually land
        n_ticks = max(16, args.requests * args.new_tokens
                      // max(1, args.slots) + 8)
        fault_plan = chaos_plan(
            args.fault_seed, n_shards=n_shards, n_ticks=n_ticks,
            deaths=max(1, round(args.fault_rate)) if n_shards > 1 else 0,
            death_dwell=max(2, n_ticks // 4),
            squeezes=max(1, round(3 * args.fault_rate)))
        print(f"[serve] fault plan seed={args.fault_seed}: "
              f"{fault_plan.counts()}")
    ft_kw = {"fault_plan": fault_plan, "ttl_ticks": args.ttl_ticks,
             "max_queue": args.max_queue}
    if args.shards:
        # the sharded engine is paged + chunked by construction — reject the
        # flags that name a different engine instead of reinterpreting them
        if args.page_size == 0:
            ap.error("--shards requires a paged cache; --page-size 0 (dense "
                     "rows) only exists on the single-host engine")
        if args.no_chunked_prefill:
            ap.error("--shards prefills in per-shard interleaved chunks; "
                     "--no-chunked-prefill only exists on the single-host "
                     "engine")
        from repro.launch.mesh import make_serve_mesh
        from repro.serve.sharded import ShardedServeEngine
        n_slots = args.slots
        if n_slots % args.shards:
            n_slots = args.shards * max(1, n_slots // args.shards)
            print(f"[serve] rounding slots to {n_slots} "
                  f"({args.shards} shards)")
        eng = ShardedServeEngine(
            model, mesh=make_serve_mesh(args.shards), n_slots=n_slots,
            max_len=args.max_len, params=params, wdtype=wdtype,
            kv_dtype=kv_dtype, page_size=args.page_size,
            n_pages=args.pages or None, chunk_pages=args.chunk_pages,
            prefix_cache=args.prefix_cache, migration=args.migration,
            rebalance_threshold=args.rebalance_threshold or None, **ft_kw)
    else:
        paged_kw = {"paged": False} if args.page_size == 0 else {
            "page_size": args.page_size,
            "n_pages": args.pages or None,
            "chunked_prefill": False if args.no_chunked_prefill else None,
            "chunk_pages": args.chunk_pages,
            "prefix_cache": args.prefix_cache,
        }
        eng = ServeEngine(model, n_slots=args.slots, max_len=args.max_len,
                          params=params, wdtype=wdtype, kv_dtype=kv_dtype,
                          **paged_kw, **ft_kw)
    sample = None if args.temperature == 0 else (
        args.temperature, args.top_k, args.top_p)
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(8, 24))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        reqs.append(eng.submit(prompt, max_new_tokens=args.new_tokens,
                               sample_params=sample, seed=args.seed + i))
    t0 = time.time()
    stats = eng.run_to_completion()
    wall = time.time() - t0
    done = sum(r.done for r in reqs)
    ttft = [r.t_first_token - r.t_enqueue for r in reqs if r.t_first_token]
    print(f"[serve] {done}/{len(reqs)} done  {stats.summary()}")
    print(f"[serve] {stats.tokens_out / wall:.1f} tok/s  "
          f"mean TTFT {1e3 * sum(ttft) / len(ttft):.0f} ms  wall {wall:.1f}s")
    if args.shards:
        ss = eng.shard_summary()
        print(f"[serve] shards={args.shards}  "
              f"tokens/shard={ss['shard_tokens']}  "
              f"occupancy_imbalance={ss['occupancy_imbalance']:.3f}")
        s = stats
        print(f"[serve] migrations={s.migrations} "
              f"migrated_pages={s.migrated_pages} "
              f"wire_bytes={s.migrated_bytes_compressed:.0f} "
              f"rebalance_events={s.rebalance_events}")
    if args.fault_seed is not None or args.ttl_ticks is not None:
        s = stats
        print(f"[serve] faults={s.faults_injected} recoveries={s.recoveries} "
              f"preemptions={s.preemptions} retries={s.retries} "
              f"timeouts={s.timeouts} "
              f"mean_recovery_ticks={s.summary()['mean_recovery_ticks']:.1f}")
        hs = getattr(eng, "health_summary", lambda: None)()
        if hs is not None:
            print(f"[serve] shard health: {hs['state']}")


if __name__ == "__main__":
    main()
