#!/usr/bin/env python3
"""Serve smollm-360m at its published widths on a TPU, and check the result.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the sharded engine on four chips

Everything runs in this one process, which starts no other: a chip belongs
to the process that first touched JAX.

One chip:
  a. `ServeEngine` with its defaults — paged KV, chunked prefill, prefix
     cache, 32-token pages, 2-page chunks — at 8 slots x max_len 2048, twice:
     bf16 weights with bf16 KV, and int8 weights with int8 KV. 16 seeded
     requests with prompts of 64-1024 tokens and 32 new tokens each; two of
     them reuse the first one's page-aligned prefix, so a prefix hit and a
     copy-on-write clone run on the chip.
  b. Every greedy token is checked against the plain model: no cache, no
     kernels, float32 at "highest" matmul precision, over the prompt plus
     the tokens emitted before it (`reference_logits_fn`). A token may differ
     from the reference argmax only where the reference puts it within the
     run's stated tolerance (`RUNS`) of its top logit.
  c. The engine's compiled decode and chunk programs must contain the Pallas
     kernels of that configuration (`tpu_custom_call`).

Four chips (`--chips 4`): `ShardedServeEngine` over `make_serve_mesh(4)` at
the same widths and traffic, bf16, against the single-host `ServeEngine` on
one of those chips, plus one seeded drain (`FaultPlan`) that forces a live
page migration. All three token streams must be identical.

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
Without a TPU the script exits non-zero and prints no result. Peak memory,
compile and wall seconds are printed as set-up facts, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import ExecOptions, build_model, transformer  # noqa: E402
from repro.models.common import softcap  # noqa: E402
from repro.models.quantized import is_quantized, quantize_params  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

ARCH = "smollm-360m"
N_SLOTS, MAX_LEN, NEW_TOKENS, N_REQUESTS = 8, 2048, 32, 16
PROMPT_LENS = (64, 1024)
PAGE_SIZE = 32          # ServeEngine's default page size
SEED = 0
# (wdtype, kv_dtype, logit tolerance) of the two one-chip runs. A token may
# differ from the reference argmax only where the reference's logit of that
# token lies within the tolerance of its top logit. Against this float32
# reference, forwards at the engine's precision move a single logit by at
# most 0.046 (bf16 weights + KV) and 0.072 (int8 weights + KV), measured at
# width 960 with 16 layers on CPU (PERF.md); a flip needs the errors of two
# logits to close the gap, so the tolerance is about twice that. A tie
# closer than this is decided by rounding, not by the model; an engine that
# lost a precision step (int8 KV in the bf16 run, a wrong scale) moves
# logits further and fails.
RUNS = (("bf16", "bf16", 0.10), ("int8", "int8", 0.15))
# the Pallas kernels each compiled program must hold (int8 weights add
# int8_matmul to both)
PROGRAM_KERNELS = {"decode": ("decode_attention",),
                   "chunk": ("flash_attention_paged",)}
KERNELS = ("decode_attention", "flash_attention_paged", "int8_matmul")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


class CompileClock:
    """Backend compile seconds and persistent-cache hits in this process."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------------ traffic
def make_prompts(vocab: int, *, n_requests: int, lens: Sequence[int],
                 page_size: int, seed: int) -> List[np.ndarray]:
    """Seeded prompts with lengths in [lens[0], lens[1]]. Request 0 is a
    page-aligned base prompt; request 1 is its first half (every page
    cached once request 0 registers: a full hit whose tail page is
    copy-on-write cloned); request 2 shares that half and adds a fresh tail
    (a partial hit). The rest are independent."""
    rng = np.random.default_rng(seed)
    base_pages = (lens[0] + lens[1]) // 2 // page_size
    base = rng.integers(0, vocab, base_pages * page_size, dtype=np.int32)
    half = base[:base_pages // 2 * page_size]
    prompts = [base, half, np.concatenate(
        [half, rng.integers(0, vocab, page_size + 7, dtype=np.int32)])]
    while len(prompts) < n_requests:
        n = int(rng.integers(lens[0], lens[1] + 1))
        prompts.append(rng.integers(0, vocab, n, dtype=np.int32))
    return prompts


def serve(engine, prompts: Sequence[np.ndarray], new_tokens: int):
    """Submit every prompt, run the engine dry, return the token streams."""
    reqs = [engine.submit(p, max_new_tokens=new_tokens) for p in prompts]
    engine.run_to_completion()
    streams = [list(r.out_tokens) for r in reqs]
    short = [r.rid for r, s in zip(reqs, streams)
             if not r.done or len(s) != new_tokens]
    if short:
        raise SmokeFailure(f"requests {short} did not emit {new_tokens} "
                           "tokens")
    return streams


def init_params(model, seed: int = SEED):
    """Seeded random weights at a trained model's scale. The schema's init
    takes a weight's fan-in from its second-to-last axis, which for the
    (d_model, heads, head_dim) and (heads, head_dim, d_model) attention
    projections is a head axis: at these widths q and k come out ~8x too
    large, every softmax saturates on one key, and a bf16 rounding then
    changes which key is attended — no engine with bf16 activations could
    match any reference token for token. The same seeded draws are rescaled
    to their true fan-in (d_model in; heads x head_dim out)."""
    cfg = model.cfg
    params = model.init(jax.random.key(seed))
    layers = dict(params["layers"])
    for key in ("wq", "wk", "wv"):
        w = layers[key]
        layers[key] = (w * (w.shape[-2] / cfg.d_model) ** 0.5).astype(w.dtype)
    w = layers["wo"]
    layers["wo"] = (w * cfg.n_heads_padded ** -0.5).astype(w.dtype)
    return dict(params, layers=layers)


# -------------------------------------------------------------- reference
def reference_params(params, cfg, wdtype: str):
    """The weights the engine serves, as float32: bf16 weights upcast
    exactly; int8 ones dequantized from the very ints the engine uses."""
    if wdtype == "int8":
        params = jax.tree.map(
            lambda w: (w["int8_q"].astype(jnp.float32) * w["s"]
                       if is_quantized(w) else w),
            quantize_params(params, cfg), is_leaf=is_quantized)
    else:
        params = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    return jax.tree.map(lambda w: w.astype(jnp.float32), params)


def reference_logits_fn(model, kv_dtype: str) -> Callable:
    """jit'd (params, tokens (1, S), positions (P,)) -> (P, vocab) logits of
    the plain model: the no-cache prefill forward (`forward_hidden`, the
    body of `transformer.prefill`, with jnp attention and no kernels), read
    at every listed position — causal attention makes position p's logits
    those of a prefill over tokens[:p+1], and trailing padding never reaches
    them. K/V round through the configured storage dtype, as `prefill` does
    for a lossy cache (`kv_round`): that is part of what the engine serves."""
    cfg, opts = model.cfg, model.opts
    kv_round = {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]

    def logits_at(params, tokens, positions):
        with jax.default_matmul_precision("highest"):
            hidden, _ = transformer.forward_hidden(
                params, tokens, cfg, opts, mode="prefill", kv_round=kv_round)
            logits = jnp.einsum(
                "pd,vd->pv", hidden[0, positions],
                transformer.lm_head_weights(params, cfg))
        return softcap(logits, cfg.logit_softcap)[:, :cfg.vocab_size]

    return jax.jit(logits_at)


def check_streams(logits_fn, params32, prompts, streams, *, seq_len: int,
                  tol: float) -> Dict[str, float]:
    """Teacher-forced comparison of every emitted token with the reference
    argmax. `gap` is the reference's top logit minus its logit of the
    engine's token (0 where they agree)."""
    n_tok = n_dis = n_bad = 0
    worst = 0.0
    for prompt, out in zip(prompts, streams):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        toks = np.zeros((1, seq_len), np.int32)
        toks[0, :seq.shape[0]] = seq
        pos = np.arange(prompt.shape[0] - 1, prompt.shape[0] - 1 + len(out),
                        dtype=np.int32)
        ref = np.asarray(logits_fn(params32, jnp.asarray(toks),
                                   jnp.asarray(pos)), np.float64)
        top = ref.argmax(axis=-1)
        for i, tok in enumerate(out):
            n_tok += 1
            if tok == top[i]:
                continue
            gap = float(ref[i, top[i]] - ref[i, tok])
            n_dis += 1
            n_bad += gap > tol
            worst = max(worst, gap)
    return {"tokens": n_tok, "disagreements": n_dis,
            "beyond_tolerance": n_bad, "max_gap": worst}


# ----------------------------------------------------------------- kernels
def kernels_in(hlo_text: str) -> List[str]:
    """Pallas kernels (`tpu_custom_call`) in compiled HLO text, by name."""
    found = set()
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line:
            names = [k for k in KERNELS if k in line]
            found.update(names or ["unnamed"])
    return sorted(found)


def engine_programs(engine: ServeEngine) -> Dict[str, str]:
    """Compiled HLO text of the engine's decode and chunk programs, lowered
    at the shapes its serving loop feeds them."""
    n = engine.n_slots
    decode = engine._decode_jit.lower(
        engine.params, {"tokens": jnp.zeros((n, 1), jnp.int32)},
        engine._cache, jnp.zeros((n,), bool))
    chunk = engine._chunk_jit.lower(
        engine.params,
        {"tokens": jnp.zeros((1, engine.chunk_tokens), jnp.int32),
         "start": jnp.zeros((1,), jnp.int32),
         "length": jnp.zeros((1,), jnp.int32),
         "page_row": jnp.zeros((engine.pages_per_seq,), jnp.int32)},
        engine._cache)
    return {"decode": decode.compile().as_text(),
            "chunk": chunk.compile().as_text()}


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ phases
def setup(cfg, n_requests: int, prompt_lens: Sequence[int]):
    """(model, seeded params, seeded prompts) shared by every phase."""
    model = build_model(cfg, ExecOptions(attn_impl="reference"))
    prompts = make_prompts(cfg.vocab_size, n_requests=n_requests,
                           lens=prompt_lens, page_size=PAGE_SIZE, seed=SEED)
    return model, init_params(model), prompts


def one_chip_phases(cfg, *, n_slots: int = N_SLOTS, max_len: int = MAX_LEN,
                    new_tokens: int = NEW_TOKENS,
                    n_requests: int = N_REQUESTS,
                    prompt_lens: Sequence[int] = PROMPT_LENS,
                    require_kernels: bool = True,
                    clock: Optional[CompileClock] = None,
                    log: Callable[[str], None] = print) -> Dict[str, dict]:
    """Phases a-c for each (wdtype, kv_dtype) of RUNS; raises SmokeFailure
    on a wrong result, returns per-run summaries."""
    model, params, prompts = setup(cfg, n_requests, prompt_lens)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; {len(prompts)} requests, "
        f"prompts {min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
        f"{new_tokens} new each; {n_slots} slots x max_len {max_len}")
    out = {}
    for wdtype, kv_dtype, tol in RUNS:
        label = f"w={wdtype},kv={kv_dtype}"
        t0 = time.perf_counter()
        c0 = clock.compile_s if clock else 0.0
        engine = ServeEngine(model, params=params, n_slots=n_slots,
                             max_len=max_len, wdtype=wdtype,
                             kv_dtype=kv_dtype)
        streams = serve(engine, prompts, new_tokens)
        st = engine.stats
        log(f"[a] {label}: {st.tokens_out} tokens, {st.prefill_chunks} "
            f"prefill chunks, {st.decode_steps} decode steps, prefix hits "
            f"{st.prefix_hits} ({st.prefix_hit_tokens} tokens), COW clones "
            f"{st.cow_copies}, compiles decode/chunk "
            f"{st.decode_compiles}/{st.chunk_compiles}")
        if st.prefix_hits < 2 or st.cow_copies < 1:
            raise SmokeFailure(f"{label}: traffic missed the prefix cache "
                               f"(hits {st.prefix_hits}, COW "
                               f"{st.cow_copies})")
        programs = engine_programs(engine)
        found = {name: kernels_in(text) for name, text in programs.items()}
        del programs
        wall = time.perf_counter() - t0
        log(f"[c] {label}: Pallas kernels in decode program: "
            f"{found['decode'] or 'none'}; in chunk program: "
            f"{found['chunk'] or 'none'}")
        log(f"setup {label}: peak_bytes_in_use {peak_bytes()}, compile_s "
            f"{(clock.compile_s - c0) if clock else 'not measured'}, wall_s "
            f"{wall} (set-up facts, not benchmark metrics)")
        del engine
        gc.collect()
        if require_kernels:
            for name, kernels in PROGRAM_KERNELS.items():
                need = kernels + (("int8_matmul",) if wdtype == "int8"
                                  else ())
                missing = [k for k in need if k not in found[name]]
                if missing:
                    raise SmokeFailure(f"{label}: {name} program lacks "
                                       f"Pallas kernels {missing}")
        check = check_streams(
            reference_logits_fn(model, kv_dtype),
            reference_params(params, cfg, wdtype), prompts, streams,
            seq_len=max_len, tol=tol)
        log(f"[b] {label}: {check['tokens']} tokens vs the float32 no-cache "
            f"reference: {check['disagreements']} disagreements, "
            f"{check['beyond_tolerance']} beyond tolerance {tol}, "
            f"largest logit gap {check['max_gap']}")
        if check["beyond_tolerance"]:
            raise SmokeFailure(f"{label}: {check['beyond_tolerance']} tokens "
                               f"disagree with the reference by more than "
                               f"{tol}")
        out[label] = {"stats": st.summary(), "kernels": found,
                      "check": check}
    return out


def four_chip_phase(cfg, *, n_slots: int = N_SLOTS, max_len: int = MAX_LEN,
                    new_tokens: int = NEW_TOKENS,
                    n_requests: int = N_REQUESTS,
                    prompt_lens: Sequence[int] = PROMPT_LENS,
                    log: Callable[[str], None] = print) -> Dict[str, object]:
    """Sharded engine over 4 devices vs the single-host engine, fault-free
    and through one seeded drain that migrates live pages."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serve.faults import FaultEvent, FaultPlan
    from repro.serve.sharded import ShardedServeEngine

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    model, params, prompts = setup(cfg, n_requests, prompt_lens)
    kw = dict(n_slots=n_slots, max_len=max_len, params=params,
              wdtype="bf16", kv_dtype="bf16")
    single = serve(ServeEngine(model, **kw), prompts, new_tokens)
    log(f"[4] single-host ServeEngine on {jax.devices()[0]}: "
        f"{sum(map(len, single))} tokens")

    mesh = make_serve_mesh(4)
    sharded = ShardedServeEngine(model, mesh=mesh, **kw)
    pool_devices = sorted(s.device.id for s in
                          sharded._pools["k"].addressable_shards)
    param_devices = sorted(d.id for d in
                           jax.tree.leaves(sharded.params)[0].sharding
                           .device_set)
    log(f"[4] sharded pools on devices {pool_devices}, params on "
        f"{param_devices}")
    if len(set(pool_devices)) != 4 or len(param_devices) != 4:
        raise SmokeFailure("sharded engine did not spread over 4 devices")
    # step the fault-free run by hand to find a drain tick: the queue has
    # been empty for two ticks (a DEGRADED shard takes no placements, so a
    # fault during admission would change the schedule), shard 1 holds live
    # slots, and other shards have free slots to migrate them into
    reqs = [sharded.submit(p, max_new_tokens=new_tokens) for p in prompts]
    per = sharded.slots_per_shard
    drain_at, tick, empty_for = None, 0, 0
    while sharded._sched.queue or any(r is not None for r in sharded._slots):
        sharded.step()
        tick += 1
        empty_for = 0 if sharded._sched.queue else empty_for + 1
        busy = [sum(sharded._slots[s * per + i] is not None
                    for i in range(per)) for s in range(4)]
        room = sum(per - b for s, b in enumerate(busy) if s != 1)
        if drain_at is None and empty_for >= 3 and 0 < busy[1] <= room:
            drain_at = tick - 1     # DRAINING lands 2 ticks after injection
    fault_free = [list(r.out_tokens) for r in reqs]
    if drain_at is None:
        raise SmokeFailure("no tick left shard 1 live with room elsewhere")
    plan = FaultPlan(events=(FaultEvent(tick=drain_at, kind="sensor_hot",
                                        shard=1, delta_c=60.0, ticks=8),))
    drained = ShardedServeEngine(model, mesh=mesh, fault_plan=plan, **kw)
    drain_streams = serve(drained, prompts, new_tokens)
    st = drained.stats
    log(f"[4] drain of shard 1 at tick {drain_at}: migrations "
        f"{st.migrations}, migrated pages {st.migrated_pages}, recoveries "
        f"{st.recoveries}")
    if st.migrations < 1:
        raise SmokeFailure("the drain migrated no live slot")
    mism = [i for i, (a, b, c) in
            enumerate(zip(single, fault_free, drain_streams))
            if not a == b == c]
    log(f"[4] token streams single-host vs sharded vs sharded+drain: "
        f"{len(prompts) - len(mism)}/{len(prompts)} identical")
    if mism:
        raise SmokeFailure(f"requests {mism} differ across engines")
    return {"drain_tick": drain_at, "migrations": st.migrations,
            "pool_devices": pool_devices}


# -------------------------------------------------------------------- main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-engine phase on 4 chips")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            four_chip_phase(cfg)
        else:
            one_chip_phases(cfg, clock=clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"setup total: compile_s {clock.compile_s}, persistent-cache hits "
          f"{clock.cache_hits}, wall_s {time.perf_counter() - t0} (set-up "
          f"facts, not benchmark metrics)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
