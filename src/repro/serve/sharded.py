"""Sharded multi-chiplet serving: slot- and page-partitioned engine (PR 5).

The paper's scale-out story (§II: dual NPU chiplets behind an AI-aware UCIe
interconnect) as a serving runtime: `ShardedServeEngine` partitions the
decode batch's slots AND the paged KV pool across a mesh axis (the
production mesh's 'data' axis — one shard per chiplet/device) via
`jax.shard_map`, so the whole fleet decodes in ONE jitted global
step while every byte of KV traffic stays on the device that owns it.

Layout invariants (what makes this GSPMD-proof instead of GSPMD-hostile):
  * **Contiguous page ranges per device.** The global K/V pools are
    (L, n_shards · n_pages, <page>), sharded on the page axis (axis 1 in
    either page format: (page_size, KV, D), or lane-dense (R, 128) for
    float GQA pools) — each device physically owns pages
    [shard·n_pages, (shard+1)·n_pages).
    Inside `shard_map` a device sees only its local (L, n_pages, ...) pool.
  * **Device-local page tables.** Table entries are LOCAL page ids
    (0..n_pages-1; local page 0 is each shard's null page). A slot's pages
    are reserved from its own shard's free list only, so the decode kernel's
    scalar-prefetch gathers (kernels/decode_attention.paged_index_maps,
    layer_page_map) and
    the chunk-prefill pool writes are local by construction — never a
    cross-device gather, which is exactly what the paged pool's scatter
    write pattern would otherwise force GSPMD to emit collectives for
    (ROADMAP: "a sharded pool wants pages partitioned by device with
    device-local tables").
  * **Tokens are the only per-step collective.** The global decode step runs
    per-shard decode attention + sampling under `shard_map` and all-gathers
    only the emitted (n_slots,) int32 tokens. Page tables and stream
    positions are HOST-authoritative (small int32 arrays fed in per tick),
    so there is no per-step cache sync at all and window-recycling needs no
    device-side remap programs.
  * **Weights are shard-stationary.** Params are replicated across the slot
    axis (the `serve_sharded` plan in parallel/sharding.py: the weight-
    stationary placement of `serve_ws` with the slot axis retired from every
    param rule — nothing is gathered per step). Intra-shard tensor
    parallelism over a 'model' axis inside shard_map needs manual
    collectives and is a recorded follow-on.

Admission runs through `serve/scheduler.ShardScheduler`: per-shard free
lists, least-loaded placement, and per-shard interleaved chunk prefill — a
long prompt admitted to one shard costs only that shard a chunk per tick, so
it can never stall decode on another shard.

Token parity: per-request token streams are schedule-independent (PR 4
pinned chunk-size/batch-composition invariance; sampling is keyed by
(request seed, token index)), so this engine is token-IDENTICAL to the
single-host `ServeEngine` for the same submissions — the equivalence
`tests/test_sharded_serve.py` pins on an 8-device CPU mesh for dense/moe ×
{f32, int8} KV, windowed configs, and mid-stream retirements.

Live page migration (PR 9): the one deliberate exception to "KV bytes never
cross devices". A shard_map'd move program (gather → all_gather → scatter of
whole physical pages) re-homes a live slot's pool-native bytes between
device-local partitions, so a DRAINING shard's work migrates at O(bytes) —
priced through `core/ucie.transfer`'s closed form, the SAME cost model the
simulator drains — instead of O(FLOPs) re-prefill replay (DEAD shards still
replay: their bytes are gone). The same primitive powers elastic
rebalancing (busy-gap moves + migration-instead-of-preemption) and
cross-shard replication of hot prefix pages; `serve/migration` owns the
planning policy, `ShardScheduler.migrate_slot` the atomic re-homing of
page-table rows, refcounts and registry entries. Migrated tokens stay
bit-exact; the only observable cost is the link hold before the slot's next
decode step.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.sanitizer import register_entry_point
from repro.models.transformer import gather_pool_pages, set_pool_page
from repro.serve.engine import (
    _KV_DTYPES, EngineOverloaded, EngineStats, Request)
from repro.serve.faults import FaultPlan
from repro.serve.health import (
    EVACUATED, Health, HealthConfig, ShardHealthMonitor)
from repro.serve.migration import (
    MigrationConfig, migration_cost, page_payload_bytes,
    plan_prefix_replication, plan_rebalance, plan_starvation_rescue)
from repro.serve.sampling import clamp_sample_params, sample_tokens
from repro.serve.scheduler import ShardScheduler


def _replicated_specs(tree):
    """Full-rank replicated PartitionSpecs matching a pytree of arrays."""
    return jax.tree.map(lambda x: P(*([None] * jnp.ndim(x))), tree)


class ShardedServeEngine:
    """Continuous batching over a device-partitioned paged KV pool.

    API mirrors `ServeEngine` (submit / step / run_to_completion / cancel /
    stats); `n_slots` is the GLOBAL decode batch (must divide by the mesh's
    shard count) and `n_pages` is the PER-SHARD pool size including each
    shard's local null page.
    """

    def __init__(self, model, *, mesh: Mesh, axis: str = "data",
                 n_slots: int = 4, max_len: int = 128, params=None,
                 page_size: int = 32, n_pages: Optional[int] = None,
                 wdtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 chunk_pages: int = 2,
                 prefix_cache: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 ttl_ticks: Optional[int] = None,
                 preempt_after: int = 2,
                 max_preemptions: int = 3,
                 fault_plan: Optional[FaultPlan] = None,
                 health_cfg: Optional[HealthConfig] = None,
                 migration: bool = True,
                 migration_cfg: Optional[MigrationConfig] = None,
                 rebalance_threshold: Optional[int] = None):
        self.model = model
        self.cfg = model.cfg
        if self.cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                "ShardedServeEngine shards paged attention-family caches "
                f"(dense/moe/vlm), not {self.cfg.family!r} (encdec needs a "
                "sharded cross-cache paste — recorded follow-on)")
        if model.prefill_chunk is None:
            raise ValueError("sharded serving requires chunked prefill")
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {dict(mesh.shape)}")
        for a, n in mesh.shape.items():
            if a != axis and n != 1:
                raise ValueError(
                    f"mesh axis {a!r} (size {n}) is unsupported: intra-shard "
                    "tensor parallelism inside the shard_map'd decode step "
                    "needs manual collectives (recorded follow-on) — shard "
                    f"slots over a 1-D {axis!r} mesh (launch/mesh."
                    "make_serve_mesh)")
        self.mesh, self.axis = mesh, axis
        self.n_shards = mesh.shape[axis]
        if n_slots % self.n_shards:
            raise ValueError(f"n_slots {n_slots} must divide over "
                             f"{self.n_shards} shards")
        self.n_slots = n_slots
        self.slots_per_shard = n_slots // self.n_shards
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} % page_size {page_size} != 0")
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_seq = max_len // page_size

        if wdtype not in (None, "bf16", "int8"):
            raise ValueError(f"wdtype must be None/'bf16'/'int8', got {wdtype!r}")
        if wdtype == "int8":
            from repro.models.quantized import quantize_params
            params = quantize_params(params, self.cfg)
        elif wdtype == "bf16":
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        self.wdtype = wdtype
        if kv_dtype not in _KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
        self.kv_dtype = _KV_DTYPES[kv_dtype]
        # shard-stationary weights: placed by the serve_sharded plan (the
        # slot axis is retired from every param rule, so on a 1-D slot mesh
        # everything resolves to a replica per shard — one device_put at
        # init, never a per-step gather). Quantized pytrees ({int8_q, s}
        # leaves) no longer match the schema the plan maps over, and their
        # plan-resolved placement is replication anyway — place directly.
        from repro.parallel import sharding as sh
        if wdtype == "int8":
            param_specs = _replicated_specs(params)
        else:
            param_specs = sh.schema_pspecs(
                model.schema, mesh, sh.rules_for_plan("serve_sharded"))
        self.params = jax.device_put(params, sh.named(mesh, param_specs))
        self._param_specs = param_specs

        self._window = self.cfg.window or 0
        # windowed slots chunk one page at a time (the single-host invariant:
        # the ceil(window/page)+2 reservation must cover the chunk write-ahead)
        self.chunk_pages = 1 if self._window else max(1, int(chunk_pages))
        self.chunk_tokens = self.chunk_pages * page_size
        # per-shard pool: local null page + worst case for the shard's slots
        self.n_pages = (1 + self.slots_per_shard * self.pages_per_seq
                        if n_pages is None else n_pages)
        assert self.n_pages >= 2, self.n_pages

        # prefix cache (PR 8): per-shard ref-counted content registries;
        # default on, silently off under a sliding window (recycling
        # rewrites remapped pages in place — incompatible with sharing)
        self.prefix_cache = (not self._window) if prefix_cache is None \
            else (bool(prefix_cache) and not self._window)
        self._sched = ShardScheduler(
            n_shards=self.n_shards, slots_per_shard=self.slots_per_shard,
            n_pages=self.n_pages, page_size=page_size,
            pages_per_seq=self.pages_per_seq, max_len=max_len,
            chunk_tokens=self.chunk_tokens, window=self._window,
            prefix_cache=self.prefix_cache)

        self.stats = EngineStats()
        # ---- fault tolerance & backpressure (PR 6) -------------------------
        self.max_queue = max_queue
        self.ttl_ticks = ttl_ticks
        self.preempt_after = max(1, int(preempt_after))
        self.max_preemptions = max(0, int(max_preemptions))
        self.fault_plan = fault_plan
        # the health monitor (and its thermal/DVFS sensor integration) only
        # exists when fault injection or health tracking is requested — the
        # default engine path stays bit-identical to the pre-fault engine
        self._monitor = (ShardHealthMonitor(self.n_shards, health_cfg)
                         if fault_plan is not None or health_cfg is not None
                         else None)
        self._tick = 0               # engine tick counter (fault/TTL clock)
        self._starved = 0            # consecutive page-starved ticks
        self._any_ttl = ttl_ticks is not None
        self._recover_started: Dict[int, int] = {}  # rid -> requeue tick
        # ---- live page migration over UCIe (PR 9) --------------------------
        self._mig_cfg = migration_cfg or MigrationConfig()
        if rebalance_threshold is not None:
            self._mig_cfg = dataclasses.replace(
                self._mig_cfg, rebalance_threshold=int(rebalance_threshold))
        self._migration = bool(migration)
        # per-slot link hold: a migrated slot's pages are "on the wire" for
        # migration_ticks(bytes, UCIeConfig) engine ticks — it neither
        # decodes nor chunks until the modeled transfer lands
        self._hold = np.zeros((n_slots,), np.int32)
        self._resume_live = [False] * n_slots
        self._replica_hold: Optional[Tuple[int, int]] = None  # (rid, ticks)
        self.shard_tokens = [0] * self.n_shards
        self.shard_occupancy_sum = [0.0] * self.n_shards
        self._slots: List[Optional[Request]] = [None] * n_slots
        self._fresh = [False] * n_slots
        self._next_rid = 0
        # HOST-authoritative per-slot state, fed to the device programs each
        # tick (device-local LOCAL page ids; null rows for free/mid-prefill
        # slots so decode's garbage writes land on each shard's null page)
        self._page_table = np.zeros((n_slots, self.pages_per_seq), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        self._active = np.zeros((n_slots,), bool)
        self._next_tok = np.zeros((n_slots, 1), np.int32)
        self._temp = np.zeros((n_slots,), np.float32)
        self._topk = np.zeros((n_slots,), np.int32)
        self._topp = np.ones((n_slots,), np.float32)
        self._sseed = np.zeros((n_slots,), np.int32)

        # ---- device-partitioned pools --------------------------------------
        abs_cache = model.cache_shape(
            n_slots, max_len, self.kv_dtype, page_size=page_size,
            n_pages=self.n_shards * self.n_pages)
        pool_keys = [k for k in abs_cache if k not in ("page_table", "pos")]
        ax = self.axis

        def _pool_spec(sds):
            # pools are (L, pages, ...) — pages partitioned over the shard
            # axis, each device owning one contiguous local range
            return P(None, ax, *([None] * (len(sds.shape) - 2)))

        self._pool_specs = {k: _pool_spec(abs_cache[k]) for k in pool_keys}
        self._pools = {
            k: jax.device_put(
                jnp.zeros(abs_cache[k].shape, abs_cache[k].dtype),
                NamedSharding(mesh, self._pool_specs[k]))
            for k in pool_keys}

        # ---- the jitted global programs ------------------------------------
        vocab = self.cfg.vocab_size
        pspecs = self._param_specs

        def _decode_core(params, tokens, pools, pt, pos):
            cache = dict(pools, page_table=pt, pos=pos)
            logits, new_cache = model.decode(params, {"tokens": tokens}, cache)
            return (logits[:, -1, :vocab],
                    {k: new_cache[k] for k in pools})

        def _decode_greedy(params, tokens, pools, pt, pos):
            self.stats.decode_compiles += 1     # trace time only
            lv, new_pools = _decode_core(params, tokens, pools, pt, pos)
            return jnp.argmax(lv, axis=-1).astype(jnp.int32), new_pools

        def _decode_sample(params, tokens, pools, pt, pos, sample):
            self.stats.decode_compiles += 1
            lv, new_pools = _decode_core(params, tokens, pools, pt, pos)
            toks = sample_tokens(
                lv.astype(jnp.float32),
                sample["temperature"], sample["top_k"], sample["top_p"],
                sample["seed"], sample["counter"])
            return toks, new_pools

        tok_spec = P(ax, None)
        pt_spec = P(ax, None)
        vec_spec = P(ax)
        sample_specs = {k: vec_spec for k in
                        ("temperature", "top_k", "top_p", "seed", "counter")}

        self._decode_jit = jax.jit(jax.shard_map(
            _decode_greedy, mesh=mesh,
            in_specs=(pspecs, tok_spec, self._pool_specs, pt_spec, vec_spec),
            out_specs=(vec_spec, self._pool_specs), check_vma=False),
            donate_argnums=(2,))
        self._decode_sample_jit = jax.jit(jax.shard_map(
            _decode_sample, mesh=mesh,
            in_specs=(pspecs, tok_spec, self._pool_specs, pt_spec, vec_spec,
                      sample_specs),
            out_specs=(vec_spec, self._pool_specs), check_vma=False),
            donate_argnums=(2,))

        def _chunk(params, batch, pools):
            self.stats.chunk_compiles += 1      # trace time only
            sub = {"tokens": batch["tokens"], "start": batch["start"],
                   "length": batch["length"],
                   "page_row": batch["page_row"][0]}
            if self.cfg.family == "vlm":
                sub["patch_rows"] = batch["patch_rows"]
                sub["n_patch"] = batch["n_patch"]
            new_cache = model.prefill_chunk(params, sub, dict(pools))
            return {k: new_cache[k] for k in pools}

        chunk_specs = {"tokens": P(ax, None), "start": vec_spec,
                       "length": vec_spec, "page_row": P(ax, None)}
        if self.cfg.family == "vlm":
            chunk_specs["patch_rows"] = P(ax, None, None)
            chunk_specs["n_patch"] = vec_spec
        self._chunk_specs = chunk_specs
        self._chunk_jit = jax.jit(jax.shard_map(
            _chunk, mesh=mesh,
            in_specs=(pspecs, chunk_specs, self._pool_specs),
            out_specs=self._pool_specs, check_vma=False),
            donate_argnums=(2,))

        def _cow(pools, src, dst):
            # COW tail clone, one (src, dst) pair per shard, LOCAL page
            # ids. Shards with no clone this round pass src=dst=0: copying
            # the null page onto itself is a no-op by construction
            return {k: p.at[:, dst[0]].set(p[:, src[0]])
                    for k, p in pools.items()}

        self._cow_jit = jax.jit(jax.shard_map(
            _cow, mesh=mesh,
            in_specs=(self._pool_specs, vec_spec, vec_spec),
            out_specs=self._pool_specs, check_vma=False),
            donate_argnums=(0,))

        # move_pool_pages (PR 9): one wave moves up to `wave_moves` pages
        # per shard between device-local pools. Each shard snapshots its
        # exports (outbox) BEFORE any write, the outboxes cross the mesh in
        # ONE all_gather — the modeled UCIe transfer — and each shard
        # scatters its imports into freshly-allocated local pages. Pools
        # move their NATIVE bytes: an int8 pool's int8 rows + bf16 scales
        # are its block-compressed wire format (half the bf16 bytes), so
        # migrated pages stay bit-exact. Unused rows are 0 on both sides —
        # exporting and importing the null page are no-ops by contract.
        M = self._mig_cfg.wave_moves

        def _move(pools, out_idx, in_shard, in_slot, in_dst):
            ob = gather_pool_pages(pools, out_idx[0])
            gath = {k: jax.lax.all_gather(v, ax) for k, v in ob.items()}
            for m in range(M):
                rows = {k: gath[k][in_shard[0, m], :, in_slot[0, m]]
                        for k in gath}
                pools = set_pool_page(pools, in_dst[0, m], rows)
            return pools

        mspec = P(ax, None)
        self._move_jit = jax.jit(jax.shard_map(
            _move, mesh=mesh,
            in_specs=(self._pool_specs, mspec, mspec, mspec, mspec),
            out_specs=self._pool_specs, check_vma=False),
            donate_argnums=(0,))
        self._page_bytes = page_payload_bytes(self._pools)
        # retrace-sanitizer labels (analysis/sanitizer): the sharded engine
        # shares the single-host labels so COMPILE_BUDGETS apply unchanged,
        # plus "move" for the migration wave program
        register_entry_point("decode", self._decode_jit)
        register_entry_point("decode", self._decode_sample_jit)
        register_entry_point("chunk", self._chunk_jit)
        register_entry_point("move", self._move_jit)
        if self.prefix_cache:
            # Warm the COW clone at construction — its first use is the
            # first prefix-cache hit, which would otherwise stall every
            # shard on an XLA compile mid-serving (steady-state retrace
            # gate). All-null src=dst=0 is the documented no-op round.
            z = jnp.zeros((self.n_shards,), jnp.int32)
            self._pools = self._cow_jit(self._pools, z, z)

    # ------------------------------------------------------------- lifecycle
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               extras: Optional[Dict[str, np.ndarray]] = None,
               sample_params: Optional[tuple] = None,
               seed: int = 0, ttl_ticks: Optional[int] = None) -> Request:
        """Queue a request — the single-host contract: malformed requests
        raise ValueError (nothing enqueued), a full queue raises
        EngineOverloaded (graceful backpressure)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(
                f"prompt must be a 1-D token array, got shape {prompt.shape}")
        if prompt.shape[0] < 1:
            raise ValueError("prompt must hold at least one token")
        if prompt.shape[0] > self.max_len:
            raise ValueError(
                f"prompt length {prompt.shape[0]} exceeds engine max_len "
                f"{self.max_len}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        need = self._sched.pages_for(prompt.shape[0], max_new_tokens)
        if need > self.n_pages - 1:
            raise ValueError(f"request needs {need} pages; each shard's pool "
                             f"has {self.n_pages - 1}")
        if self.max_queue is not None \
                and len(self._sched.queue) >= self.max_queue:
            self.stats.rejected += 1
            raise EngineOverloaded(
                f"admission queue at cap ({self.max_queue}); retry later")
        temperature, top_k, top_p = 0.0, 0, 1.0
        if sample_params is not None:
            temperature, top_k, top_p = clamp_sample_params(*sample_params)
        self._next_rid += 1
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, extras=extras,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      seed=int(seed), t_enqueue=time.perf_counter(),
                      submit_tick=self._tick, ttl_ticks=ttl_ticks)
        if ttl_ticks is not None:
            self._any_ttl = True
        self._sched.queue.append(req)
        return req

    def cancel(self, req: Request) -> None:
        """Retire a request at any stage: queued → dequeue; mid-prefill →
        drain its chunk queue and free every reserved page; decoding →
        release the slot. Pool accounting is exact in all three."""
        if req.done:
            return
        if req in self._sched.queue:
            self._sched.queue.remove(req)
        else:
            at = self._sched.find(req)
            if at is not None:
                self._release(at[0] * self.slots_per_shard + at[1])
        req.done = True
        req.t_done = time.perf_counter()

    def _gslot(self, shard: int, slot: int) -> int:
        return shard * self.slots_per_shard + slot

    def _release(self, g: int):
        shard, slot = divmod(g, self.slots_per_shard)
        self._sched.release(shard, slot)
        self._slots[g] = None
        self._active[g] = False
        self._fresh[g] = False
        self._page_table[g] = 0         # back on the shard's null page
        self._temp[g], self._topk[g] = 0.0, 0
        self._topp[g], self._sseed[g] = 1.0, 0
        self._hold[g] = 0
        self._resume_live[g] = False
        self.stats.pages_in_use = self._sched.pages_in_use

    def kv_cache_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in self._pools.values())

    def assert_local_page_tables(self) -> None:
        """The zero-cross-device-reference invariant: every page-table entry
        is a LOCAL id addressing its own shard's pool partition."""
        self._sched.assert_local()
        assert int(self._page_table.max(initial=0)) < self.n_pages, \
            self._page_table.max()
        assert int(self._page_table.min(initial=0)) >= 0

    # ---------------------------------------------------------------- prefill
    def _prefill_tick(self) -> bool:
        work = self._sched.next_chunks()
        # held slots (mid-migration) don't chunk: their pages are on the wire
        work = [w for w in work
                if not self._hold[self._gslot(w.shard, w.slot)]]
        if not work:
            return False
        S, C = self.n_shards, self.chunk_tokens
        tokens = np.zeros((S, C), np.int32)
        start = np.zeros((S,), np.int32)
        length = np.zeros((S,), np.int32)
        page_rows = np.zeros((S, self.pages_per_seq), np.int32)
        batch = {"tokens": tokens, "start": start, "length": length,
                 "page_row": page_rows}
        if self.cfg.family == "vlm":
            batch["patch_rows"] = np.zeros((S, C, self.cfg.d_model),
                                           np.float32)
            batch["n_patch"] = np.zeros((S,), np.int32)
        for w in work:
            lp = w.req.live_prompt()   # resumed requests re-prefill emitted tokens
            tokens[w.shard, :w.length] = lp[w.start:w.start + w.length]
            start[w.shard] = w.start
            length[w.shard] = w.length
            page_rows[w.shard] = self._sched.page_row(w.shard, w.slot)
            if self.cfg.family == "vlm":
                pe = np.asarray((w.req.extras or {}).get(
                    "patch_embeds",
                    np.zeros((0, self.cfg.d_model), np.float32)))
                if w.start < pe.shape[0]:
                    m = min(C, pe.shape[0] - w.start)
                    batch["patch_rows"][w.shard, :m] = pe[w.start:w.start + m]
                batch["n_patch"][w.shard] = pe.shape[0]
        self._pools = self._chunk_jit(
            self.params, {k: jnp.asarray(v) for k, v in batch.items()},
            self._pools)
        self.stats.prefill_chunks += len(work)
        self.stats.prefill_pad_tokens += sum(C - w.length for w in work)
        for w in work:
            self._sched.advance_chunk(w)
            if w.final:
                self._sched.register_prefix(w.shard, w.slot, w.req)
                self._go_live(w.shard, w.slot, w.req)
        return True

    def _go_live(self, shard: int, slot: int, r) -> None:
        """Finalize a prefilled (or fully cache-hit) slot: stamp its
        DEVICE-LOCAL table row and replay position into the
        host-authoritative state."""
        g = self._gslot(shard, slot)
        lp = r.live_prompt()
        self._page_table[g] = self._sched.page_row(shard, slot)
        self._pos[g] = lp.shape[0] - 1
        self._next_tok[g, 0] = int(lp[-1])
        self._fresh[g] = True
        self._active[g] = True
        started = self._recover_started.pop(r.rid, None)
        if started is not None:   # recovered stream back live
            self.stats.recovery_ticks_sum += self._tick - started

    # ----------------------------------------------------------------- decode
    def _place(self, placements) -> None:
        cow_rounds: List[Dict[int, Tuple[int, int]]] = []
        for p in placements:
            g = self._gslot(p.shard, p.slot)
            r = p.req
            self._slots[g] = r
            self._active[g] = False
            self._fresh[g] = False
            self._temp[g], self._topk[g] = r.temperature, r.top_k
            self._topp[g], self._sseed[g] = r.top_p, r.seed
            self.stats.prefills += 1
            self.stats.prefill_tokens += (r.live_prompt().shape[0]
                                          - p.cached_tokens)
            if p.cow is not None:
                # one clone per shard per shard_map round; same-shard clones
                # spill to later rounds preserving placement order
                for rnd in cow_rounds:
                    if p.shard not in rnd:
                        rnd[p.shard] = p.cow
                        break
                else:
                    cow_rounds.append({p.shard: p.cow})
        for rnd in cow_rounds:
            src = np.zeros((self.n_shards,), np.int32)
            dst = np.zeros((self.n_shards,), np.int32)
            for shard, (s_loc, d_loc) in rnd.items():
                src[shard], dst[shard] = s_loc, d_loc
            self._pools = self._cow_jit(self._pools, jnp.asarray(src),
                                        jnp.asarray(dst))
        for p in placements:
            if p.full_hit:
                # every prompt page came from the cache: zero prefill
                # chunks, the slot goes live straight from placement
                self._go_live(p.shard, p.slot, p.req)
            if self._replica_hold is not None \
                    and p.req.rid == self._replica_hold[0]:
                # this admission rode freshly-replicated prefix pages:
                # charge it the modeled UCIe transfer before it proceeds
                g = self._gslot(p.shard, p.slot)
                self._hold[g] = self._replica_hold[1]
                if p.full_hit:
                    self._active[g] = False
                    self._page_table[g] = 0
                    self._resume_live[g] = True
                self._replica_hold = None

    def _sync_prefix_stats(self) -> None:
        sc = self._sched
        st = self.stats
        st.prefix_hits = sc.prefix_hits
        st.prefix_misses = sc.prefix_misses
        st.prefix_hit_tokens = sc.prefix_hit_tokens
        st.prefix_evictions = sc.prefix_evictions
        st.cow_copies = sc.cow_copies
        st.prefix_cached_pages = sum(len(s.lru) for s in sc.shards)

    def step(self) -> bool:
        """One engine tick: advance migration holds, apply scheduled
        faults, advance shard health (DRAINING evacuates by live page
        migration, DEAD by replay), expire TTLs, replicate a hot prefix for
        the queue head if one is remote, admit — rescuing a page-starved
        head by migrating a victim away before falling back to preemption —
        rebalance one busy-gap move, then per-shard chunk prefill and ONE
        global shard_map'd decode step."""
        self._tick += 1
        self._advance_holds()
        if self.fault_plan is not None:
            self._apply_faults()
        if self._monitor is not None:
            self._health_tick()
        if self._any_ttl:
            self._expire_ttl()
        if self._migration:
            self._replicate_prefix()
        self._place(self._sched.admit())
        rebalance = self._migration and self._mig_cfg.rebalance_threshold > 0
        if self._sched.queue:
            head = self._sched.queue[0]
            need = self._sched.pages_for(head.live_prompt().shape[0],
                                         head.remaining_new())
            if self._sched.page_starved(need):
                self._starved += 1
                if rebalance and self._rescue(need):
                    # migration-instead-of-preemption: the head unblocked
                    # without any decoded work being thrown away
                    self._place(self._sched.admit())
                    self._starved = 0
                elif self._starved >= self.preempt_after:
                    cand = self._sched.preempt_candidate(
                        need, head.rid, self.max_preemptions)
                    if cand is not None:
                        self._preempt(*cand)
                        self._place(self._sched.admit())
            else:
                self._starved = 0
        else:
            self._starved = 0
        if rebalance:
            self._rebalance_tick()
        self.stats.pages_in_use = self._sched.pages_in_use
        self.stats.peak_pages_in_use = max(self.stats.peak_pages_in_use,
                                           self.stats.pages_in_use)
        self._sync_prefix_stats()
        chunk_ran = self._prefill_tick()
        decoding = [g for g in range(self.n_slots) if self._active[g]]
        if not decoding:
            return chunk_ran
        args = (self.params, jnp.asarray(self._next_tok), self._pools,
                jnp.asarray(self._page_table), jnp.asarray(self._pos))
        if any(self._temp[g] > 0 for g in decoding):
            counter = np.asarray(
                [len(r.out_tokens) if r is not None else 0
                 for r in self._slots], np.int32)
            sample = {"temperature": jnp.asarray(self._temp),
                      "top_k": jnp.asarray(self._topk),
                      "top_p": jnp.asarray(self._topp),
                      "seed": jnp.asarray(self._sseed),
                      "counter": jnp.asarray(counter)}
            toks, self._pools = self._decode_sample_jit(*args, sample)
        else:
            toks, self._pools = self._decode_jit(*args)
        self.stats.decode_steps += 1
        self.stats.occupancy_sum += len(decoding) / self.n_slots
        for shard in range(self.n_shards):
            busy = sum(1 for g in decoding
                       if g // self.slots_per_shard == shard)
            self.shard_occupancy_sum[shard] += busy / self.slots_per_shard
        nxt = np.asarray(toks, np.int32)     # tokens: the ONLY per-step sync
        self._pos[self._active] += 1         # host-authoritative positions
        for g in decoding:
            r = self._slots[g]
            r.out_tokens.append(int(nxt[g]))
            self._next_tok[g, 0] = nxt[g]
            self.stats.tokens_out += 1
            self.shard_tokens[g // self.slots_per_shard] += 1
            if self._fresh[g]:
                if r.t_first_token is None:   # resumed slots keep the original
                    r.t_first_token = time.perf_counter()
                    r.first_token_tick = self._tick
                self._fresh[g] = False
            if len(r.out_tokens) >= r.max_new_tokens \
                    or int(self._pos[g]) >= self.max_len:
                r.done = True
                r.t_done = time.perf_counter()
                self.stats.record_request(r)
                self._release(g)
        if self._window:
            self._recycle_window_pages()
        return True

    def _recycle_window_pages(self):
        """Slide live slots' windows: scheduler bookkeeping + mirroring the
        remap/unmap events into the host-authoritative page table (the next
        decode tick sees the moved entries — same ordering as the
        single-host engine's post-decode recycling)."""
        for g in range(self.n_slots):
            if self._slots[g] is None or not self._active[g]:
                continue
            shard, slot = divmod(g, self.slots_per_shard)
            if not self._sched.shards[shard].slot_pages[slot]:
                continue
            remaps, unmaps = self._sched.recycle(shard, slot,
                                                 int(self._pos[g]))
            for j_dead, j_new, phys in remaps:
                self._page_table[g, j_dead] = 0
                self._page_table[g, j_new] = phys
            for j_dead in unmaps:
                self._page_table[g, j_dead] = 0
        self.stats.pages_in_use = self._sched.pages_in_use

    # ------------------------------------- live page migration (PR 9)
    def _advance_holds(self):
        """Count down per-slot migration holds; a slot whose hold expires
        (and whose request survived the wait) restamps its page-table row
        from the scheduler and resumes decoding — the link latency the
        `core/ucie` cost model charged is exactly how long it sat out."""
        for g in np.nonzero(self._hold > 0)[0]:
            self._hold[g] -= 1
            if self._hold[g] == 0 and self._resume_live[g] \
                    and self._slots[g] is not None:
                shard, slot = divmod(int(g), self.slots_per_shard)
                self._page_table[g] = self._sched.page_row(shard, slot)
                self._active[g] = True
                self._resume_live[g] = False

    def _device_move(self, moves) -> None:
        """Execute (src_shard, src_phys, dst_shard, dst_phys) page moves on
        device, batched into shard_map'd waves of at most `wave_moves`
        outgoing AND incoming pages per shard. Gather-before-scatter inside
        a wave (every shard snapshots its outbox before any write) and
        freshly-allocated destinations make waves order-independent."""
        M = self._mig_cfg.wave_moves
        S = self.n_shards
        i = 0
        while i < len(moves):
            out_idx = np.zeros((S, M), np.int32)
            in_shard = np.zeros((S, M), np.int32)
            in_slot = np.zeros((S, M), np.int32)
            in_dst = np.zeros((S, M), np.int32)
            out_n = [0] * S
            in_n = [0] * S
            while i < len(moves):
                ss, sp, ds, dp = moves[i]
                if out_n[ss] >= M or in_n[ds] >= M:
                    break
                out_idx[ss, out_n[ss]] = sp
                in_shard[ds, in_n[ds]] = ss
                in_slot[ds, in_n[ds]] = out_n[ss]
                in_dst[ds, in_n[ds]] = dp
                out_n[ss] += 1
                in_n[ds] += 1
                i += 1
            self._pools = self._move_jit(
                self._pools, jnp.asarray(out_idx), jnp.asarray(in_shard),
                jnp.asarray(in_slot), jnp.asarray(in_dst))

    def _migrate_slot(self, src_shard: int, src_slot: int, dst_shard: int,
                      *, count_recovery: bool = False) -> int:
        """Re-home one live slot: scheduler bookkeeping moves atomically
        (`migrate_slot`), the pages fly over the modeled UCIe link via the
        move program, and the destination slot sits held for the link's
        `migration_ticks` before its next decode step. Returns the hold."""
        g_src = self._gslot(src_shard, src_slot)
        r = self._slots[g_src]
        # a slot already on hold (migration/replica wait in flight) keeps
        # its pending go-live across a second move
        was_active = bool(self._active[g_src]) or self._resume_live[g_src]
        prior_hold = int(self._hold[g_src])
        dst_slot, page_moves = self._sched.migrate_slot(
            src_shard, src_slot, dst_shard)
        g_dst = self._gslot(dst_shard, dst_slot)
        self._device_move([(src_shard, sp, dst_shard, dp)
                           for sp, dp in page_moves])
        self._pos[g_dst] = self._pos[g_src]
        self._next_tok[g_dst, 0] = self._next_tok[g_src, 0]
        self._fresh[g_dst] = self._fresh[g_src]
        self._temp[g_dst], self._topk[g_dst] = \
            self._temp[g_src], self._topk[g_src]
        self._topp[g_dst], self._sseed[g_dst] = \
            self._topp[g_src], self._sseed[g_src]
        self._slots[g_dst], self._slots[g_src] = r, None
        self._temp[g_src], self._topk[g_src] = 0.0, 0
        self._topp[g_src], self._sseed[g_src] = 1.0, 0
        self._fresh[g_src] = False
        self._active[g_src] = self._active[g_dst] = False
        self._page_table[g_src] = 0     # back on the source's null page
        self._page_table[g_dst] = 0     # stamped when the hold expires
        self._resume_live[g_dst] = was_active
        self._resume_live[g_src] = False
        ticks, wire = migration_cost(
            len(page_moves) * self._page_bytes, self._mig_cfg)
        self._hold[g_dst] = max(ticks, prior_hold)
        self._hold[g_src] = 0
        self.stats.migrations += 1
        self.stats.migrated_pages += len(page_moves)
        self.stats.migrated_bytes_compressed += wire
        if count_recovery:
            self.stats.recoveries += 1
            self.stats.recovery_ticks_sum += ticks
        self.stats.pages_in_use = self._sched.pages_in_use
        return ticks

    def _movable(self, shard: int, slot: int) -> bool:
        """Planner veto: only settled decoding slots migrate for balance —
        never mid-prefill, never already on the wire."""
        g = self._gslot(shard, slot)
        return bool(self._active[g]) and self._hold[g] == 0

    def _rescue(self, need: int) -> bool:
        """Try migration-instead-of-preemption for a page-starved head."""
        plan = plan_starvation_rescue(self._sched, need,
                                      self._sched.placeable, self._movable)
        if plan is None:
            return False
        self._migrate_slot(*plan)
        self.stats.rebalance_events += 1
        return True

    def _rebalance_tick(self) -> None:
        """One elastic-balance move per tick when the busy-slot gap between
        shards exceeds the configured threshold."""
        plan = plan_rebalance(self._sched, self._mig_cfg.rebalance_threshold,
                              self._sched.placeable, self._movable)
        if plan is not None:
            self._migrate_slot(*plan)
            self.stats.rebalance_events += 1

    def _replicate_prefix(self) -> None:
        """Cross-shard prefix reuse for the queue head: copy a hot remote
        prefix run onto the shard admission will pick, as compressed-UCIe
        page moves instead of local re-prefill. The admission that rides
        the fresh replicas is charged the link time via a hold."""
        if not self._sched.queue or not self._sched.prefix_cache \
                or self._replica_hold is not None:
            return
        r = self._sched.queue[0]
        plan = plan_prefix_replication(self._sched, r, self._mig_cfg,
                                       self._sched.placeable)
        if plan is None:
            return
        src, dst, digests = plan
        moves = []
        for d in digests:
            mv = self._sched.replicate_page(src, dst, d)
            if mv is None:
                break
            moves.append((src, mv[0], dst, mv[1]))
        if not moves:
            return
        self._device_move(moves)
        ticks, wire = migration_cost(
            len(moves) * self._page_bytes, self._mig_cfg)
        self.stats.migrated_pages += len(moves)
        self.stats.migrated_bytes_compressed += wire
        self._replica_hold = (r.rid, ticks)
        self.stats.pages_in_use = self._sched.pages_in_use

    # ------------------------------------------- fault tolerance (PR 6)
    def _apply_faults(self):
        """Apply this tick's FaultPlan events — at the tick boundary, before
        health/admission, so a plan replays bit-for-bit."""
        for e in self.fault_plan.events_at(self._tick):
            if e.kind == "shard_death":
                if self._monitor.force_dead(e.shard):
                    self._recover_shard(e.shard)
            elif e.kind == "shard_rejoin":
                if self._monitor.begin_rejoin(e.shard):
                    # pool comes back fresh; placement resumes after the
                    # monitor's rejoin cooldown flips the shard HEALTHY
                    self._sched.reset_shard(e.shard)
            elif e.kind == "sensor_hot":
                self._monitor.inject_sensor(e.shard, e.delta_c, e.ticks)
            elif e.kind == "page_squeeze":
                self._sched.steal_pages(e.shard, e.pages)
            elif e.kind == "page_restore":
                self._sched.restore_pages(e.shard)
            self.stats.faults_injected += 1

    def _health_tick(self):
        """Advance the sensor-driven health machine one tick and react:
        shards entering DRAINING/DEAD get their live slots recovered, a
        drained shard that cooled resets its pool for rejoin, and the
        scheduler's placement mask tracks the monitor."""
        occ = np.zeros((self.n_shards,), np.float64)
        for shard in range(self.n_shards):
            base = shard * self.slots_per_shard
            occ[shard] = sum(
                1 for s in range(self.slots_per_shard)
                if self._slots[base + s] is not None) / self.slots_per_shard
        for shard, old, new in self._monitor.step(occ):
            if new in EVACUATED and old not in EVACUATED:
                # DRAINING pool bytes are still alive → live page migration;
                # DEAD bytes are gone → re-prefill replay is all there is
                self._recover_shard(shard,
                                    migrate=(new == Health.DRAINING))
            if new == Health.REJOINING and old == Health.DRAINING:
                self._sched.reset_shard(shard)
        self._sched.placeable = self._monitor.placeable()

    def _recover_shard(self, shard: int, migrate: bool = False):
        """Evacuate every live slot off a draining/dead shard.

        With `migrate=True` (DRAINING: the pool bytes are still alive) each
        slot first tries a live page migration — its physical pages move to
        a healthy shard over the modeled UCIe link at O(bytes), no prefill
        chunk is recomputed, and the stream resumes token-identically after
        the link hold. Slots that don't fit anywhere (or when migration is
        off / the shard is DEAD and its bytes are gone) fall back to PR 6's
        re-prefill replay: release, requeue in rid order, and chunk-prefill
        the live_prompt on whichever healthy shard admission picks.
        Schedule-independent KV rounding and (seed, token_index)-keyed
        sampling make BOTH paths token-exact with an uninterrupted twin."""
        base = shard * self.slots_per_shard
        remaining = []
        for s in range(self.slots_per_shard):
            g = base + s
            if self._slots[g] is None:
                continue
            if migrate and self._migration:
                placeable = (self._monitor.placeable()
                             if self._monitor is not None
                             else self._sched.placeable)
                dst = self._sched.migration_target(shard, s, placeable)
                if dst is not None:
                    self._migrate_slot(shard, s, dst, count_recovery=True)
                    continue
            remaining.append(s)
        displaced = []
        for s in remaining:
            g = base + s
            displaced.append(self._slots[g])
            self._release(g)
        if not displaced:
            return
        displaced.sort(key=lambda r: r.rid)
        self._sched.requeue(displaced)
        for r in displaced:
            self._recover_started.setdefault(r.rid, self._tick)
            self.stats.recoveries += 1
            self.stats.retries += 1

    def _preempt(self, shard: int, slot: int):
        """Evict one young decoding slot so the starving queue head can
        admit (see scheduler.preempt_candidate for the victim policy)."""
        g = self._gslot(shard, slot)
        victim = self._slots[g]
        victim.preemptions += 1
        self._release(g)
        self._sched.requeue([victim])
        self.stats.preemptions += 1
        self.stats.retries += 1
        self._starved = 0

    def _expire_ttl(self):
        """Retire queued and live requests past their TTL (ticks since
        submit), releasing pages/slots exactly like completion."""
        def expired(r: Request) -> bool:
            ttl = r.ttl_ticks if r.ttl_ticks is not None else self.ttl_ticks
            return ttl is not None and self._tick - r.submit_tick > ttl

        q = self._sched.queue
        for r in [x for x in q if expired(x)]:
            q.remove(r)
            r.done = True
            r.timed_out = True
            r.t_done = time.perf_counter()
            self.stats.timeouts += 1
        for g, r in enumerate(self._slots):
            if r is not None and expired(r):
                r.done = True
                r.timed_out = True
                r.t_done = time.perf_counter()
                self.stats.timeouts += 1
                self._release(g)

    def assert_pool_accounting(self) -> None:
        """Exact pool accounting under faults: per shard free + mapped +
        stolen == n_pages - 1, and every slot without a live request sits on
        the shard's null page row."""
        self._sched.assert_accounting()
        for g, r in enumerate(self._slots):
            if r is None:
                assert not self._page_table[g].any(), g

    def health_summary(self) -> Optional[Dict[str, object]]:
        return None if self._monitor is None else self._monitor.summary()

    def run_to_completion(self, max_ticks: int = 10_000) -> EngineStats:
        ticks = 0
        while (self._sched.queue
               or any(r is not None for r in self._slots)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.stats

    # ------------------------------------------------------------------ stats
    def shard_summary(self) -> Dict[str, float]:
        """Per-shard balance metrics for the bench's sharded section."""
        toks = self.shard_tokens
        mean = sum(toks) / max(1, len(toks))
        imb = (max(toks) - min(toks)) / mean if mean else 0.0
        return {"shard_tokens": list(toks),
                "occupancy_imbalance": imb,
                "shard_occupancy": [
                    s / self.stats.decode_steps if self.stats.decode_steps
                    else 0.0 for s in self.shard_occupancy_sum]}
