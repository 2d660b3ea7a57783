"""Live cross-shard KV page migration over compression-aware UCIe (PR 9).

PR 6 recovers displaced slots by re-prefill replay: correct and token-exact,
but a drain recomputes prefill — O(FLOPs) in prompt length. The paper's §II
budgets the opposite: sensor-driven load migration moves STATE over the
die-to-die link, paying O(bytes) at the UCIe's compression-aware transfer
cost. This module is the host-side planner for that path; the device data
plane is `serve/sharded`'s move program (gather → all_gather → scatter built
from `models.transformer.gather_pool_pages` / `set_pool_page`), and the host
bookkeeping re-homes atomically in `ShardScheduler.migrate_slot`.

Three triggers share the one primitive:

  * **drain**   — a DRAINING shard's live slots re-home instead of being
    released + replayed (DEAD shards still replay: their pool bytes are
    gone, there is nothing to move).
  * **rebalance** — elastic load balancing: when the queue head starves on
    one shard's free list while another idles, or the busy-slot gap between
    shards exceeds `rebalance_threshold`, a young decoding slot moves.
  * **prefix replication** — a registry hit that only exists on a remote
    shard copies the hot prefix's pages instead of re-prefilling locally
    (guarded by `min_prefix_hits`).

Exactness contract: the data path moves POOL-NATIVE bytes, verbatim. An
int8 KV pool's int8 rows + bf16 scale rows *are* its block-compressed wire
format — exactly half the bf16 bytes, produced by `kernels/quantize`'s
block quantization at write time and decompressed by decode's fused dequant
on the receiving shard — so "gather → block-compress → move → decompress →
scatter" is what every int8 migration does, at zero extra loss. Float pools
move their float bytes unchanged rather than round-tripping through
`quantize_blocks` (that WOULD be lossy and would break the schedule-
independent KV rounding contract the tests pin: migrated tokens must be
bit-exact). `UCIeConfig.compression_ratio` still prices wire compression in
the COST model, which is where the paper's claim lives.

Cost model: `migration_cost` charges every move through `core/ucie`'s
`transfer()` closed form — the SAME function the time-stepped simulator
drains through `link_tick`. `ucie.migration_ticks` turns that time into
engine ticks, and the engine holds a migrated slot's next decode step for
exactly that long. A guard test pins that no serving module re-derives link
math outside this call path.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

from repro.core import ucie
from repro.serve.engine import prefix_digests, request_seed_digest


@dataclasses.dataclass(frozen=True)
class MigrationConfig:
    """Knobs for the migration planner.

    `tick_us` maps link time onto engine ticks (1 tick ≙ 1 ms, the same
    scale `serve/health.HealthConfig.tick_ms` uses for thermal integration).
    `rebalance_threshold` is the busy-slot gap that triggers an elastic
    move; 0 disables rebalancing (drain migration stays on — it replaces a
    strictly more expensive replay)."""
    ucie: ucie.UCIeConfig = dataclasses.field(default_factory=ucie.UCIeConfig)
    tick_us: float = 1000.0
    wave_moves: int = 4              # pages per shard_map'd move wave
    rebalance_threshold: int = 0
    min_prefix_hits: int = 2         # replication guard: prefix hotness


def page_payload_bytes(pools) -> int:
    """Bytes ONE physical page occupies across every pool array (the page
    axis is axis 1). Pool-native: an int8 pool contributes its int8 rows
    plus bf16 row scales — the block-compressed wire format — so int8
    migrations genuinely ship about half the bf16 bytes."""
    return int(sum(x.size // x.shape[1] * x.dtype.itemsize
                   for x in pools.values()))


def migration_cost(payload_bytes: float,
                   cfg: MigrationConfig) -> Tuple[int, float]:
    """(hold_ticks, wire_bytes) of one migration transfer — both straight
    out of `core/ucie.transfer`'s closed form (via `ucie.migration_ticks`);
    the serving stack owns NO link math of its own."""
    ticks = ucie.migration_ticks(payload_bytes, cfg.ucie, tick_us=cfg.tick_us)
    _, _, wire = ucie.transfer(float(payload_bytes), cfg.ucie)
    return ticks, float(wire)


# --------------------------------------------------------------- planners
#
# Pure policy over ShardScheduler state: each returns WHAT to move; the
# engine executes (device waves + `migrate_slot` + hold accounting).
# `movable(shard, slot)` is the engine's veto — decoding, not held, not
# mid-prefill — so policy here never has to know about engine tick state.

def plan_rebalance(sched, threshold: int, placeable: List[bool],
                   movable: Callable[[int, int], bool]
                   ) -> Optional[Tuple[int, int, int]]:
    """One busy-gap move: when some shard runs more than `threshold` live
    slots above the idlest placeable shard, its youngest movable slot
    re-homes there. Deterministic (max busy, then max rid victim; min busy,
    then lowest id destination). Returns (src_shard, src_slot, dst_shard)."""
    if threshold <= 0:
        return None
    busy = [sum(r is not None for r in s.slots) for s in sched.shards]
    dst = None
    for i, s in enumerate(sched.shards):
        if not placeable[i] or None not in s.slots:
            continue
        if dst is None or (busy[i], i) < (busy[dst], dst):
            dst = i
    if dst is None:
        return None
    best = None
    for i, s in enumerate(sched.shards):
        if i == dst or busy[i] - busy[dst] <= threshold:
            continue
        for slot, r in enumerate(s.slots):
            if r is None or slot in s.prefill_fifo or not movable(i, slot):
                continue
            if sched.shards[dst].allocatable() < len(s.slot_pages[slot]):
                continue
            key = (busy[i], r.rid)
            if best is None or key > best[0]:
                best = (key, i, slot)
    return None if best is None else (best[1], best[2], dst)


def plan_starvation_rescue(sched, need: int, placeable: List[bool],
                           movable: Callable[[int, int], bool]
                           ) -> Optional[Tuple[int, int, int]]:
    """Migration-instead-of-preemption: a decoding slot whose re-homing
    (a) frees its source shard enough that the starved queue head can admit
    there (the victim's exclusive pages plus the shard's allocatable set
    cover `need`, and its slot frees up) and (b) fits whole on a
    destination shard. The head unblocks WITHOUT any decoded work being
    thrown away — preemption stays the fallback when no such pair exists.
    Victim choice mirrors `preempt_candidate` (youngest rid)."""
    best = None
    for i, s in enumerate(sched.shards):
        if not placeable[i]:          # the head must admit on the source
            continue
        for slot, r in enumerate(s.slots):
            if r is None or slot in s.prefill_fifo or not movable(i, slot):
                continue
            exclusive = sum(1 for p in s.slot_pages[slot].values()
                            if s.ref[p] == 1)
            if exclusive + s.allocatable() < need:
                continue
            n_pages = len(s.slot_pages[slot])
            dst = None
            for k, d in enumerate(sched.shards):
                if k == i or not placeable[k] or None not in d.slots:
                    continue
                if d.allocatable() < n_pages:
                    continue
                busy_k = sum(x is not None for x in d.slots)
                key = (d.pages_in_use, busy_k, k)
                if dst is None or key < dst[0]:
                    dst = (key, k)
            if dst is None:
                continue
            if best is None or r.rid > best[0]:
                best = (r.rid, i, slot, dst[1])
    return None if best is None else (best[1], best[2], best[3])


def plan_prefix_replication(sched, r, cfg: MigrationConfig,
                            placeable: List[bool]
                            ) -> Optional[Tuple[int, int, List[bytes]]]:
    """Cross-shard prefix reuse: if the longest cached run of the queue
    head's prompt lives on a shard it cannot admit on, and the prefix is
    hot (`min_prefix_hits` admissions have hit its first page), replicate
    the missing run onto the best admitting shard — compressed-UCIe page
    moves instead of re-prefill. Returns (src_shard, dst_shard, digests to
    copy, in chain order) or None."""
    if not sched.prefix_cache:
        return None
    lp = r.live_prompt()
    n_cand = lp.shape[0] // sched.page_size
    if n_cand == 0:
        return None
    digs = prefix_digests(lp, sched.page_size, n_cand,
                          request_seed_digest(r.extras))
    runs = []
    for s in sched.shards:
        n = 0
        while n < n_cand and digs[n] in s.by_hash:
            n += 1
        runs.append(n)
    local = [i for i in range(sched.n_shards)
             if placeable[i] and None in sched.shards[i].slots]
    if not local:
        return None
    dst = min(local, key=lambda i: (-runs[i], sched.shards[i].pages_in_use, i))
    src = min(range(sched.n_shards), key=lambda i: (-runs[i], i))
    gain = runs[src] - runs[dst]
    if gain <= 0:
        return None
    if sched.digest_hits.get(digs[0], 0) < cfg.min_prefix_hits:
        return None
    if sched.shards[dst].allocatable() < gain:
        return None
    return src, dst, digs[runs[dst]:runs[src]]
