"""Serving engine: prefill + continuous-batching decode.

The "AI-optimized" configuration of the paper, as a serving runtime:
  * slot-based continuous batching: a fixed decode batch of N slots; finished
    requests free their slot, queued requests prefill into it (their KV/state
    pasted into the slot's cache rows) while other slots keep decoding.
  * int8 weight-only path (kernels/int8_matmul) — the 15 TOPS INT8 NPU
    datapath — available to the serve example/benches via `quantize_params`.
  * the faithful chiplet perf model (core/) prices batching decisions the way
    the paper's CPU chiplet dispatches to its two NPUs (see benches).

INT8 serving configuration (PR 3 — the paper's 15 TOPS INT8 datapath as the
measured serving numerics):
  * `wdtype="int8"`: weight-only int8 — the params pytree's projection
    weights become (int8, per-output-channel f32 scale) leaves via
    `models.quantized.quantize_params`; every projection einsum in the
    prefill/decode steps dispatches through `qeinsum` (Pallas int8_matmul on
    TPU, jnp dequant-matmul reference elsewhere; MoE experts quantized per
    expert). Halves weight HBM traffic per decode step — the bound at small
    batch.
  * `kv_dtype="int8"`: K/V stored int8 with per-(token, kv head) bf16 dequant
    scales ('ks'/'vs' tensors riding next to 'k'/'v' in either cache
    layout). Quantization happens at write time (prefill paste + decode
    write); dequant is fused into the decode-attention kernel's K/V tile
    loads, so cache bytes/token drop ~2× vs bf16 (~(D+2)/2D) on top of the
    paged pool's live-token scaling. The quantized bytes are identical in
    the dense and paged layouts, so an int8 paged engine is token-exact
    against the dense int8 oracle — the equivalence the tests pin. encdec
    cross K/V stay f32 (written once; see encdec.cache_shape).
  * `kv_dtype="bf16"` is also accepted (the comparison baseline the int8
    serve bench reports its byte-shrink against).

Sliding-window paged slots (PR 3): window-attention configs (cfg.window > 0)
hold O(window) pages instead of O(position): admission reserves only
ceil(window/page)+2 pages past the live floor, and every tick the engine
frees pages that fell fully out of the attention window — remapping them to
the slot's next logical page (zero pool traffic) or returning them to the
free list once the request's span is covered. Out-of-window prompt pages are
never backed at all (their paste rows land on the null page, which the
window mask already makes unreadable).

Cache layout (PR 2 — paged KV):
  * Attention families default to a PAGED KV cache: one shared page pool of
    (n_layers, n_pages, page_size, KV, D) K/V blocks (float GQA pools:
    lane-dense (n_layers, n_pages, page_size·KV·D/128, 128) pages,
    models/attention.pack_pages) plus a per-slot
    (n_slots, max_len // page_size) page table. Physical page 0 is the NULL
    page — never allocated, it absorbs writes from retired slots and backs
    unmapped table entries so every gather/DMA has a valid source. Admission
    reserves ceil(min(max_len, prompt + max_new) / page_size) pages up front
    (so a request can never starve mid-decode) and retirement returns them to
    the free list and re-points the slot's table row at the null page. When
    the free list can't cover the queue head, admission waits — the pool is
    the admission controller. Peak KV memory therefore scales with LIVE
    tokens, not n_slots × max_len: long-context engines no longer reserve the
    worst case per slot (paper §serving: 16 GB HBM3 + streaming block-granular
    UCIe transfers — a page is one FLIT-sized stream unit).
  * `paged=False` keeps the dense per-slot (n_slots, max_len) rows — the
    oracle configuration for equivalence tests (`generate_greedy` runs it).
  * ssm/hybrid families keep their O(1) dense recurrent state; paging does
    not apply.

Chunked page-granular prefill (PR 4) — paged attention-family engines
default to it:
  * `_admit` only RESERVES the request's pages and (encdec) computes the
    cross K/V once; no prompt compute happens at admission. Each engine tick
    then runs AT MOST ONE fixed-size prefill chunk (chunk = chunk_pages ×
    page_size tokens) before the decode batch steps: the chunk computes its
    K/V, streams them straight into the page pool through the slot's page
    row, and runs chunk attention against the slot's already-pasted pages
    (kernels/flash_attention.flash_attention_paged on TPU; the jnp gather
    path is the CPU oracle). Head-of-line blocking is gone — a 4k-token
    prompt costs ceil(4k/C) bounded ticks interleaved with decode instead of
    one monolithic stall — and padding waste is capped at ONE CHUNK per
    prompt (vs ~2x worst-case under pow2 bucketing). One chunk compile total
    (C is fixed), instead of one prefill compile per bucket.
  * Mid-prefill slots keep their cache page-table row on the null page and
    their `active` mask off: the batched decode step's garbage writes for
    them can only land on the null page (the PR 2 idle-slot guard, extended
    to admission). The slot's REAL page row rides the chunk call as an
    explicit argument and is stamped into the cache — with pos = plen-1 for
    the replay — only after the final chunk.
  * Windowed configs chunk one page at a time and recycle out-of-window
    pages BETWEEN chunks (host-side bookkeeping only — the cache table row
    is still null), so a prompt longer than the window holds O(window)
    pages while prefilling, not O(plen).
  * Lossy KV storage (bf16/int8) engines pass a `kv_round` marker into the
    monolithic prefill so it attends the SAME rounded values the cache
    stores (models/transformer._round_kv). Chunk attention reads the pool —
    already rounded — so chunked and monolithic prefill see identical
    numerics and the chunked engine stays token-exact against the dense
    oracle for every KV dtype.

Per-slot sampling (PR 4): `submit(..., sample_params=(temperature, top_k,
top_p), seed=...)` threads per-slot sampling state through ONE jitted
sampled-decode step (serve/sampling.py, vmapped over slots): each slot's
PRNG key for its i-th token is fold_in(key(seed), i) — deterministic under
re-runs, slot reassignment and chunk-size changes. All-greedy ticks (the
default) dispatch to a separate argmax-only decode jit — bit-identical
tokens, none of the sampler's per-vocab sort/cumsum work, and the sampled
variant never even traces unless a request asks for it.

Fast-path design (PR 1):
  * power-of-two prompt bucketing — prefill compiles once per bucket, not once
    per distinct prompt length, so compile count is O(log max_len) in steady
    state. Padded prefills are made exact by *replaying* the last prompt token
    through the decode step (causal attention leaves rows [0, plen) untouched
    by trailing pads; the replay recomputes position plen-1 and yields the
    first output token from the shared decode path). Recurrent families
    (ssm/hybrid) carry their state through padding, so they keep exact-length
    prefill.
  * the KV cache is donated through the decode jit (in-place update instead of
    a full-cache copy per step) and through the jitted slot-paste program.
  * slot pastes run as ONE jitted scatter program per family instead of a
    Python chain of `.at[].set()` dispatches.
  * `pos` is fetched from device once per step (one host sync), not once per
    active slot.
  * freed slots are masked out of the batched decode step: an `active` mask
    freezes their stream position, so an idle tick is a no-op per freed slot
    (their stale-token writes land on the null page / an overwritten dense
    row, and `pos` cannot drift past the cache).

Pure-python orchestration over jitted model fns; runs on CPU for tests and
examples, mesh-parameterized for pods.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.sanitizer import register_entry_point
from repro.models.attention import pack_pages
from repro.models.quantized import quantize_kv_rows
from repro.models.transformer import copy_pool_page, pool_data_keys
from repro.serve.faults import FaultPlan
from repro.serve.sampling import (
    apply_logit_processors, clamp_rep_penalty, clamp_sample_params,
    sample_tokens)

_ATTN_FAMILIES = ("dense", "moe", "vlm", "encdec")


class EngineOverloaded(RuntimeError):
    """Graceful backpressure: submit() refused because the admission queue
    is at its cap. Callers shed load (retry later / route elsewhere)
    instead of growing an unbounded queue."""

_KV_DTYPES = {None: jnp.float32, "f32": jnp.float32, "float32": jnp.float32,
              "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
              "int8": jnp.int8,
              # fp8 KV: bare e5m2 rows, no scale tensors (dense layout only —
              # paged fp8 pools are a recorded follow-on)
              "fp8": jnp.float8_e5m2, "e5m2": jnp.float8_e5m2}


def bucket_length(plen: int, max_len: int) -> int:
    """Next power of two ≥ plen, clipped to max_len."""
    b = 1
    while b < plen:
        b <<= 1
    return min(b, max_len)


# ---------------------------------------------------------------------------
# Paged-pool bookkeeping shared by the single-host engine and the sharded
# scheduler (serve/scheduler.py) — ONE copy of the reservation and
# sliding-window recycle math, so a fix in either engine cannot silently
# break the other's token-parity invariant.
# ---------------------------------------------------------------------------

def window_page_budget(window: int, page_size: int) -> int:
    """Mapped pages that always cover [pos-window, pos] plus one page of
    write-ahead slack while the window slides."""
    return (window - 1) // page_size + 3


def reserve_page_count(plen: int, max_new: int, *, max_len: int,
                       page_size: int, window: int, lo: int = 0) -> int:
    """Pages reserved at admission: every row the request can ever write,
    or — for window configs — the O(window) live span from logical page `lo`
    (0 under chunked prefill: the first chunk writes row 0 and recycling
    slides the mapping forward)."""
    rows = min(max_len, plen + max_new)
    full = -(-rows // page_size)
    if not window:
        return full
    return min(full - lo, window_page_budget(window, page_size))


def recycle_dead_pages(mapping: Dict[int, int], cap: int, page_size: int,
                       window: int, progress: int):
    """Sliding-window recycle core: pages fully below `progress - window`
    either become the slot's next logical page (remap forward while the
    request still has unwritten pages below `cap`) or leave the mapping once
    its span is covered. Mutates `mapping` in place; returns
    ([(j_dead, j_new, phys)] remaps, [(j_dead, phys)] unmaps) — the caller
    mirrors both into its page table and RELEASES the unmapped physical
    pages through its own (ref-counted, PR 8) allocator. Remapped pages get
    rewritten, so window engines never share pages — the prefix cache is
    disabled under a sliding window and every page here is exclusively
    owned."""
    dead = sorted(j for j in mapping
                  if (j + 1) * page_size <= progress - window)
    remaps, unmaps = [], []
    if not dead:
        return remaps, unmaps
    nxt = max(mapping) + 1
    for j in dead:
        phys = mapping.pop(j)
        if nxt < cap:
            mapping[nxt] = phys
            remaps.append((j, nxt, phys))
            nxt += 1
        else:
            unmaps.append((j, phys))
    return remaps, unmaps


def page_row_of(mapping: Dict[int, int], pages_per_seq: int) -> np.ndarray:
    """(pages_per_seq,) physical-page row: mapped pages, null page 0 rest."""
    row = np.zeros((pages_per_seq,), np.int32)
    for j, p in mapping.items():
        row[j] = p
    return row


# ---------------------------------------------------------------------------
# Prefix cache (PR 8): content addressing for page-aligned prompt prefixes.
# A page's K/V bytes are a pure function of the token prefix up to its end
# (attention context included) AND any non-token prefill inputs (vlm patch
# embeds overwrite leading embeddings; encdec cross-attention threads the
# frames through every decoder layer) — so the content key for logical page
# j is a digest CHAIN: sha1(extras) -> sha1(prev || page-j tokens). Two
# requests share page j iff their whole prefixes up to (j+1)*page_size
# match, which with schedule-independent KV rounding (PR 4) means the pool
# bytes match exactly.
# ---------------------------------------------------------------------------

def request_seed_digest(extras: Optional[Dict[str, np.ndarray]]) -> bytes:
    """Chain seed covering every non-token prefill input. Empty extras hash
    to b'' so the common text-only case costs nothing."""
    if not extras:
        return b""
    h = hashlib.sha1()
    for key in sorted(extras):
        arr = np.ascontiguousarray(np.asarray(extras[key]))
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.digest()


def prefix_digests(lp: np.ndarray, page_size: int, n_pages: int,
                   seed: bytes = b"") -> List[bytes]:
    """Digest chain for the first `n_pages` FULL pages of token prefix `lp`:
    digests[j] keys the pool content of logical page j."""
    out, d = [], seed
    for j in range(n_pages):
        d = hashlib.sha1(
            d + lp[j * page_size:(j + 1) * page_size].tobytes()).digest()
        out.append(d)
    return out


def lookup_prefix_hits(by_hash: Dict[bytes, int], lp: np.ndarray,
                       page_size: int, seed: bytes = b"") -> List[int]:
    """Longest cached run over lp's FULL prompt pages — the hit physical
    pages, in logical order. The scan stops at the first miss: page j+1's
    digest chains through page j's, and chunk resume needs a CONTIGUOUS
    cached prefix anyway."""
    n_cand = lp.shape[0] // page_size
    hits: List[int] = []
    if not n_cand:
        return hits
    for d in prefix_digests(lp, page_size, n_cand, seed=seed):
        p = by_hash.get(d)
        if p is None:
            break
        hits.append(p)
    return hits


def prefix_share_plan(plen: int, hits: List[int], page_size: int):
    """(n_shared, cow_src): hit pages shared outright vs the one COW-cloned.
    tail = (plen-1)//page_size is the page the replay decode WRITES — never
    shared; a full-page hit there (plen % page_size == 0 only) is cloned
    into a private page instead of recomputed."""
    tail = (plen - 1) // page_size
    n_shared = min(len(hits), tail)
    cow_src = hits[tail] if len(hits) > tail else None
    return n_shared, cow_src


def register_prefix_pages(mapping: Dict[int, int], lp: np.ndarray,
                          page_size: int, seed: bytes,
                          page_hash: Dict[int, bytes],
                          by_hash: Dict[bytes, int]) -> None:
    """Content-register a fully-prefilled slot's FULL prompt pages in the
    (page_hash, by_hash) registry. First registration of a content key wins;
    a page already keying another prefix keeps its key."""
    n_full = lp.shape[0] // page_size
    if not n_full:
        return
    digests = prefix_digests(lp, page_size, n_full, seed=seed)
    for j in range(n_full):
        phys = mapping.get(j)
        if phys is None or digests[j] in by_hash or phys in page_hash:
            continue
        page_hash[phys] = digests[j]
        by_hash[digests[j]] = phys


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 16
    # extra prefill inputs (e.g. encdec 'frames': (S_enc, d_model)); batched
    # with a leading axis of 1 at admission
    extras: Optional[Dict[str, np.ndarray]] = None
    # sampling: temperature 0 = greedy argmax (the exactness-test oracle);
    # top_k 0 and top_p 1.0 disable their filters
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # logit processors (PR 7): repetition penalty over prompt + emitted
    # tokens (1.0 = off, HF convention) and an additive per-token logit bias
    rep_penalty: float = 1.0
    logit_bias: Optional[Dict[int, float]] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle stamps, all on one monotonic clock (time.perf_counter), so
    # only differences mean anything. A preempted and resumed request keeps
    # its first stamps. The prefill pair brackets the request's own chunks
    # (or its monolithic prefill); a request whose whole prompt was served
    # from the prefix cache runs no chunk and has no t_prefill_start.
    t_enqueue: float = 0.0
    t_admitted: Optional[float] = None       # first admission into a slot
    t_prefill_start: Optional[float] = None  # before its first prefill
    t_prefill_end: Optional[float] = None    # slot goes live (finalize)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    # tick-domain latency (deterministic twin of the wall-clock fields: the
    # bench gates cache-hit TTFT on ticks, which replay bit-for-bit)
    first_token_tick: Optional[int] = None
    # prompt tokens served from the prefix cache at (last) admission
    cached_prompt_tokens: int = 0
    # ---- fault tolerance (PR 6) ----------------------------------------
    preemptions: int = 0            # times this request was preempted
    timed_out: bool = False         # retired by TTL, not by completion
    submit_tick: int = 0            # engine tick at submit (TTL clock)
    ttl_ticks: Optional[int] = None  # per-request TTL override

    def live_prompt(self) -> np.ndarray:
        """The token prefix a resumed request re-prefills: prompt plus every
        already-emitted token. Schedule-independent KV rounding (PR 4) makes
        the re-prefilled cache byte-identical to the one the decode steps
        wrote, and the fold_in(seed, token_index) sampling streams continue
        at counter=len(out_tokens) — so a preempted/recovered stream is
        token-exact with its uninterrupted twin."""
        if not self.out_tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out_tokens, np.int32)])

    def remaining_new(self) -> int:
        return self.max_new_tokens - len(self.out_tokens)


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0           # requests admitted into prefill
    decode_steps: int = 0
    tokens_out: int = 0
    occupancy_sum: float = 0.0
    prefill_compiles: int = 0   # actual jit traces (bucketing keeps this flat)
    decode_compiles: int = 0
    chunk_compiles: int = 0     # chunked prefill: ONE total (fixed shapes)
    prefill_chunks: int = 0     # chunk-prefill invocations
    pages_in_use: int = 0       # paged engines: currently reserved pages
    peak_pages_in_use: int = 0
    # head-of-line blocking: ticks the decode batch waited on prefill work
    # beyond the per-tick one-chunk budget (monolithic prefill of a long
    # prompt counts ceil(blen/chunk)-1; chunked prefill counts 0)
    decode_stall_ticks: int = 0
    prefill_tokens: int = 0     # real prompt tokens prefilled
    prefill_pad_tokens: int = 0  # padded prefill rows (bucket or chunk waste)
    # ---- fault tolerance & backpressure (PR 6) -------------------------
    preemptions: int = 0        # decoding slots evicted for a starving head
    retries: int = 0            # re-admissions (preempted or recovered work)
    timeouts: int = 0           # requests retired by TTL
    rejected: int = 0           # submits refused at the queue cap
    faults_injected: int = 0    # FaultPlan events applied
    recoveries: int = 0         # slots migrated off a draining/dead shard
    recovery_ticks_sum: int = 0  # requeue -> back-live latency, summed
    # ---- live page migration over UCIe (PR 9) --------------------------
    migrations: int = 0         # live slots re-homed by page moves
    migrated_pages: int = 0     # physical pages moved across shards
    migrated_bytes_compressed: float = 0.0  # UCIe wire bytes (post-compress)
    rebalance_events: int = 0   # elastic-rebalance slot moves
    # ---- prefix cache & copy-on-write (PR 8) ---------------------------
    prefix_hits: int = 0        # admissions that reused >=1 cached page
    prefix_misses: int = 0      # admissions with zero cached pages
    prefix_hit_tokens: int = 0  # prompt tokens served from cached pages
    prefix_evictions: int = 0   # refcount-zero cached pages reclaimed
    cow_copies: int = 0         # tail pages cloned instead of recomputed
    prefix_cached_pages: int = 0  # gauge: refcount-zero pages retained
    # ---- per-request latency samples (ROADMAP item 4 pre-work) ---------
    # raw seconds, one entry per COMPLETED request; summary() collapses
    # them to p50/p99 and drops the lists from the flat metric dict
    ttft_s: List[float] = dataclasses.field(default_factory=list, repr=False)
    tpot_s: List[float] = dataclasses.field(default_factory=list, repr=False)

    def record_request(self, r: "Request") -> None:
        """Fold a completed request's latencies into the TTFT/TPOT samples
        (timed-out / cancelled requests never report — their latencies
        describe the TTL policy, not the serving path)."""
        if r.t_first_token is not None:
            self.ttft_s.append(r.t_first_token - r.t_enqueue)
            if r.t_done is not None and len(r.out_tokens) > 1:
                self.tpot_s.append((r.t_done - r.t_first_token)
                                   / (len(r.out_tokens) - 1))

    def summary(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        # Every derived metric is guarded: zero-tick / zero-token runs (an
        # engine that only rejected or timed out, an early-return bench leg)
        # must report well-defined zeros, never a ZeroDivisionError or NaN.
        # Consumers index these keys unconditionally.
        d["mean_occupancy"] = (self.occupancy_sum / self.decode_steps
                               if self.decode_steps else 0.0)
        d["pad_waste_ratio"] = (self.prefill_pad_tokens / self.prefill_tokens
                                if self.prefill_tokens else 0.0)
        d["mean_recovery_ticks"] = (self.recovery_ticks_sum / self.recoveries
                                    if self.recoveries else 0.0)
        # SLO percentiles over completed requests — the flat dict stays
        # {metric: number} (the raw sample lists are dropped)
        for name in ("ttft_s", "tpot_s"):
            samples = d.pop(name)
            d[f"{name[:-2]}_p50_s"] = (
                float(np.percentile(samples, 50)) if samples else 0.0)
            d[f"{name[:-2]}_p99_s"] = (
                float(np.percentile(samples, 99)) if samples else 0.0)
        assert all(math.isfinite(v) for v in d.values()
                   if isinstance(v, (int, float))), d
        return d


def _make_paste(fam: str):
    """One jitted scatter program per family: copy request-0's prefill cache
    into engine-cache slot `slot` and stamp the slot's stream position `pos`.

    Row counts come from the prefill cache's static shapes, so the program
    retraces once per prefill bucket, not per request. The engine cache is
    donated — the paste updates in place instead of copying every tensor.
    """

    def paste(cache, pf, slot, pos):
        c = dict(cache)
        if fam in _ATTN_FAMILIES:
            plen = pf["k"].shape[2]
            int8_kv = "ks" in c
            # pools present in the prefill cache: ('k', 'v') for GQA,
            # ('k',) for MLA's single latent pool (models/mla.py)
            for key in pool_data_keys(pf):
                if int8_kv:
                    # quantize prompt rows per (position, kv head) — the same
                    # map the decode write path applies, so dense and paged
                    # int8 caches hold identical bytes
                    qr, sr = quantize_kv_rows(pf[key][:, 0, :plen])
                    c[key] = c[key].at[:, slot, :plen].set(qr)
                    c[key + "s"] = c[key + "s"].at[:, slot, :plen].set(sr)
                else:
                    c[key] = c[key].at[:, slot, :plen].set(
                        pf[key][:, 0, :plen].astype(c[key].dtype))
            for key in ("ck", "cv"):
                if key in c:
                    c[key] = c[key].at[:, slot].set(
                        pf[key][:, 0].astype(c[key].dtype))
        elif fam == "ssm":
            c["h"] = c["h"].at[:, slot].set(pf["h"][:, 0])
            c["conv"] = {
                k: c["conv"][k].at[:, slot].set(
                    pf["conv"][k][:, 0].astype(c["conv"][k].dtype))
                for k in c["conv"]}
        elif fam == "hybrid":
            new_layers = []
            for dst, src in zip(c["layers"], pf["layers"]):
                new_layers.append({
                    k: dst[k].at[slot].set(src[k][0].astype(dst[k].dtype))
                    for k in dst})
            c["layers"] = new_layers
        else:
            raise ValueError(f"unknown family {fam!r}")
        c["pos"] = c["pos"].at[slot].set(pos)
        return c

    return paste


def _make_paste_paged(fam: str, ps: int):
    """Paged paste: scatter the dense prefill rows page-by-page into the
    shared pool and stamp the slot's page-table row.

    `page_row` is the slot's full (pages_per_seq,) table row — reserved
    physical pages first, null page (0) for the rest. Prefill-bucket pad rows
    that spill past the reservation land on the null page; pad rows inside it
    sit at logical positions ≥ kv_len, masked until decode overwrites them —
    the same invariant the dense replay path relies on. A lane-dense pool
    (rank 4, models/attention's page format) takes whole packed pages, the
    last one zero-padded past the prompt."""
    assert fam in _ATTN_FAMILIES, fam

    def paste(cache, pf, slot, pos, page_row):
        c = dict(cache)
        blen = pf["k"].shape[2]
        n_prompt_pages = -(-blen // ps)    # static per prefill bucket
        int8_kv = "ks" in c
        # ('k', 'v') for GQA, ('k',) for MLA's single latent pool
        for key in pool_data_keys(pf):
            pool = c[key]
            if pool.ndim == 4:
                src = pf[key][:, 0].astype(pool.dtype)   # (L, blen, KV, D)
                src = jnp.pad(src, ((0, 0), (0, n_prompt_pages * ps - blen),
                                    (0, 0), (0, 0)))
                c[key] = pool.at[:, page_row[:n_prompt_pages]].set(
                    pack_pages(src.reshape(src.shape[0], n_prompt_pages, ps,
                                           *src.shape[2:])))
                continue
            if int8_kv:
                qrows, srows = quantize_kv_rows(pf[key][:, 0])  # (L,blen,KV,·)
                spool = c[key + "s"]
            for j in range(n_prompt_pages):
                rows = min(ps, blen - j * ps)
                if int8_kv:
                    pool = pool.at[:, page_row[j], :rows].set(
                        qrows[:, j * ps:j * ps + rows])
                    spool = spool.at[:, page_row[j], :rows].set(
                        srows[:, j * ps:j * ps + rows])
                else:
                    src = pf[key][:, 0, j * ps:j * ps + rows].astype(pool.dtype)
                    pool = pool.at[:, page_row[j], :rows].set(src)
            c[key] = pool
            if int8_kv:
                c[key + "s"] = spool
        for key in ("ck", "cv"):           # encdec cross K/V stay dense
            if key in c:
                c[key] = c[key].at[:, slot].set(
                    pf[key][:, 0].astype(c[key].dtype))
        c["page_table"] = c["page_table"].at[slot].set(page_row)
        c["pos"] = c["pos"].at[slot].set(pos)
        return c

    return paste


class _PagePlan(NamedTuple):
    """How an admission takes pages (`ServeEngine._plan_pages`)."""
    hits: List[int]               # cached pages of the prompt, in order
    digs: Optional[List[bytes]]   # its full pages' digests, where it misses
    n_shared: int                 # hit pages mapped outright
    cow_src: Optional[int]        # hit tail page cloned copy-on-write
    n_private: int                # pages to allocate
    cached: int                   # prompt tokens served from the cache


class ServeEngine:
    # Declared hot-loop compile budgets for a FIXED engine config (ROADMAP
    # contract: every serving subsystem declares its budgets). "decode" is
    # the greedy + lazily-traced sampled variants; "chunk" is the ONE
    # fixed-shape chunk-prefill compile; "prefill" is per pow2 bucket so it
    # scales O(log max_len) with traffic, not a constant — it is asserted
    # by the fastpath tests against the bucket count, not here. Enforced at
    # runtime via analysis/sanitizer.compile_budget(**COMPILE_BUDGETS).
    COMPILE_BUDGETS = {"decode": 2, "chunk": 1}

    def __init__(self, model, *, n_slots: int = 4, max_len: int = 128,
                 params=None, bucket_prompts: bool = True,
                 paged: Optional[bool] = None, page_size: int = 32,
                 n_pages: Optional[int] = None,
                 wdtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 chunked_prefill: Optional[bool] = None,
                 chunk_pages: int = 2,
                 prefix_cache: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 ttl_ticks: Optional[int] = None,
                 preempt_after: int = 2,
                 max_preemptions: int = 3,
                 fault_plan: Optional[FaultPlan] = None):
        self.model = model
        self.cfg = model.cfg
        self.n_slots = n_slots
        self.max_len = max_len
        if wdtype not in (None, "bf16", "int8"):
            raise ValueError(f"wdtype must be None/'bf16'/'int8', got {wdtype!r}")
        if wdtype == "int8":
            if self.cfg.family not in _ATTN_FAMILIES:
                raise ValueError(
                    f"wdtype='int8' applies to attention families, not "
                    f"{self.cfg.family!r}")
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
        if self.kv_dtype != jnp.float32 \
                and self.cfg.family not in _ATTN_FAMILIES:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} applies to attention-family KV "
                f"caches, not {self.cfg.family!r} recurrent state")
        self.params = params
        self.stats = EngineStats()
        self._queue: List[Request] = []
        # ---- fault tolerance & backpressure (PR 6) -------------------------
        self.max_queue = max_queue
        self.ttl_ticks = ttl_ticks
        self.preempt_after = max(1, int(preempt_after))
        self.max_preemptions = max(0, int(max_preemptions))
        self.fault_plan = fault_plan
        self._tick = 0               # engine tick counter (fault/TTL clock)
        self._starved = 0            # consecutive page-starved ticks
        self._page_blocked = False   # head blocked on pages w/ a free slot
        self._stolen_pages: List[int] = []   # page_squeeze stash (shard 0)
        self._any_ttl = ttl_ticks is not None
        self._slots: List[Optional[Request]] = [None] * n_slots
        self._fresh: List[bool] = [False] * n_slots  # replaying last prompt tok
        self._active = np.zeros((n_slots,), bool)
        self._next_rid = 0
        # Padded prefill + replay is only exact when trailing pads cannot
        # reach earlier positions — true for causal-attention KV caches, false
        # for recurrent state (ssm/hybrid), which keeps exact-length prefill.
        self._replay = self.cfg.family in _ATTN_FAMILIES
        self.bucket_prompts = bucket_prompts and self._replay
        if paged and self.cfg.family not in _ATTN_FAMILIES:
            raise ValueError(
                f"paged KV applies to attention families, not {self.cfg.family!r}")
        self.paged = (self.cfg.family in _ATTN_FAMILIES) if paged is None \
            else bool(paged)
        if self.paged and max_len % page_size != 0:
            if paged is None:
                # auto mode must not reject a max_len the dense engine took:
                # shrink to the largest compatible page size, or go dense if
                # pages would degenerate below 8 rows
                fit = math.gcd(min(page_size, max_len), max_len)
                if fit >= 8 or fit == max_len:
                    page_size = fit
                else:
                    self.paged = False
            else:
                raise ValueError(
                    f"max_len {max_len} is not a multiple of page_size "
                    f"{page_size}")
        if self.kv_dtype == jnp.float8_e5m2 and self.paged:
            raise ValueError(
                "kv_dtype fp8/e5m2 supports the dense cache layout only; "
                "pass paged=False (paged fp8 pools are a follow-on)")
        # sliding-window page recycling: attention configs with a window hold
        # O(window) live pages — out-of-window pages are freed mid-flight.
        # (encdec self-attention ignores cfg.window, so it stays full-span.)
        self._window = self.cfg.window \
            if self.paged and self.cfg.family != "encdec" else 0
        if self.paged:
            self.page_size = page_size
            self.pages_per_seq = max_len // page_size
            # page 0 is the reserved null page
            self.n_pages = (1 + n_slots * self.pages_per_seq
                            if n_pages is None else n_pages)
            assert self.n_pages >= 2, self.n_pages
            self._free_pages = list(range(self.n_pages - 1, 0, -1))
            # logical page index -> physical page, per slot
            self._slot_pages: List[Dict[int, int]] = [
                {} for _ in range(n_slots)]
            # highest logical page the request may ever write (exclusive)
            self._slot_cap = [0] * n_slots
            # ---- ref-counted, content-addressed allocator (PR 8) -----------
            # Every physical page is in exactly ONE of: the free list
            # (ref 0, unregistered), mapped by >=1 slot (ref >= 1), the
            # cached LRU (ref 0 but content-registered — evictable on
            # demand), or a page_squeeze stash. Slots hold REFERENCES, not
            # pages: release decrements, and a page only leaves the live set
            # at refcount zero.
            self._ref = np.zeros((self.n_pages,), np.int32)
            self._page_hash: Dict[int, bytes] = {}    # phys -> content key
            self._by_hash: Dict[bytes, int] = {}      # content key -> phys
            self._lru: "OrderedDict[int, None]" = OrderedDict()
        # in-flight prefix dedup (PR 9): page digests a mid-prefill slot
        # will register, so identical prompts submitted together wait for
        # the first's pages instead of prefilling twice. (Unconditional:
        # release/registration clear it on every engine flavour.)
        self._pending_digest: Dict[bytes, int] = {}       # digest -> rid
        self._pending_by_rid: Dict[int, List[bytes]] = {}
        # ---- chunked page-granular prefill (PR 4) --------------------------
        can_chunk = self.paged and model.prefill_chunk is not None
        if chunked_prefill is None:
            self.chunked = can_chunk
        else:
            self.chunked = bool(chunked_prefill)
            if self.chunked and not can_chunk:
                raise ValueError(
                    "chunked_prefill requires a paged attention-family "
                    f"engine (family {self.cfg.family!r}, paged={self.paged})")
        self.chunk_pages = max(1, int(chunk_pages))
        if self.chunked and self._window:
            # windowed slots chunk ONE page at a time so the existing
            # ceil(window/page)+2 reservation also covers the chunk's
            # write-ahead — occupancy stays O(window) during prefill
            self.chunk_pages = 1
        # chunk token budget; also the stall-metric unit for monolithic
        # engines (a monolithic prefill of blen tokens counts as
        # ceil(blen/chunk_tokens) chunk-equivalents of decode stall)
        self.chunk_tokens = (self.chunk_pages * page_size if self.paged
                             else min(64, max_len))
        # ---- prefix cache (PR 8) -------------------------------------------
        # Content-addressed sharing of page-aligned prompt prefixes. Needs
        # the paged pool (pages to share) AND chunked prefill (the resume
        # contract that skips cached pages). Sliding-window engines disable
        # it silently: window recycling REWRITES remapped pages in place,
        # which is incompatible with sharing — and the window engine is
        # already the O(window) memory optimization.
        can_cache = self.paged and self.chunked and not self._window
        if prefix_cache is None:
            self.prefix_cache = can_cache
        else:
            self.prefix_cache = bool(prefix_cache)
            if self.prefix_cache and not (self.paged and self.chunked):
                raise ValueError(
                    "prefix_cache requires a paged chunked-prefill engine "
                    f"(family {self.cfg.family!r}, paged={self.paged}, "
                    f"chunked={self.chunked})")
            if self.prefix_cache and self._window:
                self.prefix_cache = False
        self._prefill_fifo: List[int] = []     # slots mid-prefill, FIFO
        self._chunk_next = [0] * n_slots       # next chunk start per slot
        self._tick_prefill_tokens = 0
        # ---- per-slot sampling state (PR 4) --------------------------------
        self._temp = np.zeros((n_slots,), np.float32)
        self._topk = np.zeros((n_slots,), np.int32)
        self._topp = np.ones((n_slots,), np.float32)
        self._sseed = np.zeros((n_slots,), np.int32)
        # ---- per-slot logit processors (PR 7) ------------------------------
        # host-maintained, riding the same sampled-decode jit: rep_penalty
        # (1 = off), seen tokens (prompt + emitted), additive logit bias
        self._rep_pen = np.ones((n_slots,), np.float32)
        self._seen = np.zeros((n_slots, self.cfg.vocab_size), bool)
        self._bias = np.zeros((n_slots, self.cfg.vocab_size), np.float32)
        self._bias_on = np.zeros((n_slots,), bool)
        # Replay admissions discard prefill logits — use the cache-only
        # prefill (no LM-head matmul) when the family provides one.
        cache_only = self._replay and model.prefill_cache is not None

        def _prefill(params, batch):
            self.stats.prefill_compiles += 1   # runs at trace time only
            if cache_only:
                return None, model.prefill_cache(params, batch)
            return model.prefill(params, batch)

        def _decode_core(params, batch, cache, active):
            logits, new_cache = model.decode(params, batch, cache)
            # freeze freed slots' stream position: their garbage advance would
            # otherwise drift past max_len tick by tick (idle tick == no-op)
            new_cache["pos"] = jnp.where(active, new_cache["pos"],
                                         cache["pos"])
            return logits[:, -1, :self.cfg.vocab_size], new_cache

        def _decode(params, batch, cache, active):
            # all-greedy fast path (the default): plain argmax, no sampling
            # pipeline — the pre-sampling engine's hot loop, unchanged
            self.stats.decode_compiles += 1
            logits, new_cache = _decode_core(params, batch, cache, active)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache

        def _decode_sample(params, batch, cache, active, sample):
            # per-slot sampling inside the decode jit: greedy (temperature 0)
            # rows still take the raw argmax; only (B,) tokens leave device.
            # Compiled lazily — engines that never sample never trace it.
            # Logit processors (rep penalty / bias) run first — identity for
            # slots with rep_penalty=1 and zero bias, so plain-sampled and
            # greedy rows are bit-identical to the processor-free engine.
            self.stats.decode_compiles += 1
            logits, new_cache = _decode_core(params, batch, cache, active)
            logits = apply_logit_processors(
                logits.astype(jnp.float32),
                sample["rep_penalty"], sample["seen"], sample["bias"])
            toks = sample_tokens(
                logits,
                sample["temperature"], sample["top_k"], sample["top_p"],
                sample["seed"], sample["counter"])
            return toks, new_cache

        if self.paged:
            _paste = _make_paste_paged(self.cfg.family, self.page_size)

            def _unmap(cache, slot):
                # retired slot: point its whole table row at the null page so
                # freed physical pages can be re-issued without aliasing
                return dict(cache, page_table=cache["page_table"]
                            .at[slot].set(0))

            def _remap_entry(cache, slot, j_dead, j_new, phys):
                # window recycling: a page that fell out of the attention
                # window becomes the slot's next logical page (its stale rows
                # sit at positions >= kv_len until overwritten — masked, the
                # same invariant pad rows rely on)
                pt = cache["page_table"].at[slot, j_dead].set(0)
                return dict(cache, page_table=pt.at[slot, j_new].set(phys))

            def _unmap_entry(cache, slot, j_dead):
                return dict(cache, page_table=cache["page_table"]
                            .at[slot, j_dead].set(0))

            self._unmap_jit = jax.jit(_unmap, donate_argnums=(0,))
            self._remap_entry_jit = jax.jit(_remap_entry, donate_argnums=(0,))
            self._unmap_entry_jit = jax.jit(_unmap_entry, donate_argnums=(0,))

            if self.chunked:
                def _chunk(params, batch, cache):
                    self.stats.chunk_compiles += 1   # trace time only
                    return model.prefill_chunk(params, batch, cache)

                def _finalize(cache, slot, pos, page_row):
                    # last chunk done: stamp the slot's REAL page row and its
                    # replay position — only now does the slot become visible
                    # to the batched decode step
                    c = dict(cache)
                    c["page_table"] = c["page_table"].at[slot].set(page_row)
                    c["pos"] = c["pos"].at[slot].set(pos)
                    return c

                self._chunk_jit = jax.jit(_chunk, donate_argnums=(2,))
                self._finalize_jit = jax.jit(_finalize, donate_argnums=(0,))
                # COW tail clone: duplicate one physical page across every
                # pool (models/transformer.copy_pool_page), cache donated
                self._cow_jit = jax.jit(copy_pool_page, donate_argnums=(0,))
                if model.prefill_cross is not None:
                    self._cross_jit = jax.jit(model.prefill_cross)

                    def _paste_cross(cache, ck, cv, slot):
                        c = dict(cache)
                        c["ck"] = c["ck"].at[:, slot].set(
                            ck[:, 0].astype(c["ck"].dtype))
                        c["cv"] = c["cv"].at[:, slot].set(
                            cv[:, 0].astype(c["cv"].dtype))
                        return c

                    self._paste_cross_jit = jax.jit(_paste_cross,
                                                    donate_argnums=(0,))
        else:
            _paste = _make_paste(self.cfg.family)

        self._prefill_jit = jax.jit(_prefill)
        self._decode_jit = jax.jit(_decode, donate_argnums=(2,))
        self._decode_sample_jit = jax.jit(_decode_sample, donate_argnums=(2,))
        self._paste_jit = jax.jit(_paste, donate_argnums=(0,))
        # non-replay first-token sampler (recurrent families sample their
        # first output from the prefill logits, counter 0)
        self._sample1_jit = jax.jit(sample_tokens)
        self._proc1_jit = jax.jit(apply_logit_processors)
        # label the hot-loop jits for the retrace sanitizer: compile counts
        # per label back COMPILE_BUDGETS and the bench's
        # steady_state_retraces == 0 gate (analysis/sanitizer)
        register_entry_point("prefill", self._prefill_jit)
        register_entry_point("decode", self._decode_jit)
        register_entry_point("decode", self._decode_sample_jit)
        register_entry_point("paste", self._paste_jit)
        if getattr(self, "_chunk_jit", None) is not None:
            register_entry_point("chunk", self._chunk_jit)
        self._next_tok = np.zeros((n_slots, 1), np.int32)
        if self.paged:
            abs_cache = model.cache_shape(n_slots, max_len, self.kv_dtype,
                                          page_size=self.page_size,
                                          n_pages=self.n_pages)
        else:
            abs_cache = model.cache_shape(n_slots, max_len, self.kv_dtype)
        self._cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), abs_cache)
        if self.prefix_cache and getattr(self, "_cow_jit", None) is not None:
            # Warm the COW tail-clone NOW: its first use is the first
            # prefix-cache HIT, which otherwise pays the XLA compile
            # mid-serving — a latency spike on exactly the path whose point
            # is to be fast (caught by the steady-state retrace gate).
            # Cloning the null page onto itself is a no-op by construction.
            self._cache = self._cow_jit(self._cache, jnp.int32(0),
                                        jnp.int32(0))

    # ------------------------------------------------------------- lifecycle
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               extras: Optional[Dict[str, np.ndarray]] = None,
               sample_params: Optional[tuple] = None,
               seed: int = 0, ttl_ticks: Optional[int] = None,
               rep_penalty: float = 1.0,
               logit_bias: Optional[Dict[int, float]] = None) -> Request:
        """Queue a request. sample_params=(temperature, top_k, top_p) turns
        on per-slot sampling for this request (None = greedy argmax, the
        temperature=0 fast path); `seed` keys its PRNG stream; `ttl_ticks`
        overrides the engine TTL for this request.

        rep_penalty != 1 applies the CTRL/HF repetition penalty over the
        request's prompt + emitted tokens; `logit_bias` ({token_id: bias})
        adds a per-token bias — both ride the sampled-decode jit and compose
        with greedy decoding (serve/sampling.apply_logit_processors).
        Degenerate penalties clamp to 1 (off); bias keys must be in-vocab
        and values finite.

        Malformed requests raise ValueError (nothing is enqueued, no state
        changes); a full admission queue raises EngineOverloaded — graceful
        backpressure instead of unbounded queue growth."""
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
        if self.paged:
            need = self._pages_for(prompt.shape[0], max_new_tokens)
            if need > self.n_pages - 1:
                raise ValueError(
                    f"request needs {need} pages; pool has {self.n_pages - 1}")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.stats.rejected += 1
            raise EngineOverloaded(
                f"admission queue at cap ({self.max_queue}); retry later")
        temperature, top_k, top_p = 0.0, 0, 1.0
        if sample_params is not None:
            # degenerate params clamp to well-defined behavior (PR 5):
            # temperature < 0 → greedy, top_p=0 → filtered argmax, top_k out
            # of range → filter off — see serve/sampling.clamp_sample_params
            temperature, top_k, top_p = clamp_sample_params(*sample_params)
        rep_penalty = clamp_rep_penalty(rep_penalty)
        if logit_bias:
            for tok, bias in logit_bias.items():
                if not 0 <= int(tok) < self.cfg.vocab_size:
                    raise ValueError(
                        f"logit_bias token {tok} outside vocab "
                        f"[0, {self.cfg.vocab_size})")
                if not math.isfinite(float(bias)):
                    raise ValueError(
                        f"logit_bias[{tok}] must be finite, got {bias}")
        self._next_rid += 1
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens, extras=extras,
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=int(seed),
                      rep_penalty=rep_penalty,
                      logit_bias=dict(logit_bias) if logit_bias else None,
                      t_enqueue=time.perf_counter(),
                      submit_tick=self._tick, ttl_ticks=ttl_ticks)
        if ttl_ticks is not None:
            self._any_ttl = True
        self._queue.append(req)
        return req

    def _pages_for(self, plen: int, max_new: int) -> int:
        """Pages reserved at admission: every row the request can ever write
        (prompt + generated, one row per generated token, capacity-capped).

        Window configs reserve only the live span: pages below the attention
        window's floor are never backed, and ceil(window/page)+2 pages are
        enough to slide the window to the end of the request (out-of-window
        pages are recycled forward every tick — see `_recycle_window_pages`),
        so occupancy is O(window), not O(position). Chunked windowed prefill
        starts its mapping at logical page 0 (the first chunk writes row 0)
        and recycles forward between chunks, so it needs the same
        ceil(window/page)+2 budget but no live_lo offset."""
        lo = 0 if (self.chunked or not self._window) else self._live_lo(plen)
        return reserve_page_count(plen, max_new, max_len=self.max_len,
                                  page_size=self.page_size,
                                  window=self._window, lo=lo)

    def _live_lo(self, plen: int) -> int:
        """First logical page a window request can still read or write at its
        first decode step (the replay writes position plen-1)."""
        return max(0, plen - 1 - self._window) // self.page_size

    def _window_pages(self) -> int:
        return window_page_budget(self._window, self.page_size)

    def kv_cache_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self._cache))

    def programs(self) -> Dict[str, Tuple[Callable, tuple]]:
        """The jitted programs the tick loop runs, each with abstract
        arguments shaped and placed as `step()` feeds them:
        {"decode": (fn, args), "chunk": (fn, args)}, so that
        `fn.lower(*args)` is the program the loop dispatches. "chunk" is
        there only for engines with chunked prefill."""
        def spec(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype)

        params = jax.tree.map(spec, self.params)
        cache = jax.tree.map(spec, self._cache)
        n = self.n_slots
        progs = {"decode": (self._decode_jit, (
            params, {"tokens": arg((n, 1))}, cache, arg((n,), jnp.bool_)))}
        if self.chunked:
            C = self.chunk_tokens
            batch = {"tokens": arg((1, C)), "start": arg((1,)),
                     "length": arg((1,)),
                     "page_row": arg((self.pages_per_seq,))}
            if self.cfg.family == "vlm":
                batch["patch_rows"] = arg((1, C, self.cfg.d_model),
                                          jnp.float32)
                batch["n_patch"] = arg((1,))
            if self.cfg.family == "encdec":
                batch["slot"] = arg(())
            progs["chunk"] = (self._chunk_jit, (params, batch, cache))
        return progs

    def _sample_state(self, slot: int, r: Request):
        self._temp[slot] = r.temperature
        self._topk[slot] = r.top_k
        self._topp[slot] = r.top_p
        self._sseed[slot] = r.seed
        self._rep_pen[slot] = r.rep_penalty
        self._bias[slot] = 0.0
        for tok, bias in (r.logit_bias or {}).items():
            self._bias[slot, int(tok)] = bias
        self._bias_on[slot] = bool(r.logit_bias)
        # the penalty's "seen" set covers the whole live prompt — on resume
        # that already includes the emitted tokens, so a preempted stream's
        # penalties are identical to its uninterrupted twin's
        self._seen[slot] = False
        self._seen[slot, r.live_prompt()] = True

    def _admit(self):
        """Admit queued requests into free slots.

        Paged engines additionally reserve the request's worst-case page
        count up front; if the free list can't cover the queue head, admission
        stalls (FIFO — no small-request overtaking) until retirements return
        pages. Chunked engines only reserve + (encdec) compute cross K/V
        here — the prompt itself prefills one chunk per tick in
        `_prefill_tick`, so admission never stalls the decode batch.

        Resumed requests (preempted with emitted tokens) admit on their
        `live_prompt()` — prompt + out_tokens — and `remaining_new()` budget;
        the page reservation is invariant under resume
        (min(max_len, (plen+k) + (max_new-k)) == min(max_len, plen+max_new)),
        so a preempted request never needs more pages than it first did."""
        self._page_blocked = False
        with TraceAnnotation("engine.admit"):
            for slot in [i for i, r in enumerate(self._slots) if r is None]:
                if not self._queue:
                    return
                r = self._queue[0]
                lp = r.live_prompt()
                plan = self._plan_pages(r, lp) if self.paged else None
                if self.paged and plan is None:
                    return
                with TraceAnnotation(
                        "engine.admit_request", rid=r.rid, slot=slot,
                        prompt_tokens=lp.shape[0],
                        cached_tokens=0 if plan is None else plan.cached):
                    self._admit_request(slot, r, lp, plan)

    def _plan_pages(self, r: Request,
                    lp: np.ndarray) -> Optional[_PagePlan]:
        """How the queue head `r` would take pages (paged engines), or None
        when it must wait: an identical prompt's pages are being prefilled
        right now, or the pool cannot cover it (`_page_blocked` set)."""
        plen = lp.shape[0]
        need = self._pages_for(plen, r.remaining_new())
        hits, _ = self._prefix_lookup(r, lp)
        digs = None
        n_cand = plen // self.page_size if self.prefix_cache else 0
        if len(hits) < n_cand:
            digs = prefix_digests(lp, self.page_size, n_cand,
                                  request_seed_digest(r.extras))
            owner = self._pending_digest.get(digs[len(hits)])
            if owner is not None and owner != r.rid:
                # in-flight dedup: the head's first missing page is
                # being prefilled by a live slot right now — hold
                # admission (FIFO) and hit the registry once it
                # lands instead of prefilling the same bytes twice.
                # NOT a page starvation: no preemption pressure.
                return None
        n_shared, cow_src = self._share_plan(plen, hits)
        n_private = need - n_shared
        # hit pages resident in the LRU leave the allocatable set
        # the instant we incref them — account for that BEFORE
        # committing (cow_src is pinned during the clone, so it
        # counts too)
        pinned = sum(1 for p in hits[:n_shared] if self._ref[p] == 0)
        if cow_src is not None and self._ref[cow_src] == 0:
            pinned += 1
        if self._allocatable() - pinned < n_private:
            # head starved on pages while a slot sits free: the
            # signal step() counts toward preemption
            self._page_blocked = True
            return None
        return _PagePlan(hits, digs, n_shared, cow_src, n_private,
                         (n_shared + (cow_src is not None)) * self.page_size)

    def _admit_request(self, slot: int, r: Request, lp: np.ndarray,
                       plan: Optional[_PagePlan]) -> None:
        """Move the queue head `r` into free `slot`, committing `plan`."""
        plen = lp.shape[0]
        rem = r.remaining_new()
        page_row = None
        digs = None
        r.cached_prompt_tokens = 0
        if r.t_admitted is None:
            r.t_admitted = time.perf_counter()
        if plan is not None:
            digs, n_shared, cow_src = plan.digs, plan.n_shared, plan.cow_src
            shared = plan.hits[:n_shared]
            # commit order: protect the hit pages FIRST (incref pulls
            # them out of the eviction set), then allocate privates
            for p in shared:
                self._incref(p)
            if cow_src is not None:
                self._incref(cow_src)
            pages = [self._alloc_page() for _ in range(plan.n_private)]
            if cow_src is not None:
                # copy-on-write: the replay decode WRITES position
                # plen-1, so a fully-cached tail page is cloned into
                # the slot's first private page instead of recomputed
                self.stats.cow_copies += 1
                self._cache = self._cow_jit(
                    self._cache, jnp.int32(cow_src),
                    jnp.int32(pages[0]))
                self._decref_page(cow_src)
            lo = self._live_lo(plen) \
                if (self._window and not self.chunked) else 0
            mapping = {j: p for j, p in enumerate(shared)}
            for i, p in enumerate(pages):
                mapping[lo + n_shared + i] = p
            self._slot_pages[slot] = mapping
            self._slot_cap[slot] = -(-min(self.max_len, plen + rem)
                                     // self.page_size)
            r.cached_prompt_tokens = plan.cached
            if self.prefix_cache:
                if plan.cached:
                    self.stats.prefix_hits += 1
                    self.stats.prefix_hit_tokens += plan.cached
                else:
                    self.stats.prefix_misses += 1
            page_row = np.zeros((self.pages_per_seq,), np.int32)
            for j, p in mapping.items():
                page_row[j] = p
        self._queue.pop(0)
        self.stats.prefills += 1
        self.stats.prefill_tokens += plen - r.cached_prompt_tokens
        self._sample_state(slot, r)
        if self.chunked:
            # reserve-only admission: the slot's cache table row stays on
            # the null page (decode's garbage writes can't touch reserved
            # pages) until the final chunk stamps it in _prefill_tick
            self._slots[slot] = r
            self._active[slot] = False
            self._fresh[slot] = False
            self._chunk_next[slot] = r.cached_prompt_tokens
            if self.model.prefill_cross is not None:
                cross = self._cross_jit(self.params, {
                    "frames": jnp.asarray(r.extras["frames"])[None]})
                self._cache = self._paste_cross_jit(
                    self._cache, cross["ck"], cross["cv"],
                    jnp.int32(slot))
            if r.cached_prompt_tokens >= plen:
                # FULL hit: every prompt page is already in the pool
                # (shared run + COW-cloned tail) — zero prefill chunks.
                # The slot goes live immediately and its first token
                # arrives from THIS tick's decode: TTFT collapses to
                # one decode step
                self._register_prefix(slot, r, lp)
                self._cache = self._finalize_jit(
                    self._cache, jnp.int32(slot), jnp.int32(plen - 1),
                    jnp.asarray(page_row))
                if r.t_prefill_end is None:
                    r.t_prefill_end = time.perf_counter()
                self._next_tok[slot, 0] = int(lp[-1])
                self._fresh[slot] = True
                self._active[slot] = True
            else:
                self._prefill_fifo.append(slot)
                if digs is not None:
                    # claim the pages this slot will register, so
                    # identical prompts behind it wait for the cache
                    mine = self._pending_by_rid.setdefault(r.rid, [])
                    for d in digs[len(plan.hits):]:
                        if d not in self._pending_digest:
                            self._pending_digest[d] = r.rid
                            mine.append(d)
            return
        blen = bucket_length(plen, self.max_len) if self.bucket_prompts \
            else plen
        toks = np.zeros((1, blen), np.int32)
        toks[0, :plen] = lp
        batch = {"tokens": jnp.asarray(toks)}
        if self.kv_dtype != jnp.float32:
            # lossy KV storage: prefill attends the rounded values the
            # cache will hold (zero-size marker, dtype carries the info)
            batch["kv_round"] = jnp.zeros((0,), self.kv_dtype)
        for key, val in (r.extras or {}).items():
            batch[key] = jnp.asarray(val)[None]
        if r.t_prefill_start is None:
            r.t_prefill_start = time.perf_counter()
        logits, pf_cache = self._prefill_jit(self.params, batch)
        self.stats.prefill_pad_tokens += blen - plen
        self._tick_prefill_tokens += blen
        paste_args = () if page_row is None else (jnp.asarray(page_row),)
        if self._replay:
            # Cache rows [0, plen) are exact under trailing padding; the
            # next decode step replays prompt[-1] at position plen-1,
            # producing the first output token through the decode path
            # (pad rows ≥ plen are masked by kv_len until overwritten).
            self._cache = self._paste_jit(
                self._cache, pf_cache, jnp.int32(slot),
                jnp.int32(plen - 1), *paste_args)
            if r.t_prefill_end is None:
                r.t_prefill_end = time.perf_counter()
            self._next_tok[slot, 0] = int(lp[-1])
        else:
            lv = jnp.asarray(logits[:, -1, :self.cfg.vocab_size],
                             jnp.float32)
            if r.rep_penalty != 1.0 or r.logit_bias:
                # non-replay first token: processors apply here too —
                # _sample_state already loaded this slot's seen/bias rows
                lv = self._proc1_jit(
                    lv, jnp.full((1,), r.rep_penalty, jnp.float32),
                    jnp.asarray(self._seen[slot][None]),
                    jnp.asarray(self._bias[slot][None]))
            if r.temperature > 0:
                first = int(np.asarray(self._sample1_jit(
                    lv, jnp.full((1,), r.temperature, jnp.float32),
                    jnp.full((1,), r.top_k, jnp.int32),
                    jnp.full((1,), r.top_p, jnp.float32),
                    jnp.full((1,), r.seed, jnp.int32),
                    jnp.zeros((1,), jnp.int32)))[0])
            else:
                first = int(np.argmax(np.asarray(lv[0])))
            self._cache = self._paste_jit(
                self._cache, pf_cache, jnp.int32(slot), jnp.int32(plen),
                *paste_args)
            if r.t_prefill_end is None:
                r.t_prefill_end = time.perf_counter()
            r.out_tokens.append(first)
            if r.t_first_token is None:
                r.t_first_token = time.perf_counter()
                r.first_token_tick = self._tick
            self._next_tok[slot, 0] = first
            self._seen[slot, first] = True
            self.stats.tokens_out += 1
            if plen >= self.max_len \
                    or len(r.out_tokens) >= r.max_new_tokens:
                # done at admission: the cache is already full (no
                # writable row for a decode step) or the prefill token
                # exhausted the budget — never occupy a decode slot
                r.done = True
                r.t_done = time.perf_counter()
                self.stats.record_request(r)
                self._release(slot)
                return
        self._fresh[slot] = self._replay
        self._slots[slot] = r
        self._active[slot] = True

    def cancel(self, req: Request) -> None:
        """Retire a request at ANY lifecycle stage with exact pool
        accounting: queued → dequeue (nothing reserved yet); mid-prefill →
        drain its remaining chunk queue and return EVERY reserved page to
        the pool (the reservation-leak path this fixes: a slot released with
        chunks still queued used to be assumed unreachable); decoding →
        release the slot like a normal retirement."""
        if req.done:
            return
        if req in self._queue:
            self._queue.remove(req)
        elif req in self._slots:
            self._release(self._slots.index(req))
        req.done = True
        req.t_done = time.perf_counter()

    def _release(self, slot: int):
        """Return a finished slot to the pool and drain any queued prefill
        work it still holds (mid-prefill retirement must leak nothing)."""
        r = self._slots[slot]
        with TraceAnnotation("engine.release",
                             rid=-1 if r is None else r.rid, slot=slot):
            if r is not None:
                self._clear_pending(r.rid)
            self._slots[slot] = None
            self._active[slot] = False
            self._fresh[slot] = False
            self._temp[slot], self._topk[slot] = 0.0, 0
            self._topp[slot], self._sseed[slot] = 1.0, 0
            self._rep_pen[slot] = 1.0
            self._bias[slot], self._bias_on[slot] = 0.0, False
            self._seen[slot] = False
            if slot in self._prefill_fifo:      # mid-prefill: drain chunks
                self._prefill_fifo.remove(slot)
            if self.chunked:
                self._chunk_next[slot] = 0
            if self.paged:
                freed = self._slot_pages[slot]
                if freed:
                    # slots hold REFERENCES: a shared page survives its
                    # releasing slot and only leaves the live set at
                    # refcount 0
                    for phys in freed.values():
                        self._decref_page(phys)
                    self._slot_pages[slot] = {}
                self._cache = self._unmap_jit(self._cache, jnp.int32(slot))

    # ------------------------------------- ref-counted page allocator (PR 8)
    def _allocatable(self) -> int:
        """Pages an admission can obtain right now: the free list plus every
        refcount-zero cached page (evictable on demand)."""
        return len(self._free_pages) + len(self._lru)

    def pages_allocatable(self) -> int:
        """Public twin of the classic free-list length: pages obtainable by
        new work. With the prefix cache off (or cold) this equals
        len(_free_pages); after cache traffic, refcount-zero cached pages
        parked in the LRU count too — they are one eviction away from
        free."""
        return self._allocatable()

    def _unregister(self, phys: int):
        h = self._page_hash.pop(phys, None)
        if h is not None and self._by_hash.get(h) == phys:
            del self._by_hash[h]

    def _page_live(self, d: int):
        self.stats.pages_in_use += d
        if d > 0:
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, self.stats.pages_in_use)
        self.stats.prefix_cached_pages = len(self._lru)

    def _alloc_page(self) -> int:
        """One private page: pop the free list, else evict the
        least-recently-used refcount-zero cached page. Callers check
        `_allocatable()` BEFORE committing an admission."""
        if self._free_pages:
            p = self._free_pages.pop()
        else:
            p, _ = self._lru.popitem(last=False)    # oldest first
            self._unregister(p)
            self.stats.prefix_evictions += 1
        self._ref[p] = 1
        self._page_live(+1)
        return p

    def _incref(self, phys: int):
        if self._ref[phys] == 0:
            # cached page comes back live: out of the LRU, safe from
            # eviction for as long as any slot maps it
            self._lru.pop(phys, None)
            self._page_live(+1)
        self._ref[phys] += 1

    def _decref_page(self, phys: int):
        self._ref[phys] -= 1
        assert self._ref[phys] >= 0, int(phys)
        if self._ref[phys] == 0:
            self._page_live(-1)
            if self.prefix_cache and phys in self._page_hash:
                # registered content survives at refcount zero — parked in
                # the LRU until a future admission hits it or evicts it
                self._lru[phys] = None
            else:
                self._unregister(phys)
                self._free_pages.append(phys)
        self.stats.prefix_cached_pages = len(self._lru)

    def _prefix_lookup(self, r: Request, lp: np.ndarray):
        """Longest cached run over lp's FULL prompt pages (module-level
        lookup_prefix_hits — ONE shared copy with the shard scheduler)."""
        if not self.prefix_cache:
            return [], []
        hits = lookup_prefix_hits(self._by_hash, lp, self.page_size,
                                  seed=request_seed_digest(r.extras))
        return hits, []

    def _share_plan(self, plen: int, hits: List[int]):
        return prefix_share_plan(plen, hits, self.page_size)

    def _register_prefix(self, slot: int, r: Request, lp: np.ndarray):
        """Content-register the slot's fully-prefilled FULL prompt pages so
        later admissions can share them. Valid because decode only writes
        positions >= plen-1: pages strictly below the tail are never touched
        again, and a plen%page_size==0 tail page only takes the replay's
        byte-identical rewrite (schedule-independent KV rounding, PR 4)."""
        self._clear_pending(r.rid)
        if not self.prefix_cache:
            return
        register_prefix_pages(self._slot_pages[slot], lp, self.page_size,
                              request_seed_digest(r.extras),
                              self._page_hash, self._by_hash)

    def _clear_pending(self, rid: int) -> None:
        """Drop a request's in-flight dedup claims (registration landed, or
        the slot died mid-prefill) so deferred twins stop waiting on it."""
        for d in self._pending_by_rid.pop(rid, ()):
            if self._pending_digest.get(d) == rid:
                del self._pending_digest[d]

    def assert_accounting(self):
        """Ref-counted pool invariant: every non-null physical page is in
        EXACTLY one of {free list, live (mapped by >=1 slot), cached LRU,
        stolen stash}; per-page mapping references equal the refcounts; the
        pages_in_use gauge equals the unique live count."""
        assert self.paged
        free, lru = set(self._free_pages), set(self._lru)
        live = {p for m in self._slot_pages for p in m.values()}
        stolen = set(self._stolen_pages)
        assert len(free) == len(self._free_pages), "free list duplicates"
        sets = (free, lru, live, stolen)
        for i, a in enumerate(sets):
            assert 0 not in a, "null page leaked into the pool"
            for b in sets[i + 1:]:
                assert not (a & b), (free, lru, live, stolen)
        assert len(free) + len(lru) + len(live) + len(stolen) \
            == self.n_pages - 1, (len(free), len(lru), len(live),
                                  len(stolen), self.n_pages)
        refs = np.zeros_like(self._ref)
        for m in self._slot_pages:
            for p in m.values():
                refs[p] += 1
        assert np.array_equal(refs, self._ref), (refs, self._ref)
        assert self.stats.pages_in_use == len(live), \
            (self.stats.pages_in_use, len(live))
        for p in self._lru:
            assert p in self._page_hash, p

    # ---------------------------------------------------------------- prefill
    def _page_row(self, slot: int) -> np.ndarray:
        return page_row_of(self._slot_pages[slot], self.pages_per_seq)

    def _prefill_tick(self) -> bool:
        """Run AT MOST ONE fixed-size prefill chunk (FIFO over mid-prefill
        slots; the head slot finishes all its chunks first — shortest time
        to first token for the oldest admitted request)."""
        if not self._prefill_fifo:
            return False
        slot = self._prefill_fifo[0]
        r = self._slots[slot]
        s = self._chunk_next[slot]
        # resumed requests re-prefill prompt + already-emitted tokens; stable
        # across chunks because a mid-prefill slot is inactive (no decode
        # appends to out_tokens until finalize)
        lp = r.live_prompt()
        plen = lp.shape[0]
        C = self.chunk_tokens
        n = min(C, plen - s)
        with TraceAnnotation("engine.prefill_chunk", rid=r.rid, slot=slot,
                             start=s, length=n):
            if self._window and s:
                # free/remap pages that no chunk row >= s can still read —
                # a prompt longer than the window holds O(window) pages
                # while prefilling; the cache table row is still null, so
                # this is pure host bookkeeping until finalize stamps it
                self._recycle_slot_pages(slot, s, in_cache=False)
            toks = np.zeros((1, C), np.int32)
            toks[0, :n] = lp[s:s + n]
            page_row = self._page_row(slot)
            batch = {"tokens": jnp.asarray(toks),
                     "start": jnp.full((1,), s, jnp.int32),
                     "length": jnp.full((1,), n, jnp.int32),
                     "page_row": jnp.asarray(page_row)}
            if self.cfg.family == "vlm":
                pe = np.asarray((r.extras or {}).get(
                    "patch_embeds",
                    np.zeros((0, self.cfg.d_model), np.float32)))
                rows = np.zeros((1, C, self.cfg.d_model), np.float32)
                if s < pe.shape[0]:
                    m = min(C, pe.shape[0] - s)
                    rows[0, :m] = pe[s:s + m]
                batch["patch_rows"] = jnp.asarray(rows)
                batch["n_patch"] = jnp.full((1,), pe.shape[0], jnp.int32)
            if self.cfg.family == "encdec":
                batch["slot"] = jnp.int32(slot)
            if r.t_prefill_start is None:
                r.t_prefill_start = time.perf_counter()
            self._cache = self._chunk_jit(self.params, batch, self._cache)
            self.stats.prefill_chunks += 1
            self.stats.prefill_pad_tokens += C - n
            self._tick_prefill_tokens += C
            if s + C >= plen:                  # final chunk — slot goes live
                self._prefill_fifo.pop(0)
                # the slot's full prompt pages are now byte-final: register
                # them for prefix sharing before decode starts appending
                self._register_prefix(slot, r, lp)
                self._cache = self._finalize_jit(
                    self._cache, jnp.int32(slot), jnp.int32(plen - 1),
                    jnp.asarray(page_row))
                if r.t_prefill_end is None:
                    r.t_prefill_end = time.perf_counter()
                self._next_tok[slot, 0] = int(lp[-1])
                self._fresh[slot] = True
                self._active[slot] = True
            else:
                self._chunk_next[slot] = s + C
        return True

    # ----------------------------------------------------------------- decode
    def step(self) -> bool:
        """One engine tick: apply scheduled faults, expire TTLs, admit new
        work (preempting a young decoding slot if the head has starved on
        pages), run at most one prefill chunk, then one batched decode step
        over the live slots.

        Each tick is one `engine.step` span in the profiler's trace (host
        spans, `jax.profiler.TraceAnnotation`), with `engine.admit`,
        `engine.prefill_chunk`, `engine.decode`, `engine.readback` and
        `engine.release` nested inside it; they cost a few microseconds
        when no trace is being taken."""
        self._tick += 1
        with TraceAnnotation("engine.step", tick=self._tick):
            if self.fault_plan is not None:
                self._apply_faults()
            if self._any_ttl:
                self._expire_ttl()
            had_decode = bool(np.any(self._active))
            self._tick_prefill_tokens = 0
            self._admit()
            if self._page_blocked:
                self._starved += 1
                if self._starved >= self.preempt_after \
                        and self._preempt_once():
                    self._admit()
            else:
                self._starved = 0
            chunk_ran = self._prefill_tick() if self.chunked else False
            if had_decode and self._tick_prefill_tokens > self.chunk_tokens:
                # decode batch waited on more than one chunk's worth of
                # prefill this tick — the head-of-line blocking chunking
                # eliminates
                self.stats.decode_stall_ticks += \
                    -(-self._tick_prefill_tokens // self.chunk_tokens) - 1
            decoding = [i for i, r in enumerate(self._slots)
                        if r is not None and self._active[i]]
            if not decoding:
                return chunk_ran
            with TraceAnnotation("engine.decode", active=len(decoding)):
                toks = self._dispatch_decode(decoding)
            self.stats.decode_steps += 1
            self.stats.occupancy_sum += len(decoding) / self.n_slots
            with TraceAnnotation("engine.readback"):
                nxt = np.asarray(toks, np.int32)
                pos = np.asarray(self._cache["pos"])  # ONE host sync a step
            for slot in decoding:
                r = self._slots[slot]
                r.out_tokens.append(int(nxt[slot]))
                self._next_tok[slot, 0] = nxt[slot]
                self._seen[slot, int(nxt[slot])] = True  # rep-penalty seen
                self.stats.tokens_out += 1
                if self._fresh[slot]:
                    if r.t_first_token is None:   # resumed keep the original
                        r.t_first_token = time.perf_counter()
                        r.first_token_tick = self._tick
                    self._fresh[slot] = False
                # retire when out of budget OR out of cache: `pos` is the
                # next write index, so the slot can take another decode
                # step iff pos < max_len (the seed's `max_len - 1` retired
                # one writable row early, and one row earlier still on the
                # replay path)
                if len(r.out_tokens) >= r.max_new_tokens \
                        or int(pos[slot]) >= self.max_len:
                    r.done = True
                    r.t_done = time.perf_counter()
                    self.stats.record_request(r)
                    self._release(slot)
            if self._window:
                self._recycle_window_pages(pos)
            return True

    def _dispatch_decode(self, decoding: List[int]):
        """Dispatch one batched decode step over the live slots; returns the
        (n_slots,) next tokens, still on the device."""
        tokens = {"tokens": jnp.asarray(self._next_tok)}
        active = jnp.asarray(self._active)
        if any(self._temp[i] > 0 or self._rep_pen[i] != 1.0
               or self._bias_on[i] for i in decoding):
            counter = np.asarray(
                [len(r.out_tokens) if r is not None else 0
                 for r in self._slots], np.int32)
            sample = {"temperature": jnp.asarray(self._temp),
                      "top_k": jnp.asarray(self._topk),
                      "top_p": jnp.asarray(self._topp),
                      "seed": jnp.asarray(self._sseed),
                      "counter": jnp.asarray(counter),
                      "rep_penalty": jnp.asarray(self._rep_pen),
                      "seen": jnp.asarray(self._seen),
                      "bias": jnp.asarray(self._bias)}
            toks, self._cache = self._decode_sample_jit(
                self.params, tokens, self._cache, active, sample)
        else:
            toks, self._cache = self._decode_jit(
                self.params, tokens, self._cache, active)
        return toks

    def _recycle_window_pages(self, pos):
        """Free pages that fell fully out of the attention window.

        A freed page either becomes the slot's next logical page (the table
        entry moves forward, no pool traffic — the window slides in place) or,
        once the request's whole span is mapped, returns to the free list so
        queued requests can admit. Runs on the already-synced `pos`; at most
        one page transitions per slot per page_size ticks. Mid-prefill slots
        are SKIPPED — their cache `pos` is stale (chunk progress drives their
        recycling in `_prefill_tick` instead)."""
        for slot, r in enumerate(self._slots):
            if r is None or not self._active[slot] \
                    or not self._slot_pages[slot]:
                continue
            self._recycle_slot_pages(slot, int(pos[slot]), in_cache=True)

    def _recycle_slot_pages(self, slot: int, progress: int, *, in_cache: bool):
        """Recycle one slot's dead pages given `progress` = the next write
        index (decode: synced pos; chunked prefill: the next chunk's start).
        `in_cache` mirrors the remap/unmap into the cache's page-table row —
        False while the slot is mid-prefill and its row is still null."""
        remaps, unmaps = recycle_dead_pages(
            self._slot_pages[slot], self._slot_cap[slot],
            self.page_size, self._window, progress)
        for _, phys in unmaps:
            # window pages are exclusively owned (prefix cache is off under
            # a sliding window) — the decref drops them straight to free
            self._decref_page(phys)
        if in_cache:
            for j, nxt, phys in remaps:
                self._cache = self._remap_entry_jit(
                    self._cache, jnp.int32(slot), jnp.int32(j),
                    jnp.int32(nxt), jnp.int32(phys))
            for j, _ in unmaps:
                self._cache = self._unmap_entry_jit(
                    self._cache, jnp.int32(slot), jnp.int32(j))

    # ------------------------------------------- fault tolerance (PR 6)
    def _apply_faults(self):
        """Apply this tick's FaultPlan events. The single-host engine is
        "shard 0" of a one-shard fleet: it honors the page-pool events and
        ignores shard-level ones (death/rejoin/sensor need a fleet — see
        serve/sharded)."""
        for e in self.fault_plan.events_at(self._tick):
            if not self.paged or e.shard != 0:
                continue
            if e.kind == "page_squeeze":
                # steal free pages first; once the free list is dry, evict
                # refcount-zero cached pages (LRU) — capacity pressure
                # reclaims the prefix cache before it blocks live work
                take = min(e.pages, self._allocatable())
                for _ in range(take):
                    if self._free_pages:
                        p = self._free_pages.pop()
                    else:
                        p, _ = self._lru.popitem(last=False)
                        self._unregister(p)
                        self.stats.prefix_evictions += 1
                    self._stolen_pages.append(p)
                self.stats.prefix_cached_pages = len(self._lru)
                self.stats.faults_injected += 1
            elif e.kind == "page_restore":
                self._free_pages.extend(self._stolen_pages)
                self._stolen_pages.clear()
                self.stats.faults_injected += 1

    def _expire_ttl(self):
        """Retire queued and live requests past their TTL (ticks since
        submit). Timed-out requests release their pages/slot exactly like a
        completed one; `timed_out` marks them for the caller."""
        def expired(r: Request) -> bool:
            ttl = r.ttl_ticks if r.ttl_ticks is not None else self.ttl_ticks
            return ttl is not None and self._tick - r.submit_tick > ttl

        for r in [q for q in self._queue if expired(q)]:
            self._queue.remove(r)
            r.done = True
            r.timed_out = True
            r.t_done = time.perf_counter()
            self.stats.timeouts += 1
        for slot, r in enumerate(self._slots):
            if r is not None and expired(r):
                r.done = True
                r.timed_out = True
                r.t_done = time.perf_counter()
                self.stats.timeouts += 1
                self._release(slot)

    def _requeue(self, r: Request):
        """Re-enqueue a preempted request in rid order — it rejoins the FIFO
        exactly where its age puts it, ahead of anything younger."""
        i = 0
        while i < len(self._queue) and self._queue[i].rid < r.rid:
            i += 1
        self._queue.insert(i, r)

    def _preempt_once(self) -> bool:
        """Evict ONE decoding slot so the starving queue head can admit.

        Victim: the YOUNGEST (max rid) active decoding slot that is strictly
        younger than the head, still under its preemption budget, and whose
        pages (plus the free list) actually cover the head's need. Strict
        rid ordering makes progress monotone — a preempted request that
        becomes head can never preempt something older, so there is no
        preemption livelock. The victim's emitted tokens ride along in
        out_tokens and re-enter as prefill (see live_prompt), so its stream
        resumes token-exact."""
        if not self._queue:
            return False
        head = self._queue[0]
        hlp = head.live_prompt()
        need = self._pages_for(hlp.shape[0], head.remaining_new())
        # pages the head would actually have to ALLOCATE: shared hits stay
        # resident through the preemption, so only the private remainder
        # must come out of the victim + free/LRU
        hits, _ = self._prefix_lookup(head, hlp)
        n_shared, cow_src = self._share_plan(hlp.shape[0], hits)
        need -= n_shared
        # hit pages sitting in the LRU count as allocatable but get pinned
        # at admission — mirror _admit's availability math
        need += sum(1 for p in hits[:n_shared] if self._ref[p] == 0)
        if cow_src is not None and self._ref[cow_src] == 0:
            need += 1
        best = None
        for slot, r in enumerate(self._slots):
            if r is None or not self._active[slot] \
                    or slot in self._prefill_fifo:
                continue
            if r.rid <= head.rid or r.preemptions >= self.max_preemptions:
                continue
            # only the victim's EXCLUSIVELY-owned pages (ref 1) become
            # allocatable at release; shared pages just drop a reference
            exclusive = sum(1 for p in self._slot_pages[slot].values()
                            if self._ref[p] == 1)
            if exclusive + self._allocatable() < need:
                continue
            if best is None or r.rid > self._slots[best].rid:
                best = slot
        if best is None:
            return False
        victim = self._slots[best]
        victim.preemptions += 1
        self._release(best)
        self._requeue(victim)
        self.stats.preemptions += 1
        self.stats.retries += 1
        self._starved = 0
        return True

    def run_to_completion(self, max_ticks: int = 10_000) -> EngineStats:
        ticks = 0
        while (self._queue or any(r is not None for r in self._slots)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.stats


def generate_greedy(model, params, prompt: np.ndarray, n_tokens: int,
                    max_len: int = 128, paged: bool = False,
                    wdtype: Optional[str] = None,
                    kv_dtype: Optional[str] = None,
                    extras: Optional[Dict[str, np.ndarray]] = None) -> List[int]:
    """Single-request reference path (the oracle for engine equivalence).

    Runs with bucketing OFF — exact-length prefill — and a DENSE cache by
    default, so equivalence tests against a bucketed/paged/chunked engine
    actually exercise the padded-prefill + replay, page-table and
    chunk-streaming paths instead of comparing them to themselves. With
    wdtype/kv_dtype this is the dense INT8 oracle: row quantization is
    layout-independent AND schedule-independent (prefill attends the rounded
    rows the cache stores — models/transformer._round_kv), so a paged or
    chunked int8 engine must reproduce its tokens exactly."""
    eng = ServeEngine(model, n_slots=1, max_len=max_len, params=params,
                      bucket_prompts=False, paged=paged, wdtype=wdtype,
                      kv_dtype=kv_dtype)
    req = eng.submit(prompt, max_new_tokens=n_tokens, extras=extras)
    eng.run_to_completion()
    return req.out_tokens
