"""Decoder-only transformer — covers the dense, moe and vlm families.

Design notes (DESIGN.md §6):
  * layer-stacked params + lax.scan over layers (jax.checkpoint policy on the
    body) → HLO size O(1) in depth; 64-layer qwen2.5 compiles like 12 layers.
  * GQA executes with KV heads expanded to H and head-padded to a multiple of
    `head_pad` (the TP axis size): attention then shards over the flat head
    dim for every arch, including the 15/40/10-head ones that don't divide 16.
    Dead pad heads carry zeros; their wo rows don't exist, so outputs are exact.
  * sharded-vocab chunked cross-entropy: logits are never materialized beyond
    (B, ce_chunk, V) and the vocab dim stays sharded on `model`.
  * vlm (llava-next): precomputed anyres patch embeddings (frontend STUB)
    overwrite the leading n_image_tokens embedding positions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.common import ParamDef, act_fn, apply_rope, glu_act, rms_norm, softcap
from repro.models.quantized import (
    SCALE_DTYPE, dequantize_kv_rows, qeinsum, quantize_kv_rows)


def _noop_constrain(x, *logical):
    return x


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """Execution-strategy knobs (everything performance, nothing semantic)."""
    attn_impl: str = "chunked"        # chunked | reference
    q_chunk: int = 1024
    kv_chunk: int = 1024
    ce_chunk: int = 512
    remat: str = "none"               # none | dots | full
    # Megatron-style sequence parallelism on the residual stream: the layer
    # carry is sharded seq→model, cutting saved-activation memory 16×; GSPMD
    # inserts the all-gather/reduce-scatter pair at the attention boundary.
    act_seq_shard: bool = False
    moe_group: Optional[int] = None   # override cfg.moe_group
    constrain: Callable = _noop_constrain
    # dry-run cost probes: statically unroll every internal lax.scan so
    # cost_analysis counts loop bodies exactly (see common.scan_or_unroll)
    unroll_scans: bool = False

    @property
    def seq_axis(self) -> Optional[str]:
        return "seq" if self.act_seq_shard else None


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def attn_schema(cfg, L: int, prefix: str = "") -> Dict[str, Any]:
    """QKV/O projections with distribution-time head padding (ArchConfig.tp_pad).

    Dead heads are masked to zero contribution in `attn_block` — outputs are
    exactly the real-head model's, and dead slices receive zero gradient."""
    d, hd = cfg.d_model, cfg.head_dim
    hp, kvp = cfg.n_heads_padded, cfg.kv_pad
    sch = {
        prefix + "wq": ParamDef((L, d, hp, hd), ("layers", "embed", "heads", None)),
        prefix + "wk": ParamDef((L, d, kvp, hd), ("layers", "embed", "heads", None)),
        prefix + "wv": ParamDef((L, d, kvp, hd), ("layers", "embed", "heads", None)),
        prefix + "wo": ParamDef((L, hp, hd, d), ("layers", "heads", None, "embed")),
    }
    if cfg.qkv_bias and not prefix:
        sch["bq"] = ParamDef((L, hp, hd), ("layers", "heads", None), init="zeros")
        sch["bk"] = ParamDef((L, kvp, hd), ("layers", "heads", None), init="zeros")
        sch["bv"] = ParamDef((L, kvp, hd), ("layers", "heads", None), init="zeros")
    return sch


def head_mask(cfg, dtype=jnp.float32) -> jnp.ndarray:
    """(Hp,) — 1 for real heads (kv < n_kv_heads and g < q_per_kv), else 0."""
    kvp, gp = cfg.padded_kv_group
    kvi = jnp.arange(kvp * gp) // gp
    gi = jnp.arange(kvp * gp) % gp
    return ((kvi < cfg.n_kv_heads) & (gi < cfg.q_per_kv)).astype(dtype)


def schema(cfg) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    L, v = cfg.n_layers, cfg.padded_vocab
    norm_init = "zeros" if cfg.norm_plus_one else "ones"
    layers: Dict[str, Any] = {
        "attn_norm": ParamDef((L, d), ("layers", None), init=norm_init),
        "ffn_norm": ParamDef((L, d), ("layers", None), init=norm_init),
    }
    if cfg.attn_kind == "mla":
        from repro.models import mla as mla_mod
        layers.update(mla_mod.mla_schema(cfg, L))
    else:
        layers.update(attn_schema(cfg, L))
    if cfg.family == "moe":
        layers.update(moe_mod.moe_schema(cfg, L))
    else:
        layers["w1"] = ParamDef((L, d, f), ("layers", "embed", "ff"))
        layers["w3"] = ParamDef((L, d, f), ("layers", "embed", "ff"))
        layers["w2"] = ParamDef((L, f, d), ("layers", "ff", "embed"))
    sch = {
        "embed": ParamDef((v, d), ("vocab", "embed"), init="small_normal"),
        "final_norm": ParamDef((d,), (None,), init=norm_init),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        sch["lm_head"] = ParamDef((v, d), ("vocab", "embed"), init="small_normal")
    return sch


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _project_qkv(x, p, cfg, prefix=""):
    q = qeinsum("bsd,dhk->bshk", x, p[prefix + "wq"])
    k = qeinsum("bsd,dhk->bshk", x, p[prefix + "wk"])
    v = qeinsum("bsd,dhk->bshk", x, p[prefix + "wv"])
    if "bq" in p and not prefix:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def _expand_kv(k, v, cfg):
    """(B,S,KVp,D) → (B,S,Hp,D) by repeating each kv head g_pad times."""
    gp = cfg.g_pad
    if gp > 1:
        k = jnp.repeat(k, gp, axis=2)
        v = jnp.repeat(v, gp, axis=2)
    return k, v


def _round_rows(rows, kv_round):
    """Round one K/V (or MLA latent) tensor through the cache storage dtype.

    int8 takes the full quantize→dequantize round trip (the map the
    paste/decode/chunk write paths apply); any float storage dtype — bf16 or
    fp8 e5m2 — is a cast round trip with no scale tensors."""
    if kv_round is None:
        return rows
    if kv_round == jnp.int8:
        q, s = quantize_kv_rows(rows)
        return dequantize_kv_rows(q, s, rows.dtype)
    return rows.astype(kv_round).astype(rows.dtype)


def _round_kv(k, v, kv_round):
    """Round K/V through the cache storage dtype before attention.

    `kv_round` is the storage dtype (or None = lossless storage). Prefill
    attention must see the SAME values the cache will hold — otherwise a
    chunked prefill (which attends already-pasted pool rows) and a monolithic
    prefill (which would attend fresh activations) diverge numerically and
    the chunked-vs-oracle token-exactness breaks. This also makes prefill and
    decode numerics consistent: decode attention always reads stored rows.
    """
    return _round_rows(k, kv_round), _round_rows(v, kv_round)


def _pool_entry(**pools):
    """Updated-cache dict from write results, dropping absent scale pools."""
    return {key: val for key, val in pools.items() if val is not None}


def attn_block(x, p, cfg, opts: ExecOptions, *, positions,
               mode: str, cache: Optional[dict] = None, kv_round=None,
               chunk: Optional[dict] = None, causal: bool = True):
    """Self-attention — THE per-layer attention core. Returns
    (out, new_cache_entry).

    One body owns all four execution modes, for every attention family (GQA
    below; `cfg.attn_kind == 'mla'` dispatches to `models/mla.py`, which
    shares the same mode contract and write helpers):
      'train'   full attention over S positions; no cache emission (the layer
                scan carries nothing dead).
      'prefill' full attention; emits per-layer K/V rows for the engine's
                paste. Lossy caches attend the rounded values the cache will
                store (`_round_kv` / kv_round).
      'decode'  one position per sequence; writes the new row into the dense
                (B, Smax, KV, D) cache or the paged pool (via
                cache['page_table']) and attends the stored rows.
      'chunk'   chunked prefill (B=1): streams C rows into the paged pool
                through the slot's page row (`chunk=` dict with start (1,),
                length (1,), page_row (pages_per_seq,)) and runs chunk
                attention against the slot's live pages.
    `causal=False` (train/prefill only) serves the encdec encoder. int8
    storage is detected by the scale pools ('ks'/'vs') riding in `cache`;
    fp8 (e5m2) storage is a bare dtype cast, no scales.
    """
    if cfg.attn_kind == "mla":
        from repro.models import mla as mla_mod
        return mla_mod.mla_attn_block(
            x, p, cfg, opts, positions=positions, mode=mode, cache=cache,
            kv_round=kv_round, chunk=chunk, causal=causal)
    c = opts.constrain
    q, k, v = _project_qkv(x, p, cfg)
    q = apply_rope(q, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    scale = cfg.head_dim ** -0.5
    kvp, gp = cfg.padded_kv_group

    if mode in ("train", "prefill"):
        ka, va = (k, v) if mode == "train" else _round_kv(k, v, kv_round)
        kx, vx = _expand_kv(ka, va, cfg)
        qp = c(q[:, :, :, None, :], "batchlike", None, "heads_flat", None, None)
        kx = c(kx, "batchlike", None, "heads_flat", None)
        vx = c(vx, "batchlike", None, "heads_flat", None)
        o = attn_mod.attention(
            qp, kx, vx, causal=causal, window=cfg.window, scale=scale,
            impl=opts.attn_impl, q_chunk=opts.q_chunk, kv_chunk=opts.kv_chunk,
            unroll=opts.unroll_scans)
        o = o[:, :, :, 0, :]
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    elif mode == "chunk":
        assert cache is not None and chunk is not None
        b, C = x.shape[:2]
        pk, psk = _write_chunk(cache, "k", k[0], chunk)
        pv, psv = _write_chunk(cache, "v", v[0], chunk)
        qg = q.reshape(b, C, kvp, gp, cfg.head_dim)
        o = attn_mod.chunk_attention_paged(
            qg, pk, pv, chunk["page_row"][None], chunk["start"],
            kv_len=chunk["start"] + chunk["length"],
            window=cfg.window, scale=scale, k_scale=psk, v_scale=psv)
        o = o.reshape(b, C, cfg.n_heads_padded, cfg.head_dim)
        new_cache = _pool_entry(k=pk, v=pv, ks=psk, vs=psv)
    else:  # decode
        assert cache is not None
        b = x.shape[0]
        pos_b = positions.reshape(-1)             # (B,)
        page_table = cache.get("page_table")
        # write this step's k/v at each sequence position `pos_b`
        k_cache, k_scale = _write_row(cache, "k", k, pos_b, page_table)
        v_cache, v_scale = _write_row(cache, "v", v, pos_b, page_table)
        qg = q.reshape(b, 1, kvp, gp, cfg.head_dim)
        o = attn_mod.decode_attention(
            qg, k_cache, v_cache, pos_b + 1,
            window=cfg.window, scale=scale, page_table=page_table,
            k_scale=k_scale, v_scale=v_scale)
        o = o.reshape(b, 1, cfg.n_heads_padded, cfg.head_dim)
        new_cache = _pool_entry(k=k_cache, v=v_cache, ks=k_scale, vs=v_scale)

    o = o * head_mask(cfg, o.dtype)[None, None, :, None]
    out = qeinsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


def _write_cache(cache, kv_new, positions):
    """cache: (B, Smax, KV, D); kv_new: (B, 1, KV, D); positions: (B,).

    One-hot masked update — GSPMD-friendly on a sequence-sharded cache (no
    dynamic-slice cross-shard traffic; each shard updates only its slice)."""
    smax = cache.shape[1]
    onehot = (jnp.arange(smax)[None, :] == positions[:, None])  # (B, Smax)
    oh = onehot[:, :, None, None].astype(cache.dtype)
    return cache * (1 - oh) + oh * kv_new.astype(cache.dtype)


def _write_cache_q(cache, scales, kv_new, positions):
    """Dense int8 KV write: quantize the new (B,1,KV,D) row per (token, kv
    head) and masked-set both the int8 cache row and its bf16 scale. Same
    one-hot masking as `_write_cache` but via `where` — int8 arithmetic has
    no exact multiply-by-mask. Returns (cache, scales)."""
    q, s = quantize_kv_rows(kv_new)                 # (B,1,KV,D) i8, (B,1,KV)
    smax = cache.shape[1]
    onehot = (jnp.arange(smax)[None, :] == positions[:, None])  # (B, Smax)
    new_c = jnp.where(onehot[:, :, None, None], q, cache)
    new_s = jnp.where(onehot[:, :, None], s, scales)
    return new_c, new_s


def _write_cache_paged(pool, kv_new, positions, page_table):
    """pool: (n_pages, ps, KV, D); kv_new: (B, 1, KV, D); positions: (B,);
    page_table: (B, pages_per_seq).

    Scatter each sequence's new row into pool[table[b, pos//ps], pos%ps].
    Live sequences own disjoint pages, so the scatter indices never collide;
    retired slots all point at the null page, whose rows are never attended
    to. Positions past the table's logical depth clamp (jnp gather semantics)
    onto the slot's last entry — the engine zeroes retired rows, so drift
    lands on the null page too. Single-host layout; the paged pool trades the
    one-hot update's GSPMD-friendliness for O(live tokens) memory."""
    ps = pool.shape[1]
    logical = jnp.minimum(positions // ps, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    return pool.at[page, positions % ps].set(
        kv_new[:, 0].astype(pool.dtype))


def _write_cache_paged_q(pool, spool, kv_new, positions, page_table):
    """Paged int8 KV write: same scatter as `_write_cache_paged`, with the
    row quantized first and its scale scattered into the (n_pages, ps, KV)
    scale pool. The quantized bytes are identical to the dense `_write_cache_q`
    path — layout-independence is what keeps paged int8 engines token-exact
    against the dense int8 oracle."""
    q, s = quantize_kv_rows(kv_new)
    ps = pool.shape[1]
    logical = jnp.minimum(positions // ps, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    return (pool.at[page, positions % ps].set(q[:, 0]),
            spool.at[page, positions % ps].set(s[:, 0]))


def _chunk_pages(pos, length, page_row, ps):
    """(page, row) scatter targets for a prefill chunk's K/V rows.

    pos: (C,) global positions start+i; rows past `length` (chunk padding)
    and positions past the table's logical depth route to the NULL page (0),
    so padding never touches reserved pages — the capacity edge where a
    prompt's last chunk exactly fills its final page stays clean."""
    logical = jnp.minimum(pos // ps, page_row.shape[0] - 1)
    real = jnp.arange(pos.shape[0]) < length
    page = jnp.where(real, page_row[logical], 0)
    return page, pos % ps


def _write_chunk_paged(pool, rows, start, length, page_row):
    """pool: (n_pages, ps, KV, D); rows: (C, KV, D) — stream one prefill
    chunk's K/V straight into the page pool at global positions start+i."""
    page, r = _chunk_pages(start + jnp.arange(rows.shape[0]), length,
                           page_row, pool.shape[1])
    return pool.at[page, r].set(rows.astype(pool.dtype))


def _write_chunk_paged_q(pool, spool, rows, start, length, page_row):
    """Paged int8 chunk write: same scatter as `_write_chunk_paged` with the
    rows quantized per (position, kv head) first — identical bytes to the
    dense/paged decode write paths, which is what keeps chunked int8 engines
    token-exact against the dense int8 oracle."""
    q, s = quantize_kv_rows(rows)
    page, r = _chunk_pages(start + jnp.arange(rows.shape[0]), length,
                           page_row, pool.shape[1])
    return pool.at[page, r].set(q), spool.at[page, r].set(s)


def _write_row(cache, key, kv_new, positions, page_table):
    """Write one decode row into `cache[key]` — dense or paged, any storage
    dtype. Returns (pool, scales-or-None). int8 storage is detected by the
    sibling scale pool `cache[key + 's']`; float storage (f32/bf16/fp8) is a
    bare cast on write."""
    if key + "s" in cache:
        if page_table is None:
            return _write_cache_q(cache[key], cache[key + "s"], kv_new,
                                  positions)
        return _write_cache_paged_q(cache[key], cache[key + "s"], kv_new,
                                    positions, page_table)
    if page_table is None:
        return _write_cache(cache[key], kv_new, positions), None
    return _write_cache_paged(cache[key], kv_new, positions, page_table), None


def _write_chunk(cache, key, rows, chunk):
    """Stream one prefill chunk's (C, KV, D) rows into the paged pool
    `cache[key]` at global positions start+i. Returns (pool, scales-or-None);
    same int8 detection as `_write_row`."""
    start, length = chunk["start"][0], chunk["length"][0]
    if key + "s" in cache:
        return _write_chunk_paged_q(cache[key], cache[key + "s"], rows,
                                    start, length, chunk["page_row"])
    return _write_chunk_paged(cache[key], rows, start, length,
                              chunk["page_row"]), None


_POOL_KEYS = ("k", "v", "ks", "vs")


def _pools_of(cache):
    """The layer-stacked K/V pools present in a cache — family-agnostic:
    GQA carries k/v (+ int8 scale pools), MLA a single latent pool."""
    return {key: cache[key] for key in _POOL_KEYS if key in cache}


def pool_data_keys(cache) -> Tuple[str, ...]:
    """Base (unscaled) pool keys present in a cache or prefill dict —
    ("k", "v") for GQA, ("k",) for MLA's single latent pool. THE way
    engine code iterates pools (contract R6): a spelled-out key tuple at a
    call site silently skips pools the family doesn't have."""
    return tuple(key for key in ("k", "v") if key in cache)


def copy_pool_page(cache, src, dst):
    """Copy-on-write page clone: duplicate physical page `src`'s rows into
    `dst` across every pool in the cache (k/v, int8 scale pools, MLA's
    single latent pool — whatever `_POOL_KEYS` members are present), all
    layers at once.

    The prefix cache (PR 8) uses this when a new request's prompt fully
    covers a cached page that its replay decode step will overwrite (the
    page containing position plen-1): instead of recomputing that page's
    K/V with one more prefill chunk, the engine clones the cached bytes
    into a private page and maps THAT — the shared original stays
    read-only. Pages are schedule-independent bytes (`_round_kv`), so the
    clone is exactly what a cold prefill would have produced."""
    c = dict(cache)
    for key in _POOL_KEYS:
        if key in c:
            c[key] = c[key].at[:, dst].set(c[key][:, src])
    return c


def gather_pool_pages(cache, page_ids):
    """Snapshot physical pages `page_ids` ((M,) int32) out of every pool in
    the cache: {key: (L, M, page_size, ...)} — the migration outbox.

    Mesh-free on purpose: `serve/sharded` wraps this in shard_map with the
    pool's page axis device-local, all_gathers the outboxes, and scatters
    with `set_pool_page`. Because the gather snapshots BEFORE any scatter
    runs, a page may be both exported and overwritten in the same move wave.
    Pool-native bytes move as-is — an int8 pool's int8 rows + bf16 scale rows
    ARE its block-compressed wire format (half the bf16 bytes), and decode's
    fused dequant is the receive-side decompress — so migrated pages are
    bit-exact under the schedule-independent KV rounding contract."""
    return {key: jnp.take(cache[key], page_ids, axis=1)
            for key in _POOL_KEYS if key in cache}


def set_pool_page(cache, dst, rows):
    """Write one gathered page (`rows`: {key: (L, page_size, ...)}, e.g. an
    all_gathered `gather_pool_pages` outbox sliced to one move) into local
    physical page `dst` across every pool. `dst` may be a traced scalar;
    dst == 0 lands on the null page, which absorbs garbage by contract."""
    c = dict(cache)
    for key in _POOL_KEYS:
        if key in c:
            c[key] = c[key].at[:, dst].set(rows[key])
    return c


def prefill_chunk(params, batch, cache, cfg, opts: ExecOptions):
    """One fixed-size chunk of page-granular prefill (PR 4).

    Computes the chunk's K/V, streams them into the shared page pool through
    the slot's page row, and runs chunk attention against the slot's live
    pages (earlier chunks + this one) — so a long prompt prefills in
    ceil(plen/C) bounded-latency steps interleaved with the decode batch,
    with one compile total (C is fixed) instead of one per bucket.

    batch:
      tokens   (1, C) int32 — chunk tokens, zero-padded past `length`
      start    (1,)   int32 — global position of tokens[:, 0]
      length   (1,)   int32 — real rows in this chunk
      page_row (pages_per_seq,) int32 — slot's physical page per logical
               page (null page 0 beyond the reservation)
      patch_rows/n_patch (vlm) — patch-embedding rows overlapping the chunk

    Only the K/V pools (and int8 scale pools) change: the slot's page_table
    row and `pos` are stamped by the engine AFTER the last chunk, so
    mid-prefill slots stay invisible to the batched decode step (its garbage
    writes for them land on the null page — the idle-slot-drift guard).

    `start` need not be 0 for a slot's FIRST chunk: the prefix cache (PR 8)
    resumes prefill mid-prompt after cached pages. The chunk's attention
    gathers the slot's whole live span [0, start+length) through `page_row`
    — shared cached pages included — while its writes only ever target
    logical pages >= start // page_size (start is page-aligned on resume),
    so shared pages are read-only by construction. Schedule-independent KV
    rounding guarantees the cached pages hold byte-identical values to the
    cold prefill this replaces, which is what keeps cache-hit admissions
    token-exact.

    The scan body is a thin wrapper over `layer_fn(mode='chunk')` — the
    per-layer math lives ONCE in `attn_block`, so every execution path
    (train/prefill/decode/chunk, every attention family) inherits any
    layer-math change from the same body.
    """
    tokens = batch["tokens"]
    start, length = batch["start"], batch["length"]
    b, C = tokens.shape
    positions = start[:, None] + jnp.arange(C)[None, :]
    x = embed_tokens(params, tokens, cfg, opts)
    if cfg.family == "vlm" and "patch_rows" in batch:
        in_patch = (positions < batch["n_patch"][:, None])[..., None]
        x = jnp.where(in_patch, batch["patch_rows"].astype(x.dtype), x)
    chunk = {"start": start, "length": length, "page_row": batch["page_row"]}
    dyn = functools.partial(jax.lax.dynamic_index_in_dim, axis=0,
                            keepdims=False)

    def body(carry, xs):
        h, pools = carry
        lp, i = xs
        layer_cache = {key: dyn(val, i) for key, val in pools.items()}
        h, new_cache = layer_fn(h, lp, cfg, opts, positions=positions,
                                mode="chunk", cache=layer_cache, chunk=chunk)
        pools = {key: jax.lax.dynamic_update_index_in_dim(
            val, new_cache[key], i, 0) for key, val in pools.items()}
        return (h, pools), None

    from repro.models.common import scan_or_unroll
    (_, pools), _ = scan_or_unroll(
        body, (x, _pools_of(cache)),
        (params["layers"], jnp.arange(cfg.n_layers)),
        unroll=opts.unroll_scans)
    return dict(cache, **pools)


def dense_ffn(x, p, cfg, opts: ExecOptions):
    c = opts.constrain
    act = act_fn(glu_act(cfg.activation))
    h = act(qeinsum("bsd,df->bsf", x, p["w1"])) \
        * qeinsum("bsd,df->bsf", x, p["w3"])
    h = c(h, "batchlike", None, "ff")
    return qeinsum("bsf,fd->bsd", h, p["w2"])


def layer_fn(x, lp, cfg, opts: ExecOptions, *, positions, mode,
             cache: Optional[dict] = None, kv_round=None, chunk=None):
    c = opts.constrain
    x = c(x, "batchlike", opts.seq_axis, None)
    a, new_cache = attn_block(
        rms_norm(x, lp["attn_norm"], plus_one=cfg.norm_plus_one),
        lp, cfg, opts, positions=positions, mode=mode, cache=cache,
        kv_round=kv_round, chunk=chunk)
    x = x + a
    h = rms_norm(x, lp["ffn_norm"], plus_one=cfg.norm_plus_one)
    if cfg.family == "moe":
        f = moe_mod.moe_ffn(h, lp, _maybe_group(cfg, opts), constrain=c)
    else:
        f = dense_ffn(h, lp, cfg, opts)
    return x + f, new_cache


def _maybe_group(cfg, opts):
    if opts.moe_group and opts.moe_group != cfg.moe_group:
        return dataclasses.replace(cfg, moe_group=opts.moe_group)
    return cfg


# ---------------------------------------------------------------------------
# Embedding / loss
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg, opts, patch_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(x.dtype)
    if patch_embeds is not None:  # vlm stub: overwrite leading image positions
        p = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, p:]], axis=1)
    return opts.constrain(x, "batchlike", None, None)


def lm_head_weights(params, cfg):
    return params.get("lm_head", params["embed"])


def chunked_ce_loss(hidden, emb, labels, cfg, opts: ExecOptions):
    """Σ CE over sequence chunks; vocab stays sharded; fp32 logsumexp."""
    hidden = opts.constrain(hidden, "batchlike", None, None)
    b, s, d = hidden.shape
    chunk = min(opts.ce_chunk, s)
    assert s % chunk == 0
    n = s // chunk
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, y = xs
        logits = jnp.einsum("bsd,vd->bsv", h, emb).astype(jnp.float32)
        logits = softcap(logits, cfg.logit_softcap)
        logits = opts.constrain(logits, "batchlike", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        oh = jax.nn.one_hot(jnp.maximum(y, 0), logits.shape[-1],
                            dtype=logits.dtype)
        ll = jnp.sum(logits * oh, axis=-1)
        w = (y >= 0).astype(jnp.float32)
        loss, cnt = carry
        return (loss + jnp.sum(w * (lse - ll)), cnt + jnp.sum(w)), None

    from repro.models.common import scan_or_unroll
    (loss, cnt), _ = scan_or_unroll(
        remat_wrap(body, "full" if opts.remat != "none" else "none"),
        (jnp.float32(0.0), jnp.float32(0.0)), (hc, yc),
        unroll=opts.unroll_scans)
    return loss / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def _stack_scan(params, x, cfg, opts, *, positions, mode, cache=None,
                kv_round=None):
    """lax.scan over stacked layers. cache (if given) is stacked on axis 0."""
    lp = params["layers"]

    def body(h, xs):
        layer_params, layer_cache = xs
        h, new_cache = layer_fn(h, layer_params, cfg, opts,
                                positions=positions, mode=mode,
                                cache=layer_cache, kv_round=kv_round)
        return h, new_cache

    from repro.models.common import scan_or_unroll
    body = remat_wrap(body, opts.remat)
    x, new_cache = scan_or_unroll(body, x, (lp, cache),
                                  unroll=opts.unroll_scans)
    return x, new_cache


def forward_hidden(params, tokens, cfg, opts, *, patch_embeds=None,
                   mode="train", kv_round=None):
    b, s = tokens.shape
    x = embed_tokens(params, tokens, cfg, opts, patch_embeds)
    positions = jnp.arange(s)[None, :]
    x, cache = _stack_scan(params, x, cfg, opts, positions=positions,
                           mode=mode, kv_round=kv_round)
    return rms_norm(x, params["final_norm"], plus_one=cfg.norm_plus_one), cache


def _kv_round_of(batch):
    """Storage dtype of a lossy KV cache, from the serving engine's zero-size
    `kv_round` batch marker (absent = lossless storage, attend fresh K/V)."""
    marker = batch.get("kv_round")
    return None if marker is None else marker.dtype


def train_loss(params, batch, cfg, opts: ExecOptions):
    hidden, _ = forward_hidden(params, batch["tokens"], cfg, opts,
                               patch_embeds=batch.get("patch_embeds"),
                               mode="train")
    labels = batch["labels"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        p = batch["patch_embeds"].shape[1]
        mask = jnp.arange(labels.shape[1])[None, :] >= p
        labels = jnp.where(mask, labels, -1)
    loss = chunked_ce_loss(hidden, lm_head_weights(params, cfg), labels, cfg, opts)
    return loss, {"loss": loss}


def prefill_cache(params, batch, cfg, opts: ExecOptions):
    """Cache-only prefill: skips the LM-head projection.

    The serve engine's replay admission discards prefill logits (the first
    output token comes from replaying the last prompt token through the
    decode step), so this variant avoids a d_model×vocab matmul per admitted
    request on the serving hot path."""
    _, kv = forward_hidden(params, batch["tokens"], cfg, opts,
                           patch_embeds=batch.get("patch_embeds"),
                           mode="prefill", kv_round=_kv_round_of(batch))
    b, s = batch["tokens"].shape
    return dict(kv, pos=jnp.full((b,), s, jnp.int32))


def prefill(params, batch, cfg, opts: ExecOptions):
    """Returns (last-position logits, cache dict)."""
    hidden, kv = forward_hidden(params, batch["tokens"], cfg, opts,
                                patch_embeds=batch.get("patch_embeds"),
                                mode="prefill", kv_round=_kv_round_of(batch))
    last = hidden[:, -1:, :]
    logits = jnp.einsum("bsd,vd->bsv", last, lm_head_weights(params, cfg))
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    b, s = batch["tokens"].shape
    return logits, dict(kv, pos=jnp.full((b,), s, jnp.int32))


def decode_step(params, batch, cache, cfg, opts: ExecOptions):
    """One token step. batch: {'tokens': (B,1)}; cache from prefill/init.

    The layer-stacked KV cache rides the scan CARRY and is updated in place
    with dynamic-update-slice — streaming it through scan xs/ys instead
    double-buffers the whole cache as temps (measured +14 GiB/device on
    gemma-7b × decode_32k; EXPERIMENTS.md §Perf P0c)."""
    tokens = batch["tokens"]
    positions = cache["pos"]                      # (B,) next position to write
    page_table = cache.get("page_table")          # read-only within the step
    x = embed_tokens(params, tokens, cfg, opts)
    dyn = functools.partial(jax.lax.dynamic_index_in_dim, axis=0,
                            keepdims=False)

    def body(carry, xs):
        h, pools = carry
        lp, i = xs
        layer_cache = {key: dyn(val, i) for key, val in pools.items()}
        if page_table is not None:
            layer_cache["page_table"] = page_table
        h, new_cache = layer_fn(h, lp, cfg, opts,
                                positions=positions[:, None], mode="decode",
                                cache=layer_cache)
        pools = {key: jax.lax.dynamic_update_index_in_dim(
            val, new_cache[key], i, 0) for key, val in pools.items()}
        return (h, pools), None

    from repro.models.common import scan_or_unroll
    (x, pools), _ = scan_or_unroll(
        body, (x, _pools_of(cache)),
        (params["layers"], jnp.arange(cfg.n_layers)),
        unroll=opts.unroll_scans)
    x = rms_norm(x, params["final_norm"], plus_one=cfg.norm_plus_one)
    logits = jnp.einsum("bsd,vd->bsv", x, lm_head_weights(params, cfg))
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    new_cache = dict(pools, pos=positions + 1)
    if page_table is not None:
        new_cache["page_table"] = page_table
    return logits, new_cache


def paged_kv_shapes(L: int, batch: int, max_len: int, kv: int, hd: int,
                    dtype, page_size: int, n_pages: Optional[int],
                    keys: Tuple[str, ...] = ("k", "v")):
    """Shared paged-pool sizing contract (transformer + encdec cache_shape):
    (L, n_pages, page_size, KV, D) pools + a (B, max_len // page_size) page
    table. `keys` names the pools — ('k', 'v') for GQA, ('k',) for MLA's
    single latent pool. Physical page 0 is reserved by the serving engine as
    the null page, so `n_pages` defaults to one more than the dense worst
    case (callers size it down to expected live tokens)."""
    assert max_len % page_size == 0, (max_len, page_size)
    pages_per_seq = max_len // page_size
    if n_pages is None:
        n_pages = 1 + batch * pages_per_seq
    shapes = {
        key: jax.ShapeDtypeStruct((L, n_pages, page_size, kv, hd), dtype)
        for key in keys}
    shapes["page_table"] = jax.ShapeDtypeStruct((batch, pages_per_seq),
                                                jnp.int32)
    shapes["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    if dtype == jnp.int8:   # per-row (token × kv-head) dequant scales
        for key in keys:
            shapes[key + "s"] = jax.ShapeDtypeStruct(
                (L, n_pages, page_size, kv), SCALE_DTYPE)
    return shapes


def cache_shape(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
                page_size: Optional[int] = None,
                n_pages: Optional[int] = None):
    """Abstract KV-cache pytree (stacked over layers).

    GQA: kv_pad heads × head_dim K and V rows. MLA (cfg.attn_kind='mla'):
    ONE latent pool of (kv_lora_rank + qk_rope_dim)-wide rows, KV-head dim 1
    — the per-token bytes the latent family exists to shrink.
    Dense (default): per-slot (L, B, max_len, KV, D) rows.
    Paged (`page_size=`): shared page pools — see `paged_kv_shapes`.
    dtype=jnp.int8 (either layout): rows stored int8 plus per-row bf16 dequant
    scale tensors ('ks'/'vs') — the serving engine's kv_dtype='int8' layout.
    dtype=jnp.float8_e5m2: bare fp8 rows, no scale tensors (dense layout;
    the engine keeps paged fp8 pools a follow-on)."""
    L = cfg.n_layers
    if cfg.attn_kind == "mla":
        kv, hd, keys = 1, cfg.kv_lora_rank + cfg.qk_rope_dim, ("k",)
    else:
        kv, hd, keys = cfg.kv_pad, cfg.head_dim, ("k", "v")
    if page_size is None:
        shapes = {
            key: jax.ShapeDtypeStruct((L, batch, max_len, kv, hd), dtype)
            for key in keys}
        shapes["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
        if dtype == jnp.int8:
            for key in keys:
                shapes[key + "s"] = jax.ShapeDtypeStruct(
                    (L, batch, max_len, kv), SCALE_DTYPE)
        return shapes
    return paged_kv_shapes(L, batch, max_len, kv, hd, dtype, page_size,
                           n_pages, keys)
