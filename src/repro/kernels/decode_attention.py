"""Pallas TPU kernel: single-query (decode) attention against a KV cache.

The decode hot loop issues one query per sequence against a (B, Smax, KV, D)
cache where only the first `kv_len[b]` rows are valid. This kernel streams the
cache through VMEM in (block_k × D) tiles with FlashAttention-style online
softmax, so the cache is read once from HBM and never materialized, copied, or
cast wholesale (the failure mode the pure-jnp path risks on long contexts).

GQA is native: each grid step holds one (block_k, KV, D) tile of the cache
— every KV head of block_k positions — and loops over the heads; a head's
query tile is its (G, D) group, so both matmuls per head are (G×D)·(D×block_k)
and (G×block_k)·(block_k×D) — MXU work proportional to real heads only. The
tile spans all KV heads because the TPU compiler requires a block's last two
dims to be (8, 128)-divisible or whole: a one-head (…, 1, D) block of a
KV = 5 cache does not compile.

Grid: (B, n_k) — the batch is embarrassingly parallel; the k-block sweep is
innermost ('arbitrary') so the per-head m/l/acc running statistics live in
VMEM scratch across it. `kv_len` rides scalar prefetch (SMEM), which
lets `pl.when` skip tiles that lie entirely beyond the valid prefix (or, with
a sliding window, before it): a 4k-deep cache at kv_len=300 runs 3 tiles, not
32.

Paged mode (`page_table=`): the caches are shared page POOLS of shape
(n_pages, page_size, KV, D) — no batch dim — and a (B, pages_per_seq) int32
page table maps each sequence's logical k-blocks to physical pages. The table
rides scalar prefetch alongside `kv_len`, so the K/V BlockSpec index_maps
gather tiles *through* it: tile ik of sequence ib streams from physical page
`page_table[ib, ik // blocks_per_page]`. Logical positions (and therefore the
kv_len / sliding-window masks and the `pl.when` tile-liveness skip) are
unchanged — a dead logical page costs one skipped `pl.when` body, and the
serving engine points unmapped table entries at a reserved null page so the
prefetch DMA always has a valid source.

Lane-dense paged mode (`layer=`, float pools): the pools are the model's
layer-STACKED (L, n_pages, R, 128) pools in the lane-dense page format
(models/attention: head h, token t at row h·(page_size·D/128) + t·D // 128,
lane (t·D) % 128), read in place: the layer index rides scalar prefetch
next to the page table, and each grid step DMAs one whole (1, 1, R, 128)
page — unpadded, and no per-layer slice of the pool is ever taken. With
T = 128 // D tokens per row, a head's query is placed T times, copy j in
lanes [j·D, (j+1)·D) with zeros elsewhere, so one (T·G, 128)·(128, R/KV)
product scores every token of the page: row j·G + g of it scores token
r·T + j of row r. Copy j keeps its own online-softmax statistics; the
kernel writes each copy's normalized accumulator and log-sum-exp, and
`fold_lanes` merges the T copies once, outside the kernel. Masks stay
positional.

INT8 mode (`k_scale=`/`v_scale=`): the caches/pools store int8 rows and the
scales hold one bf16 factor per (position, kv head) — cache shape minus D.
The scale tiles ride as VMEM operands right next to their K/V tiles (same
index_map, so the paged gather walks the page table once for both), and
dequant `int8 → f32 × scale` is fused into the tile load feeding the MXU —
the cache crosses HBM at 1 byte/element + 2/D scale overhead, which is what
halves decode HBM traffic vs the bf16 pool (the tokens/s bound at batch ≤
n_slots). The paper's NPUs are 15 TOPS INT8 (§II); this is the KV half of
that datapath (kernels/int8_matmul is the weight half).

`interpret=True` runs the same kernel on CPU — the tests' numerics oracle is
`models.attention`'s reference path.

NOT YET COVERED — MLA latent rows (models/mla.py): the latent family passes
ONE (kv_lora_rank + qk_rope_dim)-wide pool as both K and V with values the
leading kv_lora_rank columns of each row (`v_dim=` in
models/attention.decode_attention). A kernel-side latent gather would load
each row once and slice V in-register; until then `v_dim` forces the exact
jnp reference path, which is the CPU oracle anyway. fp8 (e5m2) caches have
no kernel: they run the jnp path off-TPU and are refused on TPU
(models/attention._kernel_dispatch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def paged_index_maps(bpp: int, *, n_prefetch: int):
    """(kv_map, s_map) BlockSpec index_map factories for page-pool gathers
    of (page_size, KV, D) pages (int8 pools and their scales; lane-dense
    pools use `layer_page_map`).

    Shared by the decode kernel below and the chunk-prefill kernel in
    kernels/flash_attention: both stream K/V (and int8 scale) tiles out of a
    (n_pages, page_size, KV, D) pool through a scalar-prefetched page table.
    A tile spans ALL KV heads — (1, block_k, KV, D) and (1, block_k, KV) —
    so its last two block dims equal the pool's and satisfy the TPU tiling
    rule for any head count (a one-head (…, 1, D) block of a KV = 5 pool is
    refused by the compiler); the kernel bodies loop over heads.

    Grid convention: (batch, [q-block,] k-block) with the K-BLOCK INDEX
    LAST among grid dims and the PAGE TABLE LAST among the `n_prefetch`
    scalar-prefetch refs. `bpp` is k-blocks per page.

    Device-locality contract (sharded serving): the table values these
    maps read become DMA source pages, so every entry must address the pool
    operand THIS kernel instance was handed. Under the sharded engine the
    global pool is partitioned page-wise across the mesh's data axis and the
    kernel runs inside shard_map — each shard's table holds LOCAL ids into
    its own (n_pages, page_size, ...) partition (shard-local null page 0
    included), so the scalar-prefetch gather can never name another device's
    page. Feeding a GLOBAL page id here would index past the local pool —
    keep tables device-local (serve/scheduler reserves pages per shard and
    ShardedServeEngine.assert_local_page_tables pins the invariant)."""

    def kv_map(ib, *rest):
        ik, pt_ref = rest[len(rest) - n_prefetch - 1], rest[-1]
        return pt_ref[ib, ik // bpp], ik % bpp, 0, 0

    def s_map(ib, *rest):
        ik, pt_ref = rest[len(rest) - n_prefetch - 1], rest[-1]
        return pt_ref[ib, ik // bpp], ik % bpp, 0

    return kv_map, s_map


def layer_page_map(n_prefetch: int):
    """BlockSpec index_map of a lane-dense pool's page blocks: block
    (1, 1, R, 128) of the stacked (L, n_pages, R, 128) pool at
    (layer, page_table[b, page]). Same grid convention as
    `paged_index_maps`, with one page per k-step; the LAYER ref sits just
    before the page table, which stays LAST among the scalar-prefetch refs.
    The device-locality contract above holds unchanged."""

    def page_map(ib, *rest):
        ik = rest[len(rest) - n_prefetch - 1]
        layer_ref, pt_ref = rest[-2], rest[-1]
        return layer_ref[0], pt_ref[ib, ik], 0, 0

    return page_map


def place_queries(q, axis: int, lanes: int = 128):
    """(..., D) queries -> T = lanes // D placed copies stacked at `axis`:
    copy j holds the query in lanes [j·D, (j+1)·D) and zeros elsewhere."""
    d = q.shape[-1]
    pad = [(0, 0)] * (q.ndim - 1)
    return jnp.stack([jnp.pad(q, pad + [(j * d, lanes - (j + 1) * d)])
                      for j in range(lanes // d)], axis=axis)


def fold_lanes(o, lse, axis: int, d: int):
    """Merge the T placed copies of a lane-dense kernel's output.

    o: (..., T, ..., 128) per-copy normalized accumulators, copy axis
    `axis`; lse: the same shape with a last dim of 1. Copy j's answer is
    its lanes [j·D, (j+1)·D); the copies are combined by their log-sum-exp
    weights (flash-decoding's split merge). A copy that saw no live key has
    lse ≈ NEG_INF and weight 0; if none did, every copy's accumulator is 0
    and so is the result. Returns (..., D) with the copy axis removed."""
    t = o.shape[axis]
    parts = [jax.lax.index_in_dim(o, j, axis, keepdims=False)
             [..., j * d:(j + 1) * d] for j in range(t)]
    w = jax.nn.softmax(lse[..., 0], axis=axis)
    return sum(jax.lax.index_in_dim(w, j, axis, keepdims=False)[..., None]
               * parts[j] for j in range(t))


def page_update(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, ok, *,
                scale: float):
    """One lane-dense page's online-softmax update, every KV head in turn.

    q_ref[0, h]: head h's placed queries, any leading shape that flattens
    to (rows, 128); k_ref/v_ref: one (1, 1, R, 128) page, head h in rows
    [h·R/KV, (h+1)·R/KV); ok: (rows, R/KV) liveness of each (query row,
    key) pair. Shared by the decode and chunk-prefill kernels."""
    nkv = q_ref.shape[1]
    rh = k_ref.shape[2] // nkv
    lanes = k_ref.shape[3]
    for h in range(nkv):
        q = q_ref[0, h].astype(jnp.float32).reshape(-1, lanes)
        k = k_ref[0, 0, h * rh:(h + 1) * rh, :].astype(jnp.float32)
        v = v_ref[0, 0, h * rh:(h + 1) * rh, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rows, R/KV)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a copy can see no live key in a live page (its lane slot of the
        # page's first row lies past kv_len): keep its masked entries at 0
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)


def page_tokens(rows: int, rh: int, rows_per_copy: int, tpr: int):
    """(rows, R/KV) int32: the in-page token each (query row, key row)
    score reads — key row r, placed copy j (row // rows_per_copy) read
    token r·T + j. The copy index comes from compares, not a division."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, rh), 0)
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, rh), 1) * tpr
    for j in range(1, tpr):
        tok = tok + (r >= j * rows_per_copy).astype(jnp.int32)
    return tok


def page_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref):
    """Write each placed copy's normalized accumulator and log-sum-exp."""
    for h in range(acc_ref.shape[0]):
        l = jnp.maximum(l_ref[h], 1e-30)
        o_ref[0, h] = (acc_ref[h] / l).reshape(o_ref.shape[2:])
        lse_ref[0, h] = (m_ref[h] + jnp.log(l)).reshape(lse_ref.shape[2:])


def _page_kernel(kvlen_ref, layer_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                 lse_ref, m_ref, l_ref, acc_ref, *, scale: float,
                 window: int, page_size: int, g: int, n_k: int):
    """Decode over a lane-dense pool: grid (B, pages_per_seq), one page a
    step. The layer and the page table are consumed by the index_map."""
    del layer_ref, pt_ref
    b = pl.program_id(0)
    ik = pl.program_id(1)
    kvlen = kvlen_ref[b]
    rows = q_ref.shape[2]
    rh = k_ref.shape[2] // q_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ik * page_size < kvlen
    if window > 0:
        live = jnp.logical_and(live,
                               ik * page_size + page_size - 1 >= kvlen - window)

    @pl.when(live)
    def _page():
        k_pos = ik * page_size + page_tokens(rows, rh, g, rows // g)
        ok = k_pos < kvlen
        if window > 0:
            ok = jnp.logical_and(ok, k_pos >= kvlen - window)
        page_update(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, ok,
                    scale=scale)

    @pl.when(ik == n_k - 1)
    def _done():
        page_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref)


def _decode_lane_dense(q, k_pool, v_pool, kv_len, page_table, layer, *,
                       window, scale, interpret):
    """`decode_attention` over the stacked lane-dense pools at `layer`."""
    b, _, nkv, g, d = q.shape
    lanes = k_pool.shape[-1]
    assert lanes % d == 0, f"lane-dense kernel needs D | {lanes}, got {d}"
    tpr = lanes // d
    page_size = k_pool.shape[2] * lanes // (nkv * d)
    pages_per_seq = page_table.shape[1]
    rows = tpr * g
    qp = place_queries(q.reshape(b, nkv, g, d), axis=2, lanes=lanes)
    qp = qp.reshape(b, nkv, rows, lanes)
    q_spec = pl.BlockSpec((1, nkv, rows, lanes),
                          lambda ib, ik, *_: (ib, 0, 0, 0))
    lse_spec = pl.BlockSpec((1, nkv, rows, 1),
                            lambda ib, ik, *_: (ib, 0, 0, 0))
    page_spec = pl.BlockSpec((1, 1) + k_pool.shape[2:],
                             layer_page_map(n_prefetch=3))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, pages_per_seq),
        in_specs=[q_spec, page_spec, page_spec],
        out_specs=[q_spec, lse_spec],
        scratch_shapes=[
            pltpu.VMEM((nkv, rows, 1), jnp.float32),       # m
            pltpu.VMEM((nkv, rows, 1), jnp.float32),       # l
            pltpu.VMEM((nkv, rows, lanes), jnp.float32),   # acc
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_page_kernel, scale=scale, window=window,
                          page_size=page_size, g=g, n_k=pages_per_seq),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, nkv, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((b, nkv, rows, 1), jnp.float32)],
        name="decode_attention",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(kv_len, jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(page_table, jnp.int32), qp, k_pool, v_pool)
    o = fold_lanes(o.reshape(b, nkv, tpr, g, lanes),
                   lse.reshape(b, nkv, tpr, g, 1), axis=2, d=d)
    return o.astype(q.dtype).reshape(b, 1, nkv, g, d)


def _body(kvlen_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
          m_ref, l_ref, acc_ref, *, scale: float, window: int, block_k: int,
          n_k: int):
    """Online-softmax tile update, shared by all four (paged × int8) kernel
    layouts — position-based, so it is blind to where the tile bytes came
    from and whether they were dequantized on the way in. One grid step
    holds every KV head's (block_k, D) tile and updates each head's (G, ·)
    running statistics in turn."""
    b = pl.program_id(0)
    ik = pl.program_id(1)
    kvlen = kvlen_ref[b]
    nkv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile liveness: any k position in [ik·bk, ik·bk+bk) ∩ valid range?
    live = ik * block_k < kvlen
    if window > 0:
        live = jnp.logical_and(live,
                               ik * block_k + block_k - 1 >= kvlen - window)

    @pl.when(live)
    def _tile():
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_k), 1)
        ok = k_pos < kvlen
        if window > 0:
            ok = jnp.logical_and(ok, k_pos >= kvlen - window)
        if ks_ref is not None:                          # fused dequant
            k_sc = ks_ref[0].astype(jnp.float32)        # (block_k, KV)
            v_sc = vs_ref[0].astype(jnp.float32)
        for h in range(nkv):
            q = q_ref[0, h].astype(jnp.float32)         # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)   # (block_k, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if ks_ref is not None:
                k = k * k_sc[:, h:h + 1]
                v = v * v_sc[:, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, block_k)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _make_kernel(*, paged: bool, quantized: bool, **kw):
    """Ref order: scalar-prefetch (kvlen[, page_table]), inputs
    (q, k, v[, ks, vs]), output (o), scratch (m, l, acc). The page table is
    consumed by the K/V (and scale) index_maps — the gather happens in the
    prefetch DMA — so the body never sees it."""

    def kernel(*refs):
        refs = list(refs)
        kvlen_ref = refs.pop(0)
        if paged:
            refs.pop(0)                     # pt_ref: index_map-only
        q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
        ks_ref, vs_ref = (refs[3], refs[4]) if quantized else (None, None)
        o_ref, m_ref, l_ref, acc_ref = refs[-4], refs[-3], refs[-2], refs[-1]
        _body(kvlen_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
              m_ref, l_ref, acc_ref, **kw)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_len, *, page_table=None,
                     layer=None, k_scale=None, v_scale=None,
                     window: int = 0, scale=None, block_k: int = 128,
                     interpret: bool = False):
    """Single-position attention against a ragged-valid KV cache.

    Args:
      q:        (B, 1, KV, G, D) — one query position, grouped query heads.
      k_cache:  (B, Smax, KV, D) storage-dtype cache (never upcast wholesale);
                with `page_table`, a shared (n_pages, page_size, KV, D) pool.
      v_cache:  same layout as k_cache.
      kv_len:   () or (B,) int — number of valid cache rows per sequence
                (this step's k/v must already be written).
      page_table: optional (B, pages_per_seq) int32 — physical page of each
                sequence's logical page; logical depth is pages_per_seq ×
                page_size. Unmapped entries must point at a valid (null) page.
      layer:    with `page_table`, () int — the caches are then the
                layer-stacked lane-dense (L, n_pages, R, 128) pools, read
                at this layer in place (float pools; no scales).
      k_scale/v_scale: optional per-row dequant scales for int8 caches —
                cache shape minus the D dim ((B, Smax, KV) dense,
                (n_pages, page_size, KV) paged). Dequant is fused into the
                tile loads; both must be given together.
      window:   sliding-window size (0 = full attention over the valid prefix).
      scale:    logit scale; defaults to D**-0.5.

    Returns (B, 1, KV, G, D) in q.dtype, fp32 accumulation throughout.
    """
    b, sq, nkv, g, d = q.shape
    assert sq == 1, f"decode kernel takes one query position, got {sq}"
    assert (k_scale is None) == (v_scale is None)
    quantized = k_scale is not None
    scale = float(scale if scale is not None else d ** -0.5)
    kv_len = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    if layer is not None:
        assert page_table is not None and not quantized
        return _decode_lane_dense(q, k_cache, v_cache, kv_len, page_table,
                                  layer, window=window, scale=scale,
                                  interpret=interpret)
    qf = q.reshape(b, nkv, g, d)
    scratch_shapes = [
        pltpu.VMEM((nkv, g, 1), jnp.float32),   # m
        pltpu.VMEM((nkv, g, 1), jnp.float32),   # l
        pltpu.VMEM((nkv, g, d), jnp.float32),   # acc
    ]
    q_spec = pl.BlockSpec((1, nkv, g, d), lambda ib, ik, *_: (ib, 0, 0, 0))

    if page_table is None:
        smax = k_cache.shape[1]
        block_k = min(block_k, smax)
        if smax % block_k:
            block_k = smax      # a whole-depth block is always tile-legal
        n_k = smax // block_k
        n_prefetch = 1
        kv_map = lambda ib, ik, *_: (ib, ik, 0, 0)      # noqa: E731
        s_map = lambda ib, ik, *_: (ib, ik, 0)          # noqa: E731
        prefetch = [kv_len]
    else:
        page_size = k_cache.shape[1]
        pages_per_seq = page_table.shape[1]
        assert page_table.shape[0] == b, (page_table.shape, b)
        block_k = min(block_k, page_size)
        assert page_size % block_k == 0, (page_size, block_k)
        bpp = page_size // block_k              # k-blocks per page
        n_k = pages_per_seq * bpp               # logical k-block sweep
        n_prefetch = 2
        # physical page of each tile's logical page via prefetch; the scale
        # tile gathers through the same table entry as its K/V tile
        kv_map, s_map = paged_index_maps(bpp, n_prefetch=2)
        prefetch = [kv_len, jnp.asarray(page_table, jnp.int32)]

    kv_spec = pl.BlockSpec((1, block_k, nkv, d), kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qf, k_cache, v_cache]
    if quantized:
        s_spec = pl.BlockSpec((1, block_k, nkv), s_map)
        in_specs += [s_spec, s_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(b, n_k),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        _make_kernel(paged=page_table is not None, quantized=quantized,
                     scale=scale, window=window, block_k=block_k, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, d), q.dtype),
        name="decode_attention",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*prefetch, *operands)
    return out.reshape(b, 1, nkv, g, d)
