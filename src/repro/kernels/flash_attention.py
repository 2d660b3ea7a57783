"""Pallas TPU kernels: blockwise online-softmax attention (causal + window).

VMEM tiling: (block_q × D) query tile resident; K/V stream through in
(block_k × D) tiles along the innermost grid dim; the m/l/acc running
statistics live in VMEM scratch across the K sweep (FlashAttention-2
schedule adapted to the MXU: both matmuls per tile are 128-aligned).
The causal/window structure prunes dead tiles via `pl.when` on block
indices, so the kernel does ~half the tiles of a dense-masked pass.

Layout: q/k/v (B, H, S, D) — B·H is the embarrassingly-parallel leading
grid dim; q blocks next; k blocks innermost ('arbitrary').

`flash_attention_paged` is the CHUNK-PREFILL variant (PR 4): a fixed-size
chunk of query rows at global positions q_offset+i attends against the
serving engine's shared KV page POOLS through a scalar-prefetched page
table (the same gather convention as kernels/decode_attention — the index
maps are shared via `paged_index_maps`). `kv_len` is the live length (rows
the prompt has actually written), so stale pool rows and chunk padding are
masked exactly like the decode kernel's ragged prefix. Optional
k_scale/v_scale operands fuse int8 dequant into the tile loads, giving the
int8 KV pool a chunked prefill path with no densify/cast step.

NOT YET COVERED — MLA latent rows: `v_dim=` chunk attention (one latent
pool as both K and V — see kernels/decode_attention's note) runs the exact
jnp reference path in models/attention.chunk_attention_paged; the
kernel-side latent gather is a recorded follow-on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import NEG_INF, paged_index_maps


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # tile liveness: any (q,k) pair in range?
    live = True
    if causal:
        live = jnp.logical_and(live, iq * block_q + block_q - 1 >= ik * block_k)
    if window > 0:
        live = jnp.logical_and(
            live, iq * block_q <= ik * block_k + block_k - 1 + window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, ...].astype(jnp.float32)       # (block_q, D)
        k = k_ref[0, ...].astype(jnp.float32)       # (block_k, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window > 0:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, ...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[0, ...] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale=None, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q/k/v: (B, H, S, D) → (B, H, S, D)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = float(scale if scale is not None else d ** -0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    n_k = sk // block_k
    grid = (b * h, sq // block_q, n_k)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# Chunk-prefill attention against the paged KV pool
# ---------------------------------------------------------------------------

def _paged_kernel(off_ref, kvlen_ref, pt_ref, *refs, scale: float,
                  window: int, block_q: int, block_k: int, n_k: int,
                  quantized: bool):
    """One (q-block × k-block) online-softmax tile of chunk prefill.

    Ref order after the scalar prefetch (q_offset, kv_len, page_table):
    inputs (q, k, v[, ks, vs]), output (o), scratch (m, l, acc). The page
    table is consumed by the K/V index_maps — the body only sees positions.
    The K/V tile holds every KV head (see paged_index_maps); the body loops
    over heads, and a head's query tile is its G query heads × block_q rows
    stacked into one (G·block_q, D) operand, row r at chunk row r % block_q.
    """
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    ks_ref, vs_ref = (refs[3], refs[4]) if quantized else (None, None)
    o_ref, m_ref, l_ref, acc_ref = refs[-4], refs[-3], refs[-2], refs[-1]
    nkv, g, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[4]
    rows = g * block_q
    ib = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    kvlen = kvlen_ref[ib]
    q_lo = off_ref[ib] + iq * block_q          # global position of q row 0

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile liveness: below the live prefix AND not strictly above the causal
    # frontier of the block's last q row AND (windowed) not entirely below
    # the first q row's window floor
    live = jnp.logical_and(ik * block_k < kvlen,
                           ik * block_k <= q_lo + block_q - 1)
    if window > 0:
        live = jnp.logical_and(live, ik * block_k + block_k > q_lo - window)

    @pl.when(live)
    def _tile():
        q_pos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0) % block_q
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        ok = jnp.logical_and(k_pos <= q_pos, k_pos < kvlen)
        if window > 0:
            ok = jnp.logical_and(ok, q_pos - k_pos < window)
        if ks_ref is not None:                          # fused int8 dequant
            k_sc = ks_ref[0].astype(jnp.float32)        # (block_k, KV)
            v_sc = vs_ref[0].astype(jnp.float32)
        for h in range(nkv):
            q = q_ref[0, h].astype(jnp.float32).reshape(rows, d)
            k = k_ref[0, :, h, :].astype(jnp.float32)   # (block_k, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if ks_ref is not None:
                k = k * k_sc[:, h:h + 1]
                v = v * v_sc[:, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # rows with no valid key yet keep m == NEG_INF; NEG_INF is
            # finite, so exp(s - m) would be exp(0)=1 for their masked
            # entries — zero them
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        for h in range(nkv):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = o.reshape(g, block_q, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention_paged(q, k_pool, v_pool, page_table, q_offset, kv_len, *,
                          k_scale=None, v_scale=None, window: int = 0,
                          scale=None, block_q: int = 128,
                          block_k: "int | None" = None,
                          interpret: bool = False):
    """Chunk-prefill attention through the page table.

    Args:
      q:          (B, C, KV, G, D) — one fixed-size prefill chunk of queries;
                  row i sits at global position q_offset[b] + i.
      k_pool/v_pool: shared (n_pages, page_size, KV, D) page pools.
      page_table: (B, pages_per_seq) int32 — the slot's physical page per
                  logical page (null page 0 for unmapped entries).
      q_offset:   (B,) int32 — global position of the chunk's first row.
      kv_len:     (B,) int32 — live rows (this chunk's K/V already written).
      k_scale/v_scale: optional (n_pages, page_size, KV) int8 dequant scales.
      window:     sliding-window size (0 = full causal).

    Returns (B, C, KV, G, D) in q.dtype, fp32 accumulation throughout.
    """
    b, cq, nkv, g, d = q.shape
    assert (k_scale is None) == (v_scale is None)
    quantized = k_scale is not None
    scale = float(scale if scale is not None else d ** -0.5)
    page_size = k_pool.shape[1]
    pages_per_seq = page_table.shape[1]
    block_k = page_size if block_k is None else min(block_k, page_size)
    assert page_size % block_k == 0, (page_size, block_k)
    bpp = page_size // block_k
    n_k = pages_per_seq * bpp
    block_q = min(block_q, cq)
    assert cq % block_q == 0, (cq, block_q)
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(b)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    qf = jnp.transpose(q, (0, 2, 3, 1, 4))             # (B, KV, G, C, D)

    q_spec = pl.BlockSpec((1, nkv, g, block_q, d),
                          lambda ib, iq, ik, *_: (ib, 0, 0, iq, 0))
    kv_map, s_map = paged_index_maps(bpp, n_prefetch=3)
    kv_spec = pl.BlockSpec((1, block_k, nkv, d), kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qf, k_pool, v_pool]
    if quantized:
        s_spec = pl.BlockSpec((1, block_k, nkv), s_map)
        in_specs += [s_spec, s_spec]
        operands += [k_scale, v_scale]
    rows = g * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, cq // block_q, n_k),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, rows, 1), jnp.float32),   # m
            pltpu.VMEM((nkv, rows, 1), jnp.float32),   # l
            pltpu.VMEM((nkv, rows, d), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, window=window,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        name="flash_attention_paged",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q_offset, kv_len, page_table, *operands)
    return jnp.transpose(out, (0, 3, 1, 2, 4))         # (B, C, KV, G, D)
