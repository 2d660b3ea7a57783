"""Attention: GQA/MQA/MHA with RoPE (full or partial), causal + sliding-window,
in three execution styles:

  reference_attention — naive einsum; oracle for tests and small smoke runs.
  chunked_attention   — flash-style online-softmax over (q-block, kv-block)
                        tiles in pure JAX. Peak memory is O(block²) instead of
                        O(S²); causal runs only the lower-triangular blocks
                        (python loop over q blocks → static, scan-free HLO that
                        GSPMD shards cleanly). This is the dry-run/training
                        path for the big shapes.
  decode_attention    — single-query attention against a KV cache.

Shapes: q (B, Sq, KV, G, D) where G = n_heads // n_kv_heads; k/v (B, Sk, KV, D).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int):
    """(Tq, Tk) additive bias from absolute positions."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window > 0:
        ok &= diff < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def reference_attention(q, k, v, *, causal=True, window=0, scale=None,
                        q_offset=0, kv_len: Optional[jnp.ndarray] = None):
    """Oracle. q: (B,Sq,KV,G,D); k,v: (B,Sk,KV,D) → (B,Sq,KV,G,D)."""
    b, sq, nkv, g, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
    if kv_len is not None:  # ragged validity (decode caches)
        s = jnp.where(k_pos[None, None, None, None, :] < kv_len[:, None, None, None, None],
                      s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32)).astype(q.dtype)


def _block_attn(q, k, v, bias, scale, m, l, acc):
    """One online-softmax tile update. q:(B,Tq,KV,G,D) k/v:(B,Tk,KV,D)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s + bias  # (Tq, Tk) broadcast
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def chunked_attention(q, k, v, *, causal=True, window=0, scale=None,
                      q_chunk=1024, kv_chunk=1024, unroll=False):
    """Flash-style attention. Python loop over q blocks; per block, a lax.scan
    over exactly the kv blocks that can contribute (causal → lower triangle;
    window → the trailing `window` band). FLOPs therefore match the masked
    ideal to within one block-row, not the 2× of a dense-masked einsum."""
    b, sq, nkv, g, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]      # MLA values are the latent's leading slice: dv < d
    scale = scale if scale is not None else d ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, (sq, q_chunk, sk, kv_chunk)
    n_q, n_kv = sq // q_chunk, sk // kv_chunk

    k_blocks = k.reshape(b, n_kv, kv_chunk, nkv, d)
    v_blocks = v.reshape(b, n_kv, kv_chunk, nkv, dv)

    outs = []
    for iq in range(n_q):
        qi = jax.lax.slice_in_dim(q, iq * q_chunk, (iq + 1) * q_chunk, axis=1)
        q_pos = iq * q_chunk + jnp.arange(q_chunk)
        # contributing kv block range (static)
        hi = n_kv if not causal else min(n_kv, ((iq + 1) * q_chunk + kv_chunk - 1) // kv_chunk)
        lo = 0
        if window > 0:
            lo = max(0, (iq * q_chunk - window) // kv_chunk)
        kb = k_blocks[:, lo:hi]
        vb = v_blocks[:, lo:hi]

        def body(carry, blk, q_pos=q_pos, qi=qi, lo=lo):
            m, l, acc, j = carry
            kj, vj = blk
            k_pos = (lo + j) * kv_chunk + jnp.arange(kv_chunk)
            diff = q_pos[:, None] - k_pos[None, :]
            ok = jnp.ones(diff.shape, bool)
            if causal:
                ok &= diff >= 0
            if window > 0:
                ok &= diff < window
            bias = jnp.where(ok, 0.0, NEG_INF)
            m, l, acc = _block_attn(qi, kj, vj, bias, scale, m, l, acc)
            return (m, l, acc, j + 1), None

        m0 = jnp.full((b, nkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, nkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, nkv, g, q_chunk, dv), jnp.float32)
        from repro.models.common import scan_or_unroll
        (m, l, acc, _), _ = scan_or_unroll(
            body, (m0, l0, a0, jnp.int32(0)),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)),
            unroll=unroll,
        )
        o = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,KV,G,Tq,D)
        outs.append(jnp.transpose(o, (0, 3, 1, 2, 4)).astype(q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def _kernel_dispatch(k_cache) -> bool:
    """impl='auto' dispatch of the paged/dense attention kernels: on TPU the
    Pallas kernel always runs — a cache it cannot tile fails loudly in the
    kernel's own shape checks instead of quietly taking the jnp path, which
    would densify the whole logical view per step. Every other backend runs
    the jnp reference (the CPU oracle; tests force the kernel in interpret
    mode with impl='pallas')."""
    if jax.default_backend() != "tpu":
        return False
    if jnp.issubdtype(k_cache.dtype, jnp.floating) \
            and k_cache.dtype.itemsize == 1:
        raise NotImplementedError(
            "fp8 (e5m2) KV caches have no TPU attention kernel; serve "
            "bf16 or int8 KV on TPU")
    return True


def decode_attention(q, k_cache, v_cache, cur_len, *, window=0, scale=None,
                     page_table=None, k_scale=None, v_scale=None,
                     v_dim: Optional[int] = None, impl: str = "auto"):
    """Single-position attention against a cache.

    q: (B,1,KV,G,D); caches: (B,Smax,KV,D); cur_len: () or (B,) int — number of
    valid cache positions (the new token's k/v must already be written).

    v_dim (MLA latent rows): the caller passes the SAME latent pool as both
    k_cache and v_cache, with keys q·D-wide and values only the leading
    `v_dim` columns of each row (models/mla.py absorbed layout). The jnp path
    slices after gather/dequant; the Pallas kernel has no latent-row gather
    yet, so v_dim forces the reference path (documented fallback —
    kernels/decode_attention.py).

    Paged layout (`page_table=` (B, pages_per_seq) int32): the caches are
    shared (n_pages, page_size, KV, D) page pools and each sequence's rows
    live at pool[page_table[b, j]] for logical page j. The jnp path below
    gathers the table back to a dense per-sequence view — exact, and the CPU
    oracle for the kernel — while the Pallas kernel gathers tile-by-tile
    through scalar prefetch and never materializes the dense view.

    INT8 caches (`k_scale`/`v_scale`): the caches hold int8 rows and the
    scales hold one bf16 dequant factor per (position, kv head) — shaped like
    the caches minus the D dim. Dequant is `int8.astype(f32) * scale` — the
    jnp path materializes it on the gathered view (CPU oracle), the Pallas
    kernel fuses it into the K/V tile loads so the cache crosses HBM as int8.

    impl: 'auto' dispatches to the Pallas decode kernel
    (kernels/decode_attention) on TPU — the engine's decode step streams the
    cache through VMEM tiles instead of materializing masked scores over the
    whole Smax. 'pallas' forces the kernel (interpret mode off-TPU, used by
    the numerics tests); 'reference' forces the jnp path below.

    The caches stay in their storage dtype: fp32 accumulation happens inside
    the einsums (preferred_element_type), never as a materialized cast — a
    whole-cache fp32 copy would double the decode footprint (measured +15 GiB
    on gemma-7b × decode_32k; EXPERIMENTS.md §Perf).
    """
    assert (k_scale is None) == (v_scale is None)
    if v_dim is not None:
        impl = "reference"  # latent-row kernel gather is a follow-on
    if impl == "auto":
        impl = "pallas" if _kernel_dispatch(k_cache) else "reference"
    if impl == "pallas":
        from repro.kernels.decode_attention import (
            decode_attention as pallas_decode)
        return pallas_decode(
            q, k_cache, v_cache, cur_len, window=window,
            page_table=page_table, k_scale=k_scale, v_scale=v_scale,
            scale=None if scale is None else float(scale),
            interpret=jax.default_backend() != "tpu")
    b, _, nkv, g, d = q.shape
    if page_table is not None:
        # (n_pages, ps, KV, D)[(B, pp)] → (B, pp·ps, KV, D) dense view.
        # Null-page entries gather garbage rows, but they sit at logical
        # positions ≥ cur_len and are masked below like any dead row.
        k_cache = k_cache[page_table].reshape(b, -1, nkv, d)
        v_cache = v_cache[page_table].reshape(b, -1, nkv, d)
        if k_scale is not None:
            k_scale = k_scale[page_table].reshape(b, -1, nkv)
            v_scale = v_scale[page_table].reshape(b, -1, nkv)
    if k_scale is not None:
        from repro.models.quantized import dequantize_kv_rows
        k_cache = dequantize_kv_rows(k_cache, k_scale)
        v_cache = dequantize_kv_rows(v_cache, v_scale)
    if jnp.issubdtype(v_cache.dtype, jnp.floating) and v_cache.dtype.itemsize == 1:
        # fp8 storage (dense layout only): the softmax probs must not
        # round-trip through e5m2 below — upcast the gathered view once
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    if v_dim is not None:
        v_cache = v_cache[..., :v_dim]
    smax = k_cache.shape[1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(smax)
    valid = pos[None, :] < jnp.reshape(cur_len, (-1, 1))        # (B, Smax)
    if window > 0:
        valid &= pos[None, :] >= (jnp.reshape(cur_len, (-1, 1)) - window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # kv_len == 0 means "no valid keys": emit zeros (matching the Pallas
    # kernel) instead of softmax's uniform mean over masked positions
    p = p * (jnp.reshape(cur_len, (-1, 1, 1, 1, 1)) > 0)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def chunk_attention_paged(q, k_pool, v_pool, page_table, q_offset, *, kv_len,
                          window=0, scale=None, k_scale=None, v_scale=None,
                          v_dim: Optional[int] = None, impl: str = "auto"):
    """Chunk-prefill attention: a block of query rows against the page pool.

    q: (B, C, KV, G, D) — one fixed-size prefill chunk whose row i sits at
    global position q_offset[b] + i; k_pool/v_pool are the engine's shared
    (n_pages, page_size, KV, D) pools and page_table (B, pages_per_seq) maps
    the slot's logical pages onto them (null page 0 absorbs unmapped
    entries). kv_len (B,) is the LIVE length — q_offset + the chunk's real
    rows, which the caller must already have written to the pool — and masks
    stale pool rows beyond it; causality masks by global position, so chunk
    padding rows only ever produce garbage outputs, never garbage inputs.

    k_scale/v_scale: optional (n_pages, page_size, KV) scales for int8
    pools — the jnp path dequantizes the gathered view (CPU oracle), the
    Pallas kernel fuses dequant into its tile loads.

    v_dim (MLA latent rows): same single-pool convention as
    decode_attention — values are the leading v_dim columns of each latent
    row; forces the jnp reference path (kernel gather is a follow-on).

    impl: 'auto' dispatches to kernels/flash_attention.flash_attention_paged
    on TPU; 'pallas' forces the kernel (interpret off-TPU — tests);
    'reference' forces the jnp gather path below.
    """
    b, cq, nkv, g, d = q.shape
    assert (k_scale is None) == (v_scale is None)
    scale = scale if scale is not None else d ** -0.5
    if v_dim is not None:
        impl = "reference"
    if impl == "auto":
        impl = "pallas" if _kernel_dispatch(k_pool) else "reference"
    if impl == "pallas":
        from repro.kernels.flash_attention import flash_attention_paged
        return flash_attention_paged(
            q, k_pool, v_pool, page_table, q_offset, kv_len,
            k_scale=k_scale, v_scale=v_scale, window=window,
            scale=float(scale), interpret=jax.default_backend() != "tpu")
    # reference: gather the table back to a dense logical view (CPU oracle)
    kd = k_pool[page_table].reshape(b, -1, nkv, d)
    vd = v_pool[page_table].reshape(b, -1, nkv, d)
    if k_scale is not None:
        from repro.models.quantized import dequantize_kv_rows
        kd = dequantize_kv_rows(kd, k_scale[page_table].reshape(b, -1, nkv))
        vd = dequantize_kv_rows(vd, v_scale[page_table].reshape(b, -1, nkv))
    if jnp.issubdtype(vd.dtype, jnp.floating) and vd.dtype.itemsize == 1:
        kd = kd.astype(jnp.float32)   # fp8 pools (see decode_attention)
        vd = vd.astype(jnp.float32)
    if v_dim is not None:
        vd = vd[..., :v_dim]
    smax = kd.shape[1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, kd,
                   preferred_element_type=jnp.float32) * scale
    q_pos = jnp.reshape(q_offset, (-1, 1)) + jnp.arange(cq)[None, :]  # (B, C)
    k_pos = jnp.arange(smax)
    ok = k_pos[None, None, :] <= q_pos[:, :, None]                # causal
    ok &= k_pos[None, None, :] < jnp.reshape(kv_len, (-1, 1, 1))  # live rows
    if window > 0:
        ok &= q_pos[:, :, None] - k_pos[None, None, :] < window
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vd.dtype), vd,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def attention(q, k, v, *, causal=True, window=0, scale=None, impl="chunked",
              q_chunk=1024, kv_chunk=1024, unroll=False):
    if impl == "reference" or q.shape[1] <= max(256, q_chunk // 4):
        return reference_attention(q, k, v, causal=causal, window=window, scale=scale)
    return chunked_attention(q, k, v, causal=causal, window=window, scale=scale,
                             q_chunk=q_chunk, kv_chunk=kv_chunk, unroll=unroll)
