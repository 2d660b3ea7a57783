"""Pallas decode-attention kernel vs the pure-jnp reference (interpret mode):
GQA grouping, ragged per-sequence kv_len, sliding windows, storage dtypes,
and the paged page-table gather path."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import decode_attention as pallas_decode
from repro.kernels.flash_attention import flash_attention_paged
from repro.models.attention import chunk_attention_paged, decode_attention


def _inputs(seed, b, kv, g, d, smax, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, kv, g, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, smax, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, smax, kv, d), jnp.float32).astype(dtype)
    kv_len = jax.random.randint(ks[3], (b,), 1, smax + 1)
    return q, k, v, kv_len


def _paged_inputs(seed, b, kv, g, d, ps, pages_per_seq, dtype=jnp.float32):
    """Page pools + a shuffled (non-identity) page table, and the dense
    per-sequence view obtained by gathering the table — the exactness oracle."""
    n_pages = 1 + b * pages_per_seq         # page 0 reserved as null
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, kv, g, d), jnp.float32).astype(dtype)
    pk = jax.random.normal(ks[1], (n_pages, ps, kv, d), jnp.float32).astype(dtype)
    pv = jax.random.normal(ks[2], (n_pages, ps, kv, d), jnp.float32).astype(dtype)
    perm = jax.random.permutation(ks[3], jnp.arange(1, n_pages))
    pt = perm.reshape(b, pages_per_seq).astype(jnp.int32)
    kd = pk[pt].reshape(b, pages_per_seq * ps, kv, d)
    vd = pv[pt].reshape(b, pages_per_seq * ps, kv, d)
    return q, pk, pv, pt, kd, vd


@pytest.mark.parametrize("b,kv,g,d,smax", [
    (2, 2, 4, 64, 256),     # GQA, multi-block sweep
    pytest.param(3, 1, 1, 64, 128,      # MQA single head, one block
                 marks=pytest.mark.slow),
    pytest.param(1, 4, 2, 32, 512,      # many kv heads, deep cache
                 marks=pytest.mark.slow),
])
def test_matches_reference(b, kv, g, d, smax):
    q, k, v, kv_len = _inputs(b * smax + d, b, kv, g, d, smax)
    want = decode_attention(q, k, v, kv_len, impl="reference")
    got = pallas_decode(q, k, v, kv_len, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


@pytest.mark.parametrize("window", [
    32, pytest.param(128, marks=pytest.mark.slow)])
def test_sliding_window(window):
    q, k, v, kv_len = _inputs(7, 2, 2, 2, 64, 256)
    want = decode_attention(q, k, v, kv_len, window=window, impl="reference")
    got = pallas_decode(q, k, v, kv_len, window=window, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_scalar_kv_len_broadcasts():
    q, k, v, _ = _inputs(3, 2, 2, 2, 64, 256)
    want = decode_attention(q, k, v, jnp.int32(100), impl="reference")
    got = pallas_decode(q, k, v, jnp.int32(100), interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_bf16_cache_stays_in_storage_dtype():
    q, k, v, kv_len = _inputs(11, 2, 2, 4, 64, 256, dtype=jnp.bfloat16)
    want = decode_attention(q, k, v, kv_len, impl="reference")
    got = pallas_decode(q, k, v, kv_len, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert jnp.max(jnp.abs(want.astype(jnp.float32)
                           - got.astype(jnp.float32))) < 2e-2


def test_partial_tail_block_masked():
    """kv_len one past / one short of a block edge must flip exactly the
    edge position's contribution."""
    q, k, v, _ = _inputs(5, 1, 1, 1, 32, 256)
    for kv_len in (127, 128, 129):
        want = decode_attention(q, k, v, jnp.asarray([kv_len]),
                                impl="reference")
        got = pallas_decode(q, k, v, jnp.asarray([kv_len]), interpret=True)
        assert jnp.max(jnp.abs(want - got)) < 2e-5, kv_len


def test_empty_sequence_yields_zeros():
    """kv_len == 0 ("no valid keys") must produce zeros from BOTH impls —
    not softmax's uniform mean over masked positions."""
    q, k, v, _ = _inputs(9, 2, 1, 2, 32, 128)
    kv_len = jnp.asarray([0, 64], jnp.int32)
    ref = decode_attention(q, k, v, kv_len, impl="reference")
    pal = pallas_decode(q, k, v, kv_len, interpret=True)
    assert jnp.all(ref[0] == 0.0) and jnp.all(pal[0] == 0.0)
    assert jnp.max(jnp.abs(ref[1] - pal[1])) < 2e-5


@pytest.mark.parametrize("window,block_k", [
    (300, 64),    # kv_len can be < window: full prefix attends
    (40, 32),     # window not a multiple of block_k: partial leading tile
    (64, 128),    # window smaller than one block
])
def test_sliding_window_edges(window, block_k):
    q, k, v, _ = _inputs(13, 2, 2, 2, 32, 256)
    kv_len = jnp.asarray([17, 256], jnp.int32)    # < window and == smax
    want = decode_attention(q, k, v, kv_len, window=window, impl="reference")
    got = pallas_decode(q, k, v, kv_len, window=window, block_k=block_k,
                        interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_empty_sequence_with_window_yields_zeros():
    """kv_len == 0 under a sliding window must still emit zeros, not the
    softmax of an all-masked row."""
    q, k, v, _ = _inputs(15, 2, 1, 2, 32, 128)
    kv_len = jnp.asarray([0, 100], jnp.int32)
    ref = decode_attention(q, k, v, kv_len, window=32, impl="reference")
    pal = pallas_decode(q, k, v, kv_len, window=32, interpret=True)
    assert jnp.all(ref[0] == 0.0) and jnp.all(pal[0] == 0.0)
    assert jnp.max(jnp.abs(ref[1] - pal[1])) < 2e-5


# ------------------------------------------------------------------ paged path
def test_paged_matches_dense_gather():
    """Kernel reading through a shuffled page table == the same rows laid out
    densely."""
    q, pk, pv, pt, kd, vd = _paged_inputs(21, 2, 2, 4, 32, ps=16,
                                          pages_per_seq=4)
    kv_len = jnp.asarray([37, 61], jnp.int32)
    want = decode_attention(q, kd, vd, kv_len, impl="reference")
    ref = decode_attention(q, pk, pv, kv_len, page_table=pt, impl="reference")
    pal = pallas_decode(q, pk, pv, kv_len, page_table=pt, interpret=True)
    assert jnp.max(jnp.abs(want - ref)) < 2e-5
    assert jnp.max(jnp.abs(want - pal)) < 2e-5


def test_paged_null_pages_are_dead():
    """Table entries past kv_len may point anywhere (the engine points them
    at the null page); they must not contribute."""
    q, pk, pv, pt, kd, vd = _paged_inputs(23, 2, 1, 2, 32, ps=16,
                                          pages_per_seq=4)
    kv_len = jnp.asarray([16, 33], jnp.int32)
    # kill every entry beyond the live prefix: seq0 keeps page 0 only,
    # seq1 keeps three pages
    pt_null = pt.at[0, 1:].set(0).at[1, 3:].set(0)
    want = decode_attention(q, kd, vd, kv_len, impl="reference")
    pal = pallas_decode(q, pk, pv, kv_len, page_table=pt_null, interpret=True)
    ref = decode_attention(q, pk, pv, kv_len, page_table=pt_null,
                           impl="reference")
    assert jnp.max(jnp.abs(want - pal)) < 2e-5
    assert jnp.max(jnp.abs(want - ref)) < 2e-5


@pytest.mark.parametrize("window", [24, 40])
def test_paged_sliding_window(window):
    q, pk, pv, pt, kd, vd = _paged_inputs(25, 2, 2, 2, 32, ps=16,
                                          pages_per_seq=4)
    kv_len = jnp.asarray([29, 64], jnp.int32)
    want = decode_attention(q, kd, vd, kv_len, window=window,
                            impl="reference")
    pal = pallas_decode(q, pk, pv, kv_len, page_table=pt, window=window,
                        block_k=8, interpret=True)
    assert jnp.max(jnp.abs(want - pal)) < 2e-5


def test_paged_bf16_pool_stays_in_storage_dtype():
    q, pk, pv, pt, kd, vd = _paged_inputs(27, 2, 2, 2, 32, ps=16,
                                          pages_per_seq=2, dtype=jnp.bfloat16)
    kv_len = jnp.asarray([9, 30], jnp.int32)
    want = decode_attention(q, kd, vd, kv_len, impl="reference")
    got = pallas_decode(q, pk, pv, kv_len, page_table=pt, interpret=True)
    assert got.dtype == jnp.bfloat16
    assert jnp.max(jnp.abs(want.astype(jnp.float32)
                           - got.astype(jnp.float32))) < 2e-2


# ------------------------------------------------------------------- int8 path
def _int8_inputs(seed, b, kv, g, d, smax):
    """int8 cache + per-row bf16 scales, from quantizing an f32 cache — the
    jnp reference with the same operands is the dequant oracle."""
    from repro.models.quantized import quantize_kv_rows
    q, k, v, kv_len = _inputs(seed, b, kv, g, d, smax)
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    return q, k8, ks, v8, vs, kv_len


def test_int8_dense_fused_dequant_matches_reference():
    """Dense int8 cache: the kernel's fused (tile * scale) dequant must match
    the jnp path's materialized dequant."""
    q, k8, ks, v8, vs, kv_len = _int8_inputs(31, 2, 2, 4, 64, 256)
    want = decode_attention(q, k8, v8, kv_len, k_scale=ks, v_scale=vs,
                            impl="reference")
    got = pallas_decode(q, k8, v8, kv_len, k_scale=ks, v_scale=vs,
                        interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_int8_paged_fused_dequant_matches_reference():
    """Paged int8 pools: scales gather through the same page-table entries as
    their K/V tiles; kernel == jnp gather-then-dequant oracle."""
    from repro.models.quantized import quantize_kv_rows
    q, pk, pv, pt, kd, vd = _paged_inputs(33, 2, 2, 2, 32, ps=16,
                                          pages_per_seq=4)
    pk8, pks = quantize_kv_rows(pk)
    pv8, pvs = quantize_kv_rows(pv)
    kv_len = jnp.asarray([37, 61], jnp.int32)
    want = decode_attention(q, pk8, pv8, kv_len, page_table=pt,
                            k_scale=pks, v_scale=pvs, impl="reference")
    got = pallas_decode(q, pk8, pv8, kv_len, page_table=pt,
                        k_scale=pks, v_scale=pvs, interpret=True)
    assert got.dtype == q.dtype
    assert jnp.max(jnp.abs(want - got)) < 2e-5


@pytest.mark.parametrize("window", [24, 40])
def test_int8_paged_sliding_window(window):
    from repro.models.quantized import quantize_kv_rows
    q, pk, pv, pt, kd, vd = _paged_inputs(35, 2, 2, 2, 32, ps=16,
                                          pages_per_seq=4)
    pk8, pks = quantize_kv_rows(pk)
    pv8, pvs = quantize_kv_rows(pv)
    kv_len = jnp.asarray([29, 64], jnp.int32)
    want = decode_attention(q, pk8, pv8, kv_len, page_table=pt, window=window,
                            k_scale=pks, v_scale=pvs, impl="reference")
    got = pallas_decode(q, pk8, pv8, kv_len, page_table=pt, window=window,
                        k_scale=pks, v_scale=pvs, block_k=8, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_int8_quantized_cache_close_to_f32_cache():
    """End-to-end numerics: attention over the quantized cache stays within
    the int8 grid error of attention over the original f32 cache."""
    q, k, v, kv_len = _inputs(37, 2, 2, 2, 64, 128)
    from repro.models.quantized import quantize_kv_rows
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    exact = decode_attention(q, k, v, kv_len, impl="reference")
    quant = pallas_decode(q, k8, v8, kv_len, k_scale=ks, v_scale=vs,
                          interpret=True)
    assert jnp.max(jnp.abs(exact - quant)) < 0.05


def test_dispatch_stays_reference_off_tpu():
    """On CPU/GPU the model-level entry point keeps the jnp path (the kernel
    is opt-in via impl='pallas' with interpret)."""
    q, k, v, kv_len = _inputs(1, 1, 2, 2, 64, 128)
    a = decode_attention(q, k, v, kv_len)            # impl='auto'
    b = decode_attention(q, k, v, kv_len, impl="reference")
    assert jnp.array_equal(a, b)


# ------------------------------------------- chunk-prefill kernel (paged)
def _chunk_inputs(seed, b, kv, g, d, ps, pages_per_seq, cq, dtype=jnp.float32):
    n_pages = 1 + b * pages_per_seq
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, cq, kv, g, d), jnp.float32).astype(dtype)
    pk = jax.random.normal(ks[1], (n_pages, ps, kv, d),
                           jnp.float32).astype(dtype)
    pv = jax.random.normal(ks[2], (n_pages, ps, kv, d),
                           jnp.float32).astype(dtype)
    perm = jax.random.permutation(ks[3], jnp.arange(1, n_pages))
    pt = perm.reshape(b, pages_per_seq).astype(jnp.int32)
    return q, pk, pv, pt


def test_chunk_prefill_kernel_matches_reference():
    """flash_attention_paged (interpret) == the jnp gather reference at a
    mid-stream chunk offset: causal masking by GLOBAL position, live-length
    masking of stale pool rows beyond kv_len."""
    b, kv, g, d, ps, pps, cq = 2, 2, 2, 32, 16, 6, 32
    q, pk, pv, pt = _chunk_inputs(11, b, kv, g, d, ps, pps, cq)
    off = jnp.asarray([16, 40], jnp.int32)
    kv_len = off + jnp.asarray([cq, 20], jnp.int32)   # partial final chunk
    want = chunk_attention_paged(q, pk, pv, pt, off, kv_len=kv_len,
                                 impl="reference")
    got = flash_attention_paged(q, pk, pv, pt, off, kv_len, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5


def test_chunk_prefill_kernel_first_chunk_and_window():
    """Offset-0 chunks and sliding windows: rows with no in-window keys
    below their own position must not pick up garbage (the all-masked-tile
    guard), matching the reference bit-for-bit in structure."""
    b, kv, g, d, ps, pps, cq = 1, 2, 2, 32, 16, 6, 32
    q, pk, pv, pt = _chunk_inputs(12, b, kv, g, d, ps, pps, cq)
    off = jnp.zeros((1,), jnp.int32)
    kv_len = jnp.asarray([cq], jnp.int32)
    want = chunk_attention_paged(q, pk, pv, pt, off, kv_len=kv_len,
                                 impl="reference")
    got = flash_attention_paged(q, pk, pv, pt, off, kv_len, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5
    off = jnp.asarray([48], jnp.int32)
    kv_len = off + cq
    for window in (8, 24):
        want = chunk_attention_paged(q, pk, pv, pt, off, kv_len=kv_len,
                                     window=window, impl="reference")
        got = flash_attention_paged(q, pk, pv, pt, off, kv_len,
                                    window=window, interpret=True)
        assert jnp.max(jnp.abs(want - got)) < 2e-5, window


def test_chunk_prefill_kernel_int8_fused_dequant():
    """int8 pools: dequant fused into the chunk kernel's tile loads == the
    dequantized-gather reference."""
    from repro.models.quantized import quantize_kv_rows
    b, kv, g, d, ps, pps, cq = 1, 2, 2, 32, 16, 4, 16
    q, pk, pv, pt = _chunk_inputs(13, b, kv, g, d, ps, pps, cq)
    k8, ks = quantize_kv_rows(pk)
    v8, vs = quantize_kv_rows(pv)
    off = jnp.asarray([24], jnp.int32)
    kv_len = off + cq
    want = chunk_attention_paged(q, k8, v8, pt, off, kv_len=kv_len,
                                 k_scale=ks, v_scale=vs, impl="reference")
    got = flash_attention_paged(q, k8, v8, pt, off, kv_len,
                                k_scale=ks, v_scale=vs, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 2e-5
