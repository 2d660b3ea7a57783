"""The lane-dense page format of the paged float GQA pools.

A page is stored as (R, 128), R = page_size·KV·D/128, head-major (head h,
token t at row h·(page_size·D/128) + t·D // 128, lane (t·D) % 128); the
layer scans write it in place in the layer-stacked (L, n_pages, R, 128)
pool and both Pallas kernels read it there by layer index. Pinned here, on
the CPU with the kernels in interpret mode:

  * `pack_pages` / `view_pages` are inverse, and they and `token_rows`
    put each (head, token) where the format says;
  * both kernels match the jnp reference over the same pool, and the
    reference over the plain (page_size, KV, D) rows: any layer, odd
    chunk lengths, a chunk that ends inside a row, empty sequences;
  * the decode write into one lane slot of a row leaves the row's other
    tokens as they were; the chunk write covers whole pages;
  * pages reached through a prefix-cache hit and a copy-on-write clone
    read like the cold pages they stand for, and the shared pages stay
    untouched;
  * retired slots' writes land on null page 0 only;
  * a paged engine running the kernels emits the reference path's tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention as pallas_decode
from repro.kernels.flash_attention import flash_attention_paged
from repro.models import attention as attn_mod
from repro.models.attention import (
    chunk_attention_paged, decode_attention, pack_pages, token_rows,
    view_pages)
from repro.models.transformer import (
    _write_chunk_lanes, _write_row_lanes, copy_pool_page)

L = 3
# (KV, G, D, page_size): two, four and one tokens per 128-lane row
WIDTHS = [(5, 3, 64, 32), (2, 4, 32, 8), (1, 2, 128, 16)]


def _rows(seed, *shape):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _pools(seed, kv, d, ps, b, pages_per_seq):
    """Stacked lane-dense K/V pools, their plain rows, and a shuffled page
    table over pages 1.. (page 0 is the null page)."""
    n_pages = 1 + b * pages_per_seq
    rk = _rows(seed, L, n_pages, ps, kv, d)
    rv = _rows(seed + 1, L, n_pages, ps, kv, d)
    perm = jax.random.permutation(jax.random.key(seed + 2),
                                  jnp.arange(1, n_pages))
    pt = perm.reshape(b, pages_per_seq).astype(jnp.int32)
    return pack_pages(rk), pack_pages(rv), rk, rv, pt


@pytest.mark.parametrize("ps,kv,d", [(8, 2, 32), (32, 5, 64), (16, 1, 128),
                                     (8, 3, 16)])
def test_pack_view_round_trip(ps, kv, d):
    rows = _rows(0, 2, 3, ps, kv, d)
    pages = pack_pages(rows)
    assert pages.shape == (2, 3, ps * kv * d // 128, 128)
    assert jnp.array_equal(view_pages(pages, kv, d), rows)
    assert jnp.array_equal(pack_pages(view_pages(pages, kv, d)), pages)
    # `token_rows` (the decode write's addressing) agrees with the packing
    ts = jnp.arange(ps)
    at, slot = token_rows(ts, kv, ps, d)
    for t in range(ps):
        for h in range(kv):
            row, lane = int(at[t, h]), int(slot[t]) * d
            assert (row, lane) == (h * (ps * d // 128) + t * d // 128,
                                   (t * d) % 128)
            assert jnp.array_equal(pages[:, :, row, lane:lane + d],
                                   rows[:, :, t, h])


@pytest.mark.parametrize("kv,g,d,ps", WIDTHS)
@pytest.mark.parametrize("window", [0, 11])
def test_decode_kernel_matches_reference(kv, g, d, ps, window):
    b, pp = 4, 3
    pk, pv, rk, rv, pt = _pools(10, kv, d, ps, b, pp)
    q = _rows(13, b, 1, kv, g, d)
    kv_len = jnp.array([0, 1, ps + 1, pp * ps], jnp.int32)
    for layer in (0, L - 1):
        want = decode_attention(q, rk[layer], rv[layer], kv_len,
                                page_table=pt, window=window,
                                impl="reference")
        ref = decode_attention(q, pk, pv, kv_len, page_table=pt,
                               layer=layer, window=window, impl="reference")
        got = pallas_decode(q, pk, pv, kv_len, page_table=pt,
                            layer=jnp.int32(layer), window=window,
                            interpret=True)
        np.testing.assert_array_equal(ref, want)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0   # kv_len 0: zeros


@pytest.mark.parametrize("kv,g,d,ps", WIDTHS)
@pytest.mark.parametrize("start_page,length", [(0, 13), (1, 2 * 16 - 1),
                                               (2, 3), (1, 5)])
def test_chunk_kernel_matches_reference(kv, g, d, ps, start_page, length):
    """Odd chunk lengths: 13 and 31 end inside a row at two or four tokens
    a row, 3 and 5 inside the chunk's first page; real rows only."""
    pp, C = 5, 2 * ps
    length = min(length, C)
    pk, pv, rk, rv, pt = _pools(20, kv, d, ps, 1, pp)
    q = _rows(23, 1, C, kv, g, d)
    off = jnp.array([start_page * ps], jnp.int32)
    kv_len = off + length
    want = chunk_attention_paged(q, rk[1], rv[1], pt, off, kv_len=kv_len,
                                 impl="reference")
    ref = chunk_attention_paged(q, pk, pv, pt, off, kv_len=kv_len, layer=1,
                                impl="reference")
    got = flash_attention_paged(q, pk, pv, pt, off, kv_len,
                                layer=jnp.int32(1), interpret=True)
    np.testing.assert_array_equal(ref, want)
    np.testing.assert_allclose(got[:, :length], want[:, :length],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv,g,d,ps", WIDTHS)
def test_decode_write_fills_one_lane_slot(kv, g, d, ps):
    """A token written into the odd half (D = 64) or any quarter (D = 32)
    of a row whose other tokens are live leaves them as they were; the
    kernel then reads the new token with the old ones."""
    b, pp = 2, 3
    pk, pv, rk, rv, pt = _pools(30, kv, d, ps, b, pp)
    pos = jnp.array([1, ps + 3], jnp.int32)       # odd slots of their rows
    new_k, new_v = _rows(31, b, 1, kv, d), _rows(32, b, 1, kv, d)
    pk2 = _write_row_lanes(pk, 1, new_k, pos, pt)
    pv2 = _write_row_lanes(pv, 1, new_v, pos, pt)
    want_k, want_v = rk, rv
    for i in range(b):
        page = pt[i, pos[i] // ps]
        want_k = want_k.at[1, page, pos[i] % ps].set(new_k[i, 0])
        want_v = want_v.at[1, page, pos[i] % ps].set(new_v[i, 0])
    np.testing.assert_array_equal(view_pages(pk2, kv, d), want_k)
    np.testing.assert_array_equal(view_pages(pv2, kv, d), want_v)
    q = _rows(33, b, 1, kv, g, d)
    want = decode_attention(q, want_k[1], want_v[1], pos + 1, page_table=pt,
                            impl="reference")
    got = pallas_decode(q, pk2, pv2, pos + 1, page_table=pt,
                        layer=jnp.int32(1), interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_retired_slots_write_only_the_null_page():
    """Retired slots' table rows are all null: their decode writes, at any
    positions (colliding ones too), change page 0 and nothing else."""
    kv, d, ps = 2, 32, 8
    pk, _, _, _, pt = _pools(40, kv, d, ps, 3, 2)
    table = pt.at[1:].set(0)                       # slots 1, 2 retired
    pos = jnp.array([5, 3, 3], jnp.int32)
    new = _rows(41, 3, 1, kv, d)
    out = _write_row_lanes(pk, 2, new, pos, table)
    changed = jnp.any(out != pk, axis=(2, 3))      # (L, n_pages)
    assert set(np.argwhere(np.asarray(changed))[:, 1]) <= {0, int(pt[0, 0])}
    assert bool(changed[2, pt[0, 0]])
    np.testing.assert_array_equal(view_pages(out, kv, d)[2, pt[0, 0], 5],
                                  new[0, 0])


@pytest.mark.parametrize("kv,g,d,ps", WIDTHS[:2])
def test_prefix_hit_and_cow_pages_read_like_cold_ones(kv, g, d, ps):
    """Sequence A prefills 2.5 pages in chunks. Sequence B shares A's first
    page (a prefix hit) and a copy-on-write clone of its second, resumes
    its chunked prefill at the page-aligned start after them, and decodes
    one token into the clone. B's pages through the kernels read exactly
    like a cold B whose every page is private; A's pages never change."""
    C, pp = 2 * ps, 4
    n_pages = 1 + 3 * pp
    prompt_k, prompt_v = _rows(50, 4 * ps, kv, d), _rows(51, 4 * ps, kv, d)
    pool = {"k": jnp.zeros((L, n_pages, ps * kv * d // 128, 128)),
            "v": jnp.zeros((L, n_pages, ps * kv * d // 128, 128))}

    def prefill(pool, page_row, start, end):
        for s in range(start, end, C):
            n = jnp.int32(min(C, end - s))
            for key, rows in (("k", prompt_k), ("v", prompt_v)):
                chunk = jnp.zeros((C, kv, d)).at[:n].set(rows[s:s + n])
                pool[key] = _write_chunk_lanes(pool[key], 1, chunk,
                                               jnp.int32(s), n, page_row)
        return pool

    a_row = jnp.array([1, 2, 3, 0], jnp.int32)
    a_len = 2 * ps + ps // 2
    pool = prefill(pool, a_row, 0, a_len)
    a_pages = {key: pool[key][:, 1:4] for key in pool}
    # B: page 1 shared, page 2 cloned into page 5, fresh pages 6.. after
    pool = copy_pool_page(pool, 2, 5)
    b_row = jnp.array([1, 5, 6, 7], jnp.int32)
    b_len = 3 * ps + 3
    pool = prefill(pool, b_row, 2 * ps, b_len)
    # the replayed last token rewrites its own slot inside the clone
    last = 2 * ps - 1
    for key, rows in (("k", prompt_k), ("v", prompt_v)):
        pool[key] = _write_row_lanes(pool[key], 1, rows[None, last:last + 1],
                                     jnp.array([last]), b_row[None])
    cold = {"k": jnp.zeros_like(pool["k"]), "v": jnp.zeros_like(pool["v"])}
    cold_row = jnp.array([9, 10, 11, 12], jnp.int32)
    cold = prefill(cold, cold_row, 0, b_len)
    for key in pool:
        np.testing.assert_array_equal(pool[key][:, 1:4], a_pages[key])
        np.testing.assert_array_equal(pool[key][1, jnp.array([5, 6, 7])],
                                      cold[key][1, jnp.array([10, 11, 12])])
    q = _rows(52, 1, 1, kv, g, d)
    n = jnp.array([b_len], jnp.int32)
    want = decode_attention(q, prompt_k[None], prompt_v[None], n,
                            impl="reference")
    got = pallas_decode(q, pool["k"], pool["v"], n, page_table=b_row[None],
                        layer=jnp.int32(1), interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    qc = _rows(53, 1, C, kv, g, d)
    off = jnp.array([2 * ps], jnp.int32)
    dense_k = view_pages(cold["k"][1, cold_row], kv, d).reshape(-1, kv, d)
    dense_v = view_pages(cold["v"][1, cold_row], kv, d).reshape(-1, kv, d)
    want = attn_mod.reference_attention(
        qc, dense_k[None], dense_v[None], q_offset=2 * ps, kv_len=n)
    got = flash_attention_paged(qc, pool["k"], pool["v"], b_row[None], off,
                                n, layer=jnp.int32(1), interpret=True)
    real = b_len - 2 * ps
    np.testing.assert_allclose(got[:, :real], want[:, :real],
                               atol=2e-5, rtol=2e-5)


def test_paged_engine_on_the_kernels_matches_the_reference(monkeypatch):
    """A prefix-caching paged engine whose decode and chunk programs run
    the Pallas kernels (interpret mode) over its lane-dense pools emits
    the same greedy tokens as its twin on the jnp reference path: a
    warm-up prompt, then prompts that share its pages (hits, one full hit
    with its copy-on-write clone) and one that shares nothing."""
    from repro.configs import get_config
    from repro.models import ExecOptions, build_model
    from repro.serve.engine import ServeEngine

    cfg = get_config("smollm-360m").smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference"))
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for n in (5, 0, 11)]
    prompts = [np.concatenate([system, t]) for t in tails]
    prompts.append(rng.integers(0, cfg.vocab_size, 9).astype(np.int32))

    def serve():
        eng = ServeEngine(model, params=params, n_slots=3, max_len=64,
                          page_size=8, chunk_pages=1, prefix_cache=True)
        eng.submit(system, max_new_tokens=3)
        eng.run_to_completion()
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_to_completion()
        assert eng.stats.prefix_hit_tokens > 0
        return [r.out_tokens for r in reqs]

    want = serve()
    monkeypatch.setattr(attn_mod, "_kernel_dispatch", lambda pool: True)
    assert serve() == want


@pytest.mark.parametrize("arch,kv_dtype,want", [
    ("smollm-360m", jnp.bfloat16, (32, 9, 80, 128)),     # 5 KV x 64
    ("smollm-360m", jnp.float32, (32, 9, 80, 128)),
    ("smollm-360m", jnp.int8, (32, 9, 32, 5, 64)),       # + scale pools
    ("gemma-7b", jnp.bfloat16, (28, 9, 32, 16, 256)),    # D = 256 > 128
    ("deepseek-v2-lite", jnp.bfloat16, (27, 9, 32, 1, 576)),  # MLA latent
])
def test_cache_shape_picks_the_page_format(arch, kv_dtype, want):
    """Which pools take the lane-dense format: float GQA pools whose widths
    fit; int8 pools, MLA's latent pool and D > 128 keep their pages."""
    from repro.configs import get_config
    from repro.models.transformer import cache_shape

    shapes = cache_shape(get_config(arch), 2, 128, kv_dtype, page_size=32,
                         n_pages=9)
    assert shapes["k"].shape == want
    assert ("ks" in shapes) == (kv_dtype == jnp.int8)
