"""End-to-end INT8 decode path (weights + KV), quality-guarded.

Layering of the guards:
  * STRUCTURE  — quantize_params quantizes exactly the projection weights,
    per output channel (per expert for MoE), within the int8 grid's error
    bound.
  * KERNEL     — qeinsum's Pallas dispatch (interpret mode) is bit-identical
    to its jnp dequant-matmul reference for both the 2-D and the vmapped
    expert patterns.
  * ENGINE     — an int8 engine (paged + bucketed + batched) is TOKEN-EXACT
    against the dense int8 oracle for all four attention families across
    page-boundary prompt lengths: row quantization is layout-independent, so
    any drift is an engine bug, not quantization noise.
  * QUALITY    — vs the f32 oracle the guard is numeric (prefill logits RMS
    relative error) plus a token-divergence tolerance. Smoke models are
    RANDOM-INIT, so greedy logits sit near ties and a sub-percent
    perturbation can flip argmax — the divergence tolerance is therefore
    loose (mean prefix divergence <= 0.7 for the bench config, <= 0.9 per
    family); the tight guarantees live in the exactness layers above.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ExecOptions, build_model
from repro.models.quantized import (
    is_quantized, qeinsum, quantize_kv_rows, quantize_params,
    quantize_weight_channelwise, token_divergence,
)
from repro.serve.engine import ServeEngine, generate_greedy


def _prompt(seed, n, vocab=512):
    return np.asarray(
        jax.random.randint(jax.random.key(seed), (n,), 0, vocab), np.int32)


def _build(arch):
    cfg = get_config(arch).smoke()
    model = build_model(cfg, ExecOptions(attn_impl="reference", ce_chunk=32))
    params = model.init(jax.random.key(1))
    extras = None
    if cfg.family == "encdec":
        extras = {"frames": np.asarray(jax.random.normal(
            jax.random.key(9), (cfg.cross_len, cfg.d_model)), np.float32)}
    return cfg, model, params, extras


@pytest.fixture(scope="module")
def smol():
    return _build("smollm-360m")


# ------------------------------------------------------------------ structure
def test_quantize_params_structure_and_bounds(smol):
    cfg, model, params, _ = smol
    qp = quantize_params(params, cfg)
    for key in ("wq", "wk", "wv", "wo", "w1", "w2", "w3"):
        assert is_quantized(qp["layers"][key]), key
    for key in ("attn_norm", "ffn_norm"):
        assert not is_quantized(qp["layers"][key]), key
    assert not is_quantized(qp["embed"])
    # per-channel reconstruction within half an int8 grid step
    w = params["layers"]["wq"]
    q = qp["layers"]["wq"]
    back = q["int8_q"].astype(jnp.float32) * q["s"]
    err = jnp.max(jnp.abs(w.astype(jnp.float32) - back))
    assert float(err) <= float(jnp.max(q["s"])) * 0.5 + 1e-6


def test_quantize_params_moe_per_expert():
    cfg, model, params, _ = _build("qwen2-moe-a2.7b")
    qp = quantize_params(params, cfg)
    w1 = qp["layers"]["w1"]
    assert is_quantized(w1)
    L, e = params["layers"]["w1"].shape[:2]
    # scale keeps (layer, expert, 1, channel): per-expert channels
    assert w1["s"].shape[:2] == (L, e) and w1["s"].shape[2] == 1
    assert not is_quantized(qp["layers"]["router"])


def test_quantize_params_rejects_recurrent_families():
    cfg, model, params, _ = _build("mamba2-780m")
    with pytest.raises(ValueError):
        quantize_params(params, cfg)


# -------------------------------------------------------------------- qeinsum
def test_qeinsum_passthrough_plain_weights():
    x = jax.random.normal(jax.random.key(0), (2, 3, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(qeinsum("bsd,df->bsf", x, w)),
                                  np.asarray(jnp.einsum("bsd,df->bsf", x, w)))


@pytest.mark.parametrize("eq,xs,wshape,axes", [
    ("bsd,dhk->bshk", (2, 4, 128), (128, 4, 32), (0,)),     # qkv projection
    ("bshk,hkd->bsd", (2, 4, 4, 32), (4, 32, 128), (0, 1)), # output proj
    ("bsf,fd->bsd", (2, 4, 256), (256, 128), (0,)),         # ffn down
])
def test_qeinsum_pallas_matches_jnp_reference(eq, xs, wshape, axes):
    """Forced-kernel (interpret) dispatch must agree with the jnp dequant
    path bit-for-bit — both accumulate f32 and scale in the epilogue."""
    x = jax.random.normal(jax.random.key(2), xs, jnp.float32)
    w = quantize_weight_channelwise(
        jax.random.normal(jax.random.key(3), wshape, jnp.float32), axes)
    got = qeinsum(eq, x, w, impl="pallas", interpret=True)
    want = qeinsum(eq, x, w, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_qeinsum_pallas_vmaps_expert_weights():
    """The MoE pattern (shared leading expert dim) rides jax.vmap over the
    kernel — one grid batch dim per expert."""
    xe = jax.random.normal(jax.random.key(4), (4, 2, 64, 128), jnp.float32)
    we = quantize_weight_channelwise(
        jax.random.normal(jax.random.key(5), (4, 128, 128), jnp.float32), (1,))
    got = qeinsum("egcd,edf->egcf", xe, we, impl="pallas", interpret=True)
    want = qeinsum("egcd,edf->egcf", xe, we, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_qeinsum_pallas_falls_back_on_unfit_shapes():
    """A shape whose only tile-legal blocks (whole non-128-multiple K and N)
    overflow the kernel's VMEM budget must fall back to jnp off-TPU (not
    crash inside the kernel's asserts); on TPU the refusal raises."""
    from repro.kernels.int8_matmul import pick_blocks
    x = jax.random.normal(jax.random.key(6), (2, 4, 2600), jnp.float32)
    w = quantize_weight_channelwise(
        jax.random.normal(jax.random.key(7), (2600, 4, 650), jnp.float32),
        (0,))
    assert pick_blocks(8, 2600, 2600, 4) is None
    got = qeinsum("bsd,dhk->bshk", x, w, impl="pallas", interpret=True)
    want = qeinsum("bsd,dhk->bshk", x, w, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------- KV rows / bounds
def test_quantize_kv_rows_roundtrip_bound():
    kv = jax.random.normal(jax.random.key(8), (3, 17, 2, 32), jnp.float32)
    q, s = quantize_kv_rows(kv)
    assert q.dtype == jnp.int8 and s.shape == kv.shape[:-1]
    back = q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]
    err = np.abs(np.asarray(kv) - np.asarray(back))
    bound = np.asarray(s, np.float32)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()


# ---------------------------------------------------- engine: exact vs oracle
@pytest.mark.parametrize("wdtype,kv_dtype", [
    ("int8", None), (None, "int8"), ("int8", "int8")])
def test_int8_engine_token_exact_vs_int8_oracle(smol, wdtype, kv_dtype):
    """Paged + bucketed int8 engine == dense int8 oracle, token for token,
    at prompt lengths straddling page edges (page_size=8). Quantization is
    per-row and layout-independent, so these must be EXACT."""
    cfg, model, params, _ = smol
    lengths = (7, 8, 9, 16, 17)
    solo = {n: generate_greedy(model, params, _prompt(n, n), n_tokens=4,
                               max_len=64, wdtype=wdtype, kv_dtype=kv_dtype)
            for n in lengths}
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params,
                      page_size=8, wdtype=wdtype, kv_dtype=kv_dtype)
    reqs = {n: eng.submit(_prompt(n, n), max_new_tokens=4) for n in lengths}
    eng.run_to_completion()
    for n in lengths:
        assert reqs[n].done
        assert reqs[n].out_tokens == solo[n], (n, reqs[n].out_tokens, solo[n])
    assert eng.stats.pages_in_use == 0      # pool fully returned


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "llava-next-mistral-7b",
                                  "seamless-m4t-medium"])
def test_int8_engine_families_exact(arch):
    """moe / vlm / encdec: full-int8 paged engines stay token-exact against
    their dense int8 oracles across a page boundary."""
    cfg, model, params, extras = _build(arch)
    solo = {n: generate_greedy(model, params, _prompt(n, n), n_tokens=3,
                               max_len=64, wdtype="int8", kv_dtype="int8",
                               extras=extras)
            for n in (7, 9)}
    eng = ServeEngine(model, n_slots=2, max_len=64, params=params,
                      page_size=8, wdtype="int8", kv_dtype="int8")
    reqs = {n: eng.submit(_prompt(n, n), max_new_tokens=3, extras=extras)
            for n in (7, 9)}
    eng.run_to_completion()
    for n, r in reqs.items():
        assert r.out_tokens == solo[n], (arch, n, r.out_tokens, solo[n])
    assert eng.stats.pages_in_use == 0


# ------------------------------------------------------- quality vs f32 oracle
@pytest.mark.slow
@pytest.mark.parametrize("arch,tol", [
    ("smollm-360m", 0.5), ("qwen2-moe-a2.7b", 0.5),
    ("llava-next-mistral-7b", 0.6),
    # random-init enc+dec stacks with cross attention compound the per-layer
    # quantization error; still an order of magnitude under a scale bug
    ("seamless-m4t-medium", 1.0),
])
def test_int8_prefill_logits_close_to_f32(arch, tol):
    """Numeric quality guard: weight-only int8 perturbs prefill logits by a
    bounded RMS relative error. (A mis-applied or dropped per-channel scale
    fails this at O(10)-O(100).)"""
    cfg, model, params, extras = _build(arch)
    batch = {"tokens": jnp.asarray(_prompt(3, 9)[None])}
    if extras:
        batch["frames"] = jnp.asarray(extras["frames"])[None]
    lf, _ = model.prefill(params, batch)
    lq, _ = model.prefill(quantize_params(params, cfg), batch)
    rms = float(jnp.sqrt(jnp.mean((lq - lf) ** 2))
                / jnp.sqrt(jnp.mean(lf ** 2)))
    assert rms < tol, (arch, rms)


@pytest.mark.slow
@pytest.mark.parametrize("arch,tol", [
    ("smollm-360m", 0.7),            # the serve-bench config: tighter
    ("qwen2-moe-a2.7b", 0.9),
    ("llava-next-mistral-7b", 0.9),
    ("seamless-m4t-medium", 0.95),   # random frames + random weights: the
])                                   # greedy argmax sits nearest to ties
def test_int8_token_divergence_bounded(arch, tol):
    """Greedy streams vs the f32 dense oracle stay within the stated mean
    prefix-divergence tolerance over page-boundary prompt lengths. Loose by
    necessity on random-init smoke models (see module docstring); the exact
    guarantees are the int8-oracle equivalence tests above."""
    cfg, model, params, extras = _build(arch)
    divs = []
    for n in (7, 8, 9, 16, 17):
        base = generate_greedy(model, params, _prompt(n, n), n_tokens=6,
                               max_len=64, extras=extras)
        q8 = generate_greedy(model, params, _prompt(n, n), n_tokens=6,
                             max_len=64, wdtype="int8", kv_dtype="int8",
                             extras=extras)
        divs.append(token_divergence(base, q8))
    mean = sum(divs) / len(divs)
    assert mean <= tol, (arch, divs)


# -------------------------------------------------------------- memory + API
def test_int8_kv_pool_bytes_vs_bf16(smol):
    """The acceptance ratio: int8 pool (int8 rows + bf16 row scales + table)
    <= ~0.55x the bf16 pool, same paging geometry."""
    cfg, model, params, _ = smol
    kw = dict(n_slots=4, max_len=64, params=params, page_size=8)
    bf = ServeEngine(model, **kw, kv_dtype="bf16")
    i8 = ServeEngine(model, **kw, kv_dtype="int8")
    ratio = i8.kv_cache_bytes() / bf.kv_cache_bytes()
    assert ratio <= 0.55, ratio


def test_int8_dtype_validation(smol):
    cfg, model, params, _ = smol
    with pytest.raises(ValueError):
        ServeEngine(model, params=params, wdtype="fp4")
    with pytest.raises(ValueError):
        ServeEngine(model, params=params, kv_dtype="int4")
    cfg2, model2, params2, _ = _build("mamba2-780m")
    with pytest.raises(ValueError):
        ServeEngine(model2, params=params2, wdtype="int8")
    with pytest.raises(ValueError):
        ServeEngine(model2, params=params2, kv_dtype="int8")


# ------------------------------------------------- sliding-window page slots
def test_window_slots_hold_o_window_pages(smol):
    """A window-attention config generating far past its window must hold
    O(window) pages — freed/unmapped mid-flight — and stay token-exact
    against the dense oracle (whose window mask hides the same rows)."""
    cfg, model, params, _ = smol
    cfgw = dataclasses.replace(cfg, window=16)
    mw = build_model(cfgw, ExecOptions(attn_impl="reference", ce_chunk=32))
    pw = mw.init(jax.random.key(2))
    p = _prompt(21, 12)
    solo = generate_greedy(mw, pw, p, n_tokens=48, max_len=64)
    eng = ServeEngine(mw, n_slots=1, max_len=64, params=pw, page_size=8)
    assert eng._window == 16
    r = eng.submit(p, max_new_tokens=48)
    eng.run_to_completion()
    assert r.out_tokens == solo
    # O(window): ceil((W-1)/ps) + 3 pages, NOT the 8-page full span
    assert eng.stats.peak_pages_in_use <= eng._window_pages() < 8
    assert eng.stats.pages_in_use == 0 \
        and eng.pages_allocatable() == eng.n_pages - 1


def test_window_pool_frees_pages_for_queued_requests(smol):
    """Mid-flight frees must reach the shared pool: two long window requests
    through a pool far smaller than their combined span, exact tokens."""
    cfg, model, params, _ = smol
    cfgw = dataclasses.replace(cfg, window=8)
    mw = build_model(cfgw, ExecOptions(attn_impl="reference", ce_chunk=32))
    pw = mw.init(jax.random.key(3))
    solo = {s: generate_greedy(mw, pw, _prompt(s, 10), n_tokens=30,
                               max_len=64) for s in (31, 32)}
    # full span would be 2 slots x 5 pages; window needs only 3+1 each
    eng = ServeEngine(mw, n_slots=2, max_len=64, params=pw, page_size=8,
                      n_pages=9)
    reqs = {s: eng.submit(_prompt(s, 10), max_new_tokens=30) for s in (31, 32)}
    eng.run_to_completion()
    for s, r in reqs.items():
        assert r.done and r.out_tokens == solo[s], (s, r.out_tokens, solo[s])
    assert eng.stats.pages_in_use == 0


def test_window_int8_combined(smol):
    """Window recycling composes with the int8 pool: same exactness vs the
    dense int8 oracle."""
    cfg, model, params, _ = smol
    cfgw = dataclasses.replace(cfg, window=16)
    mw = build_model(cfgw, ExecOptions(attn_impl="reference", ce_chunk=32))
    pw = mw.init(jax.random.key(4))
    p = _prompt(33, 20)
    solo = generate_greedy(mw, pw, p, n_tokens=30, max_len=64,
                           wdtype="int8", kv_dtype="int8")
    eng = ServeEngine(mw, n_slots=1, max_len=64, params=pw, page_size=8,
                      wdtype="int8", kv_dtype="int8")
    r = eng.submit(p, max_new_tokens=30)
    eng.run_to_completion()
    assert r.out_tokens == solo
    assert eng.stats.peak_pages_in_use <= eng._window_pages()
