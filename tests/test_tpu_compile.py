"""Compile-only checks for a TPU v5e that is described, not attached.

The serving path's Pallas kernels at smollm-360m widths (d_model 960, 5 KV
heads x 3 query heads x head_dim 64, 32-row pages, 8 slots x 2,048 tokens,
64-token chunks) must pass the TPU compiler — tiling and VMEM refusals that
interpret mode hides surface here at no chip time. Nothing runs: each test
lowers a kernel against shapes placed on a described device and asserts the
compiled program holds a Pallas `tpu_custom_call`.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_paged
from repro.kernels.int8_matmul import int8_matmul, pick_blocks
from repro.models.quantized import SCALE_DTYPE

SLOTS, MAX_LEN, PAGE, CHUNK = 8, 2048, 32, 64
KV, G, D = 5, 3, 64
PAGES_PER_SEQ = MAX_LEN // PAGE
N_PAGES = 1 + SLOTS * PAGES_PER_SEQ
# (K, N) of every smollm-360m projection: q/o (960x960), k/v (960x320),
# gate/up (960x2560), down (2560x960)
PROJECTIONS = [(960, 960), (960, 320), (960, 2560), (2560, 960)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    pool = ((N_PAGES, PAGE, KV, D), kv_dtype)
    scale = ((N_PAGES, PAGE, KV), SCALE_DTYPE)
    shapes = [((SLOTS, 1, KV, G, D), jnp.bfloat16), pool, pool,
              ((SLOTS,), jnp.int32), ((SLOTS, PAGES_PER_SEQ), jnp.int32)]
    if kv_dtype == jnp.int8:
        def fn(q, k, v, n, pt, ks, vs):
            return decode_attention(q, k, v, n, page_table=pt, k_scale=ks,
                                    v_scale=vs)
        shapes += [scale, scale]
    else:
        def fn(q, k, v, n, pt):
            return decode_attention(q, k, v, n, page_table=pt)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_dense_decode_attention_compiles(one_chip):
    """The dense-layout kernel `generate_greedy(paged=False)` reaches."""
    cache = ((SLOTS, MAX_LEN, KV, D), jnp.bfloat16)
    text = _compiled_text(decode_attention, one_chip,
                          ((SLOTS, 1, KV, G, D), jnp.bfloat16), cache, cache,
                          ((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_flash_attention_paged_compiles(one_chip, kv_dtype):
    pool = ((N_PAGES, PAGE, KV, D), kv_dtype)
    scale = ((N_PAGES, PAGE, KV), SCALE_DTYPE)
    one = ((1,), jnp.int32)
    shapes = [((1, CHUNK, KV, G, D), jnp.bfloat16), pool, pool,
              ((1, PAGES_PER_SEQ), jnp.int32), one, one]
    if kv_dtype == jnp.int8:
        def fn(q, k, v, pt, off, n, ks, vs):
            return flash_attention_paged(q, k, v, pt, off, n, k_scale=ks,
                                         v_scale=vs)
        shapes += [scale, scale]
    else:
        fn = flash_attention_paged
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("k,n", PROJECTIONS)
@pytest.mark.parametrize("m", [SLOTS, CHUNK], ids=["decode", "chunk"])
def test_int8_matmul_compiles(one_chip, m, k, n):
    assert pick_blocks(m, n, k) is not None
    text = _compiled_text(int8_matmul, one_chip, ((m, k), jnp.bfloat16),
                          ((k, n), jnp.int8), ((n,), jnp.float32))
    assert "tpu_custom_call" in text


def _kernels(lowered):
    text = lowered.compile().as_text()
    return sorted({k for line in text.splitlines() if "tpu_custom_call" in line
                   for k in ("decode_attention", "flash_attention_paged")
                   if k in line})


def test_program_handle_lowers_the_loop_kernels(one_chip, monkeypatch):
    """`ServeEngine.programs()` gives the decode and chunk programs with
    the arguments the tick loop feeds them: lowered for the chip, each
    holds the same kernels as the program lowered from the engine's own
    arrays. Two layers of smollm-360m (with one, the chunk program's
    attention feeds nothing and compiles away), bf16 weights and KV."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import ExecOptions, build_model
    from repro.serve.engine import ServeEngine

    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2,
                              vocab_size=1024)
    model = build_model(cfg, ExecOptions(attn_impl="reference"))
    eng = ServeEngine(model, params=model.init(jax.random.key(0)),
                      n_slots=SLOTS, max_len=8 * PAGE, page_size=PAGE,
                      chunk_pages=CHUNK // PAGE, wdtype="bf16",
                      kv_dtype="bf16")

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    z = jnp.zeros
    loop = {
        "decode": (eng._decode_jit, (
            eng.params, {"tokens": z((SLOTS, 1), jnp.int32)}, eng._cache,
            z((SLOTS,), bool))),
        "chunk": (eng._chunk_jit, (
            eng.params, {"tokens": z((1, CHUNK), jnp.int32),
                         "start": z((1,), jnp.int32),
                         "length": z((1,), jnp.int32),
                         "page_row": z((eng.pages_per_seq,), jnp.int32)},
            eng._cache))}
    # the kernel dispatch asks the backend; the programs compile for the
    # described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    progs = eng.programs()
    assert set(progs) == set(loop)
    want = {"decode": ["decode_attention"],
            "chunk": ["flash_attention_paged"]}
    for name, (fn, args) in progs.items():
        got = _kernels(fn.lower(*on_chip(args)))
        lfn, largs = loop[name]
        assert got == _kernels(lfn.lower(*on_chip(largs))) == want[name]
