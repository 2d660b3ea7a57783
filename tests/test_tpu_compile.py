"""Compile-only checks for a TPU v5e that is described, not attached.

The serving path's Pallas kernels at smollm-360m widths (d_model 960, 5 KV
heads x 3 query heads x head_dim 64, 32-row pages, 8 slots x 2,048 tokens,
64-token chunks) must pass the TPU compiler — tiling and VMEM refusals that
interpret mode hides surface here at no chip time. Nothing runs: each test
lowers a kernel against shapes placed on a described device and asserts the
compiled program holds a Pallas `tpu_custom_call`. bf16 pools are the
layer-stacked lane-dense (L, n_pages, 80, 128) pools read at a layer
index; int8 pools keep the (n_pages, 32, 5, 64) page.

`test_layer_scans_copy_no_pool` pins what that format is for: the decode
and chunk programs of the model step read and write the pool in place, so
neither copies a pool or one layer's slice of it.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import re

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
LAYERS = 2
ROWS = PAGE * KV * D // 128          # one lane-dense page: (80, 128)
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


def _pool(kv_dtype):
    """A bf16 pool is the stacked lane-dense one; int8 keeps its page."""
    if kv_dtype == jnp.int8:
        return ((N_PAGES, PAGE, KV, D), kv_dtype)
    return ((LAYERS, N_PAGES, ROWS, 128), kv_dtype)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    pool = _pool(kv_dtype)
    scale = ((N_PAGES, PAGE, KV), SCALE_DTYPE)
    shapes = [((SLOTS, 1, KV, G, D), jnp.bfloat16), pool, pool,
              ((SLOTS,), jnp.int32), ((SLOTS, PAGES_PER_SEQ), jnp.int32)]
    if kv_dtype == jnp.int8:
        def fn(q, k, v, n, pt, ks, vs):
            return decode_attention(q, k, v, n, page_table=pt, k_scale=ks,
                                    v_scale=vs)
        shapes += [scale, scale]
    else:
        def fn(q, k, v, n, pt, layer):
            return decode_attention(q, k, v, n, page_table=pt, layer=layer)
        shapes += [((), jnp.int32)]
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
    pool = _pool(kv_dtype)
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
        def fn(q, k, v, pt, off, n, layer):
            return flash_attention_paged(q, k, v, pt, off, n, layer=layer)
        shapes += [((), jnp.int32)]
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


_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(")


def _in_place_fusions(text):
    """Names of the fused computations whose root updates its operand in
    place (scatter, dynamic-update-slice): a fusion calling one has the
    whole buffer's shape without copying it."""
    names, comp = set(), None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif comp and re.match(r"\s*ROOT %\S+ = \S+ "
                               r"(scatter|dynamic-update-slice)\(", line):
            names.add(comp)
    return names


def _pool_sized(text, shapes):
    """Instructions whose result, with size-1 dims dropped, has one of
    `shapes`, other than in-place updates and loop plumbing."""
    in_place = _in_place_fusions(text)
    passive = {"parameter", "get-tuple-element", "tuple", "while",
               "scatter", "dynamic-update-slice", "custom-call", "bitcast"}
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) in passive:
            continue
        if m.group(2) == "fusion":
            called = re.search(r"calls=%([\w.-]+)", line)
            if called and called.group(1) in in_place:
                continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1)):
            if tuple(int(x) for x in dims.split(",") if x != "1") in shapes:
                found.append(line.strip()[:160])
    return found


def test_layer_scans_copy_no_pool(one_chip, monkeypatch):
    """The model step's decode and chunk programs, compiled for the chip
    with the Pallas kernels as the chip runs them, hold no copy (nor any
    other non-in-place result) shaped like the stacked K/V pool or one
    layer's slice of it, in either page format, and their temp memory is
    below one layer's slice. Reading each layer's slice out of the stacked
    pool instead costs six pool-sized copies a layer (the compact stored
    layout is not the kernels'). smollm-360m at two layers and a 1,024
    vocab: the layer loop and the pool are the deployment's, and the tied
    embedding's layout copy outside the loop stays below the bound."""
    from repro.configs import get_config
    from repro.models import ExecOptions, build_model

    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=LAYERS,
                              vocab_size=1024)
    model = build_model(cfg, ExecOptions(attn_impl="reference"))

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype if dtype and jnp.issubdtype(x.dtype, jnp.floating)
            else x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)),
                     jnp.bfloat16)
    cache = on_chip(model.cache_shape(SLOTS, MAX_LEN, jnp.bfloat16,
                                      page_size=PAGE, n_pages=N_PAGES))
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    programs = {
        "decode": (model.decode, {"tokens": z(SLOTS, 1)}, "decode_attention"),
        "chunk": (model.prefill_chunk,
                  {"tokens": z(1, CHUNK), "start": z(1), "length": z(1),
                   "page_row": z(PAGES_PER_SEQ)}, "flash_attention_paged")}
    slice_shapes = {(N_PAGES, PAGE, KV, D), (N_PAGES, ROWS, 128)}
    shapes = slice_shapes | {(LAYERS,) + s for s in slice_shapes}
    slice_bytes = N_PAGES * PAGE * KV * D * 2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name, (fn, batch, kernel) in programs.items():
        # the engine donates the cache to both programs
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(
            params, batch, cache).compile()
        text = compiled.as_text()
        assert any("tpu_custom_call" in line and kernel in line
                   for line in text.splitlines()), name
        assert _pool_sized(text, shapes) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < slice_bytes, (name, temp, slice_bytes)


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
