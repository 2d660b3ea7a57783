"""An int8-weight configuration through the harness: the int8 draw is the
program's own leaf, the reference reads weights and KV as they are served,
a whole tiny int8 run is correct and its int4-grid control is not, and
the throughput cell's metrics (`output_tokens_per_s`, the int8_matmul
roofline, `step_mfu`) come out as hand arithmetic says."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testlib import DATA, REPO, load_run, run_tiny, tiny_tree
from chipbench import costs, engine_trace, readers, reference, serving, weights
from chipbench.reference import Shape
from chipbench.spec import Bench, load_json
from chipbench.traffic import Item
from test_chipbench_harness import _break_decode
from test_chipbench_trace import ev, plane

CELL = "chatglm3-int8-batch"
V5E = "TPU v5 lite"


def tiny_int8_model():
    from repro.models import ExecOptions, build_model
    cfg = load_json(DATA / "tiny-int8.json")
    return build_model(load_run().program_arch(cfg),
                       ExecOptions(attn_impl="reference"))


def test_int8_draw_is_the_programs_leaf():
    """Every projection comes as the program's `{int8_q, s}` leaf, shaped
    as its own quantizer shapes it; re-quantizing the served weights with
    the program's quantizer gives back the same ints and scales; the rest
    of the tree stays bf16."""
    from repro.models.quantized import (
        _ATTN_AXES, _FFN_AXES, quantize_params, quantize_weight_channelwise)
    model = tiny_int8_model()
    drawn = weights.make_params(model, 2**40 + 3, "int8")
    prog = jax.eval_shape(lambda p: quantize_params(p, model.cfg),
                          weights.make_params(model, 1))
    assert jax.tree.structure(drawn) == jax.tree.structure(prog)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(prog)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    axes = dict(_ATTN_AXES, **_FFN_AXES)
    for name, leaf in drawn["layers"].items():
        if name not in axes:
            assert leaf.dtype == jnp.bfloat16, name
            continue
        assert leaf["int8_q"].dtype == jnp.int8
        assert leaf["s"].dtype == jnp.float32
        assert int(jnp.max(jnp.abs(leaf["int8_q"]))) == 127
        again = quantize_weight_channelwise(
            leaf["int8_q"].astype(jnp.float32) * leaf["s"], axes[name])
        np.testing.assert_array_equal(again["int8_q"], leaf["int8_q"])
        np.testing.assert_allclose(again["s"], leaf["s"], rtol=1e-6)
    for name in ("embed", "lm_head", "final_norm"):
        assert drawn[name].dtype == jnp.bfloat16


def test_int4_control_is_the_same_draw_on_a_coarser_grid():
    """The control's weights lie on 15 levels a channel, each within half
    an int4 step of the int8 weights the reference reads."""
    model = tiny_int8_model()
    w8 = weights.make_params(model, 5, "int8")["layers"]["w1"]
    w4 = weights.make_params(model, 5, "int4")["layers"]["w1"]
    assert int(jnp.max(jnp.abs(w4["int8_q"]))) == 7
    np.testing.assert_allclose(w4["s"], w8["s"] * 127 / 7, rtol=1e-6)
    gap = jnp.abs(reference.dequant(w4) - reference.dequant(w8))
    assert bool(jnp.all(gap <= w4["s"] * 0.5 + w8["s"] * 0.5 + 1e-7))
    assert float(jnp.mean(gap)) > 3 * float(jnp.mean(w8["s"]))


@pytest.mark.parametrize("name,eq,n_contract", [
    ("wq", "bsd,dhk->bshk", 1), ("wo", "bshk,hkd->bsd", 2),
    ("w2", "bsf,fd->bsd", 1)])
def test_reference_dequantizes_what_the_engine_serves(name, eq, n_contract):
    """The program's own einsum over an identity reads each served int8
    weight back exactly as the reference dequantizes it."""
    from repro.models.quantized import qeinsum
    leaf = weights.make_params(tiny_int8_model(), 9, "int8")["layers"][name]
    leaf = jax.tree.map(lambda a: a[1], leaf)
    w = reference.dequant(leaf)
    contract = w.shape[:n_contract]
    n = int(np.prod(contract))
    eye = jnp.eye(n, dtype=jnp.float32).reshape((1, n) + contract)
    served = qeinsum(eq, eye, leaf, impl="jnp")[0]
    np.testing.assert_array_equal(np.asarray(served).reshape(w.shape),
                                  np.asarray(w))


def test_int8_kv_rounding_matches_a_hand_computation():
    """Row [1, -0.5, 0.25, 0.1]: absmax / 127 = 0.0078740 rounds in bf16 to
    2^-7 x (1 + 1/128) = 0.00787353515625; the ints against it are 127,
    -64, 32, 13. An all-zero row stays zero. The program's own row
    quantizer stores the same values."""
    from repro.models.quantized import dequantize_kv_rows, quantize_kv_rows
    x = jnp.asarray([[[1.0, -0.5, 0.25, 0.1]], [[0.0, 0.0, 0.0, 0.0]]],
                    jnp.float32)
    s = 0.00787353515625
    want = np.asarray([[[127 * s, -64 * s, 32 * s, 13 * s]], [[0.0] * 4]])
    got = reference.round_kv(x, "int8")
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(dequantize_kv_rows(*quantize_kv_rows(x))), np.asarray(got))


@pytest.fixture(scope="module")
def int8_tree(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("int8"), config="tiny-int8.json")


def test_tiny_int8_run_is_correct_and_its_int4_control_is_not(int8_tree,
                                                              capsys):
    """A whole run of the tiny int8 cell (int8 weights and KV) reads
    correct; the same run with the int4-grid weights served reads false,
    with a mean gap at least 3 x the program's (on five seeds on the CPU
    the program read 0-0.00021, the control 0.021-0.049)."""
    code, res, _ = run_tiny(int8_tree, capsys, seed=2**35 + 1)
    assert code == 0 and res["correct"] is True
    code, ctl, _ = run_tiny(int8_tree, capsys, "--control", seed=2**35 + 1)
    assert code == 0 and ctl["correct"] is False
    assert ctl["check"]["mean_gap"]["value"] > \
        3 * res["check"]["mean_gap"]["value"]


@pytest.mark.parametrize("fault", [
    pytest.param(lambda t, v: (t + 1) % v, id="token-altered"),
    pytest.param(lambda t, v: t.at[::2].set(0), id="half-the-batch-left-out"),
])
def test_broken_int8_timed_path_is_not_correct(int8_tree, capsys,
                                               monkeypatch, fault):
    _break_decode(monkeypatch, fault)
    code, res, _ = run_tiny(int8_tree, capsys, seed=11)
    assert code == 0 and res is not None
    assert res["correct"] is False


def test_throughput_cell_runs_the_batch_mix(int8_tree, capsys):
    """The closed-loop tiny cell on int8 weights reports the throughput
    cell's end-to-end metrics, and its tokens per second agrees with the
    window's token count."""
    code, res, out = run_tiny(int8_tree, capsys, cell="tiny-batch",
                              seconds=1.0)
    assert code == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "output_tokens_per_s"}
    line = [ln for ln in out.splitlines() if ln.startswith("window ")
            and "tokens/s" in ln][0]
    rate = float(line.split("(")[1].split(" tokens/s")[0])
    assert res["metrics"]["output_tokens_per_s"]["value"] == \
        pytest.approx(rate)


def _rec(stamps):
    r = serving.Record(item=Item(due=0.0, prompt=np.zeros(4, np.int32),
                                 max_new=len(stamps)))
    r.stamps = list(stamps)
    return r


def test_output_tokens_per_s_counts_only_the_window():
    """Window [10, 20): tokens at 9.9 and at 20.0 fall outside, and every
    request counts, measured or not."""
    recs = [_rec([9.9, 10.0, 12.5, 19.99]), _rec([15.0, 20.0, 21.0]),
            _rec([11.0] * 3)]
    w = serving.Window(t0=10.0, t1=20.0, counters0={}, counters1={})
    run = types.SimpleNamespace(records=recs, measured=recs[:1], window=w)
    assert readers.output_tokens_per_s(run) == pytest.approx(7 / 10.0)
    run.records = [_rec([1.0, 25.0])]
    assert readers.output_tokens_per_s(run) is None


def test_batch_occupancy_differences_the_window():
    c0 = {"decode_steps": 10, "occupancy_sum": 5.0}
    c1 = {"decode_steps": 30, "occupancy_sum": 23.0}
    w = serving.Window(t0=0.0, t1=1.0, counters0=c0, counters1=c1)
    run = types.SimpleNamespace(window=w)
    assert readers.batch_occupancy(run) == pytest.approx(90.0)
    run.window = serving.Window(t0=0.0, t1=1.0, counters0=c0, counters1=c0)
    assert readers.batch_occupancy(run) is None


def chatglm_shape():
    return Shape.of(Bench(REPO).config("chatglm3-6b-int8")["model"])


def test_int8_matmul_costs_at_a_chatglm3_projection():
    """The gate projection at decode: x (32, 4096) bf16, w (4096, 13696)
    int8, 13,696 float32 scales, out (32, 13696) bf16."""
    shape = chatglm_shape()
    assert (4096, 13696) in costs.projections(shape)
    assert costs.int8_matmul_ops(32, 4096, 13696) == 3_590_324_224
    assert costs.int8_matmul_bytes(32, 4096, 13696) == \
        262_144 + 56_098_816 + 54_784 + 876_544
    peak = costs.peaks(V5E)
    assert costs.int8_matmul_least_s(32, 4096, 13696, peak) == \
        pytest.approx(57_292_288 / 819e9)            # bound by HBM
    # per layer 2 x 16,777,216 (q, o) + 2 x 1,048,576 (k, v) + 3 x
    # 56,098,816 (SwiGLU); plus the 65,024 x 4,096 output layer
    assert costs.matmul_params(shape) == 28 * 203_948_032 + 266_338_304


def _traced_run(kind=V5E):
    cfg = {"model": {"n_layers": 1, "d_model": 128, "n_heads": 1,
                     "n_kv_heads": 1, "head_dim": 128, "d_ff": 128,
                     "vocab": 128, "tie_embeddings": True,
                     "qkv_bias": False, "rope_dims": 128,
                     "rope_theta": 1e4, "norm_eps": 1e-6},
           "serve": {"n_slots": 32, "chunk_pages": 2, "page_size": 32}}
    red = types.SimpleNamespace(
        window_s=10000e-9, kernel_s={"int8_matmul": 2e-6},
        program_s={"_decode": [1e-6, 1e-6], "_chunk": [2e-6]})
    return types.SimpleNamespace(config=cfg, device={"kind": kind},
                                 trace=red, cell={"name": "x"})


def test_int8_matmul_roofline_hand_built_trace():
    """Seven 128 x 128 projections, all bound by HBM: a decode step (m 32)
    moves 7 x 33,280 bytes and a chunk (m 64) 7 x 49,664. Two decode
    steps and a chunk: 813,568 bytes at 819 GB/s over 2 us of kernel."""
    run = _traced_run()
    want = 100 * 813_568 / 819e9 / 2e-6
    assert costs.int8_matmul_roofline(run) == pytest.approx(want)
    assert costs.int8_matmul_roofline(_traced_run("cpu")) is None
    run.trace.kernel_s = {}
    assert costs.int8_matmul_roofline(run) is None


def test_step_mfu_hand_built_trace(monkeypatch):
    """Window [1000, 11000] ns. Decode steps of 3 and 4 active slots and a
    chunk of 100 tokens begin in it; a decode step of 5 begins after it.
    107 tokens x 2 x (7 x 128 x 128 + the 128 x 128 output layer)
    operations over 10 us at 393 TOP/s."""
    host = [ev("bench.window", 1000, 10000),
            ev("engine.decode", 1500, 100, active=3),
            ev("engine.prefill_chunk", 2000, 100, rid=1, slot=0,
               length=100),
            ev("engine.decode", 9000, 100, active=4),
            ev("engine.decode", 11500, 100, active=5)]
    pdata = types.SimpleNamespace(planes=[plane("/host:CPU", {"py": host})])
    monkeypatch.setattr(engine_trace, "cell_trace", lambda run: pdata)
    run = _traced_run()
    want = 100 * 2 * 8 * 128 * 128 * 107 / (10000e-9 * 393e12)
    assert costs.step_mfu(run) == pytest.approx(want)
    assert engine_trace.span_total(pdata, "engine.decode", "active") == 7
    assert engine_trace.span_total(pdata, "engine.release", "slot") is None
    assert costs.step_mfu(_traced_run("cpu")) is None


def test_throughput_cell_is_found_by_name():
    """The configuration, mix and cell resolve by name; the configuration
    is the program's own chatglm3-6b at its published widths, every slot
    able to reach its full context; the cell reports its throughput and
    the five per-layer metrics, and no other cell's."""
    bench = Bench(REPO)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("chatglm3-6b-int8", "batch", 1)
    cfg = bench.config(cell["config"])
    arch = load_run().program_arch(cfg)
    assert (arch.n_layers, arch.d_model, arch.n_kv_heads) == (28, 4096, 2)
    assert cfg["precision"]["weights"] == "int8"
    serve = cfg["serve"]
    assert serve["n_pages"] == 1 + serve["n_slots"] * serve["max_len"] \
        // serve["page_size"]
    mix = bench.traffic(cell["traffic"])
    assert (mix["loop"], mix["clients"]) == ("closed", 48)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_len"]
    e2e = {m["name"] for m in bench.metrics(CELL, trace=False)}
    assert e2e == {"output_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in bench.metrics(CELL, trace=True)}
    assert per_layer == {"decode_step_ms.thru", "device_idle_share.thru",
                         "batch_occupancy.thru", "int8_matmul_roofline.thru",
                         "step_mfu.thru"}
    assert not per_layer & {m["name"] for m in bench.metrics(
        "smollm-chat", trace=True)}
    for m in bench.spec["per_layer"]:
        if m["name"] in per_layer:
            assert m["moves"] == "output_tokens_per_s"


def test_tiny_int8_config_departs_only_in_size():
    """The tiny int8 test configuration keeps ChatGLM3's block: qkv bias
    and rotary over half of each head, int8 weights and KV."""
    cfg = load_json(DATA / "tiny-int8.json")
    arch = load_run().program_arch(cfg)
    assert arch.qkv_bias and arch.rope_fraction == 0.5
    assert dataclasses.asdict(Shape.of(cfg["model"]))["rope_dims"] == 8
