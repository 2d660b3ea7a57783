"""The architecture is a file the configuration names
(references/<name>.py): the dense references draw and compute what they
drew and computed before they became one; a reference of another
architecture, brought with its configuration as files alone, reaches the
weights, the program check and the comparison; a configuration that names
no reference, or a kernel of its own, behaves as the harness says."""

import hashlib
import json
import shutil

import jax
import numpy as np
import pytest

from chipbench_testlib import DATA, REPO, load_run, tiny_tree
from chipbench import check, trace, weights
from chipbench.spec import Bench, SpecError, load_json
from test_chipbench_trace import ev, plane

DENSE = Bench(REPO).reference("dense")
SEED = 2**40 + 17

# sha256 (first 16 hex digits) of the seeded weights in each type and of
# the reference's logits, as the harness made them before the references
# were files (the same CPU arithmetic; `_digest` says what is hashed)
PINNED = {
    "tiny-dense.json": {"bf16": "3c13e58d6fafd056", "int8": "28d25ab1c4ca5414",
                        "int4": "8553074341500d0e",
                        "logits": "5b97e170ad3f944b"},
    "tiny-int8.json": {"bf16": "110d45ae0ab9a627", "int8": "680c6ca03a11e9ff",
                       "int4": "3f9fc4fe34de6827",
                       "logits": "ad744da51ec712c6"},
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _model(cfg, ref):
    from repro.models import ExecOptions, build_model
    return build_model(load_run().program_arch(cfg, ref),
                       ExecOptions(attn_impl="reference"))


@pytest.mark.parametrize("config", sorted(PINNED))
def test_dense_weights_are_bit_identical(config):
    cfg = load_json(DATA / config)
    assert cfg["reference"] == "dense"
    model = _model(cfg, DENSE)
    for dtype in ("bf16", "int8", "int4"):
        params = weights.make_params(model, DENSE.WEIGHTS, SEED, dtype)
        assert _digest(jax.tree.leaves(params)) == PINNED[config][dtype], \
            dtype


@pytest.mark.parametrize("config", sorted(PINNED))
def test_dense_reference_logits_are_bit_identical(config):
    """300 tokens from a fixed generator, logits read at rows 250-299."""
    cfg = load_json(DATA / config)
    prec = cfg["precision"]
    params = weights.make_params(_model(cfg, DENSE), DENSE.WEIGHTS, SEED,
                                 prec["weights"])
    tokens = np.random.default_rng(5).integers(
        0, cfg["model"]["vocab"], 300).astype(np.int32)
    logits = DENSE.logits(params, DENSE.Shape.of(cfg["model"]), prec["kv"],
                          tokens, np.arange(250, 300))
    assert logits.shape == (50, cfg["model"]["vocab"])
    assert _digest([logits]) == PINNED[config]["logits"]


# ------------------------------------------- another architecture by files
@pytest.fixture(scope="module")
def mla_tree(tmp_path_factory):
    """The harness's tree with the MLA + MoE configuration and its
    reference added as files, and nothing of the harness edited."""
    root = tiny_tree(tmp_path_factory.mktemp("mla"),
                     config="tiny-mla-moe.json")
    shutil.copy(DATA / "mla_moe.py",
                root / "benchmarks" / "chip" / "references")
    bench = Bench(root, root / "benchmarks" / "chip")
    cfg = bench.config("tiny")
    return bench, cfg, bench.reference(cfg["reference"])


def test_mla_moe_weights_are_drawn_by_the_references_rules(mla_tree):
    """Every leaf of the program's latent-attention, mixture-of-experts
    model is drawn by the reference's table: each expert's w1 and w3 at
    1 / sqrt(d_model), not from the expert axis; a leaf the table lacks
    is refused by name before anything is drawn."""
    _, cfg, ref = mla_tree
    load_run().check_published(cfg)
    model = _model(cfg, ref)
    assert model.cfg.attn_kind == "mla" and model.cfg.n_experts == 8
    params = jax.jit(weights.builder(model, ref.WEIGHTS))(
        jax.random.key(weights.jax_seed(SEED)))
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(abstract)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(abstract)):
        assert got.shape == want.shape
    lay = params["layers"]
    d = cfg["model"]["d_model"]
    for name in ("w1", "w3"):
        w = np.asarray(lay[name], np.float32)        # (L, E, d, f)
        for e in range(w.shape[1]):
            assert np.std(w[:, e]) == pytest.approx(d ** -0.5, rel=0.05)
    w2 = np.asarray(lay["w2"], np.float32)
    assert np.std(w2) == pytest.approx(cfg["model"]["d_ff_expert"] ** -0.5,
                                       rel=0.05)
    assert np.std(np.asarray(lay["router"], np.float32)) == \
        pytest.approx(d ** -0.5, rel=0.05)
    assert np.all(np.asarray(lay["kv_norm"], np.float32) == 1.0)
    lacking = {k: v for k, v in ref.WEIGHTS.items() if k != "wkv_a"}
    with pytest.raises(ValueError, match="'wkv_a'"):
        weights.builder(model, lacking)


def test_mla_moe_program_departure_is_reported(mla_tree):
    """A published latent rank the program does not run, and a shared
    expert count it states only as a width, are each named."""
    _, cfg, ref = mla_tree
    run = load_run()
    assert run.program_arch(cfg, ref).kv_lora_rank == 32
    for key, value in (("kv_lora_rank", 64), ("n_shared_experts", 1)):
        wrong = json.loads(json.dumps(cfg))
        wrong["model"][key] = value
        with pytest.raises(run.BenchError, match=key):
            run.program_arch(wrong, ref)


class _Req:
    def __init__(self, prompt, out):
        self.prompt, self.out_tokens = prompt, out


def test_gaps_come_from_the_configured_reference(mla_tree, monkeypatch):
    """`check.gaps` runs the configuration's reference at its sizes over
    each prompt and its served tokens; a served token's gap is the
    reference's best logit less its own."""
    _, cfg, ref = mla_tree
    calls = []

    def logits(params, shape, kv_dtype, tokens, rows):
        calls.append((params, shape, kv_dtype, list(tokens), list(rows)))
        out = np.zeros((len(rows), shape.vocab))
        out[:, 3] = 1.0
        return out

    monkeypatch.setattr(ref, "logits", logits, raising=False)
    picked = [type("R", (), {"req": _Req([7, 8, 9], [3, 5])})()]
    gap = check.gaps(ref, "params", cfg["model"], "bf16", picked)
    np.testing.assert_array_equal(gap, [0.0, 1.0])
    assert calls == [("params", ref.Shape.of(cfg["model"]), "bf16",
                      [7, 8, 9, 3], [2, 3])]


# ----------------------------------------------- names the harness looks up
@pytest.mark.parametrize("name", ["absent", "../references/dense", None])
def test_missing_reference_is_refused_before_weights(tmp_path, capsys,
                                                     monkeypatch, name):
    root = tiny_tree(tmp_path)
    path = root / "benchmarks" / "chip" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if name is None:
        del cfg["reference"]
    else:
        cfg["reference"] = name
    path.write_text(json.dumps(cfg))
    with pytest.raises(SpecError):
        Bench(root, root / "benchmarks" / "chip").config("tiny")

    def drawn(*a, **kw):
        raise AssertionError("weights made for a configuration with no "
                             "reference")

    monkeypatch.setattr(weights, "make_params", drawn)
    run = load_run()
    with pytest.raises(SpecError):
        run.main(["--workload", "tiny-chat", "--seed", "3", "--seconds",
                  "1"], root=root, bench_dir=root / "benchmarks" / "chip",
                 allow_cpu=True)
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


def test_configured_kernel_is_found_and_timed():
    """A kernel named only in a configuration is looked for in the
    compiled programs and timed in the trace, apart from a fixed kernel
    whose name its own holds; both real configurations add none."""
    run = load_run()
    cfg = {"kernels": {"decode": ["decode_attention",
                                  "decode_attention_latent"],
                       "chunk": ["flash_attention_paged"]}}
    names = run.kernel_names(cfg)
    assert names == run.KERNELS + ("decode_attention_latent",)
    for c in ("smollm-360m", "chatglm3-6b-int8"):
        assert run.kernel_names(Bench(REPO).config(c)) == run.KERNELS
    hlo = "\n".join([
        '%a = custom-call(%x), custom_call_target="tpu_custom_call", '
        'backend_config="decode_attention_latent"',
        '%b = f32[8] add(%y, %z)  // decode_attention in a comment',
        '%c = custom-call(%w), custom_call_target="tpu_custom_call", '
        'backend_config="int8_matmul"'])
    assert run.found_kernels(hlo, names) == ["decode_attention_latent",
                                             "int8_matmul"]
    assert run.found_kernels(hlo, run.KERNELS) == ["decode_attention",
                                                   "int8_matmul"]
    host = plane("/host:CPU", {"py": [ev("bench.window", 1000, 10000)]})
    ops = [ev("custom-call.1", 2000, 1000,
              long_name="decode_attention_latent.3"),
           ev("custom-call.2", 4000, 500, long_name="decode_attention.1")]
    dev = plane("/device:TPU:0", {trace.OPS_LINE: ops})
    red = trace.reduce(type("P", (), {"planes": [host, dev]})(), names)
    assert red.kernel_s == {"decode_attention_latent": pytest.approx(1e-6),
                            "decode_attention": pytest.approx(5e-7)}
