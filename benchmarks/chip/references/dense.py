"""The dense GQA decoder, as the configurations publish it (Llama's and
ChatGLM3's blocks): everything the harness needs that is particular to
it (chipbench/spec.py lists what a reference holds).

The plain model: float32, no cache, no kernels, no batching. RMSNorm with
the configuration's epsilon, q/k/v projections (with ChatGLM3's qkv
bias), rotary embedding over the first `rope_dims` of each head,
grouped-query causal attention in float32, SwiGLU, a final RMSNorm and
the LM head (the embedding where tied). Weights and K/V rows are read as
they are served (chipbench/plain.py): the seeded weights the benchmark
made, and each K/V row rounded through the stated KV storage.

Departure noted: rotary pairs are interleaved (dims 2i, 2i+1). That is
ChatGLM3's published pairing; Llama's checkpoints pair dims i and i+d/2,
which is the same model with q/k output channels permuted, so random
weights cannot tell the two apart.

It runs one layer at a time, each layer's weights upcast inside its own
jitted call, so no float32 copy of the whole model is resident beside the
served one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import plain, weights
from chipbench.plain import mm


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model's published sizes (configs/<name>.json, "model")."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie_embeddings: bool
    qkv_bias: bool
    rope_dims: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, model: Dict) -> "Shape":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})


# the program's ArchConfig field each published number must equal
PROGRAM_FIELDS = {"n_layers": "n_layers", "d_model": "d_model",
                  "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
                  "head_dim": "head_dim", "d_ff": "d_ff",
                  "vocab_size": "vocab", "tie_embeddings": "tie_embeddings",
                  "qkv_bias": "qkv_bias", "rope_theta": "rope_theta"}


def derived_fields(arch) -> Dict[str, object]:
    """The rotary width, which the program states as a fraction, and the
    heads it runs once padded for tensor parallelism, which neither the
    weights nor this model would follow."""
    return {"rope_dims": int(arch.head_dim * arch.rope_fraction),
            "n_heads": arch.n_heads_padded, "n_kv_heads": arch.kv_pad}


# per-layer contraction axes of each projection (the layer axis removed)
WEIGHTS = {
    "wq": weights.proj(0), "wk": weights.proj(0), "wv": weights.proj(0),
    "wo": weights.proj(0, 1),
    "w1": weights.proj(0), "w3": weights.proj(0), "w2": weights.proj(0),
    "attn_norm": weights.NORM, "ffn_norm": weights.NORM,
    "final_norm": weights.NORM,
    "embed": weights.EMBED, "lm_head": weights.EMBED,
    "bq": weights.BIAS, "bk": weights.BIAS, "bv": weights.BIAS}


def projections(shape: Shape) -> List[Tuple[int, int]]:
    """(K, N) of each projection of one layer: q, k, v, o, and the
    SwiGLU's gate, up and down."""
    d, hd, ff = shape.d_model, shape.head_dim, shape.d_ff
    return [(d, shape.n_heads * hd), (d, shape.n_kv_heads * hd),
            (d, shape.n_kv_heads * hd), (shape.n_heads * hd, d),
            (d, ff), (d, ff), (ff, d)]


def matmul_params(shape: Shape) -> int:
    """Parameters that a token multiplies: every layer's projections and
    the output layer (the embedding is a gather)."""
    return shape.n_layers * sum(k * n for k, n in projections(shape)) \
        + shape.vocab * shape.d_model


@functools.partial(jax.jit, static_argnames=("shape", "kv_dtype"))
def layer(x, layers, i, *, shape: Shape, kv_dtype: str):
    """One decoder layer of the plain model over x (S, d_model)."""
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), layers)
    w = {k: plain.dequant(v) for k, v in lw.items()}
    pos = jnp.arange(x.shape[0])
    a = plain.rms_norm(x, w["attn_norm"], shape.norm_eps)
    q = mm("sd,dhk->shk", a, w["wq"])
    k = mm("sd,dhk->shk", a, w["wk"])
    v = mm("sd,dhk->shk", a, w["wv"])
    if shape.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = plain.rope(q, pos, shape.rope_dims, shape.rope_theta)
    k = plain.rope(k, pos, shape.rope_dims, shape.rope_theta)
    o = plain.attention(q, plain.round_kv(k, kv_dtype),
                        plain.round_kv(v, kv_dtype),
                        scale=shape.head_dim ** -0.5)
    x = x + mm("shk,hkd->sd", o, w["wo"])
    m = plain.rms_norm(x, w["ffn_norm"], shape.norm_eps)
    f = jax.nn.silu(mm("sd,df->sf", m, w["w1"])) * mm("sd,df->sf", m,
                                                     w["w3"])
    return x + mm("sf,fd->sd", f, w["w2"])


def logits(params, shape: Shape, kv_dtype: str, tokens: np.ndarray,
           rows: np.ndarray) -> np.ndarray:
    """(len(rows), vocab) float64 logits of the plain model over `tokens`,
    read at positions `rows`. Causal attention makes row p's logits those
    of the prefix tokens[:p+1]."""
    x = plain.embed(params["embed"], tokens)
    for i in range(shape.n_layers):
        x = layer(x, params["layers"], jnp.int32(i), shape=shape,
                  kv_dtype=kv_dtype)
    head = params["embed"] if shape.tie_embeddings else params["lm_head"]
    return plain.head_logits(x, params["final_norm"], head, rows,
                             eps=shape.norm_eps, vocab=shape.vocab)
