"""The plain model: float32, no cache, no kernels, no batching.

A dense pre-norm decoder as the configurations publish it (Llama's and
ChatGLM3's blocks): RMSNorm with the published epsilon, q/k/v projections
(with ChatGLM3's qkv bias), rotary embedding over the first `rope_dims`
of each head, grouped-query causal attention in float32, SwiGLU, a final
RMSNorm and the LM head (the embedding where tied). Every matmul runs at
`Precision.HIGHEST`, since a float32 matmul on a TPU otherwise runs in
bfloat16.

Two things come from the deployment the configuration states, not from
the program: the weights are the seeded ones the benchmark made, read as
they are served (bf16 upcast exactly; an int8 leaf `{"int8_q", "s"}` as
int8_q x s in float32, which is exact), and each K/V row rounds through
the stated KV storage before attention reads it, as a served cache holds
it: bf16, or int8 with one bf16 scale per (token, KV head).

Departure noted: rotary pairs are interleaved (dims 2i, 2i+1). That is
ChatGLM3's published pairing; Llama's checkpoints pair dims i and i+d/2,
which is the same model with q/k output channels permuted, so random
weights cannot tell the two apart.

It runs one layer at a time, each layer's weights upcast inside its own
jitted call, so no float32 copy of the whole model is resident beside the
served one.

`Shape` holds the model's published sizes (configs/<name>.json, "model").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
SEQ_BUCKET = 1024        # sequences pad to a multiple of this
ROW_BUCKET = 256         # and the rows read to a multiple of this


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


@dataclasses.dataclass(frozen=True)
class Shape:
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


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, positions, dims: int, theta: float):
    """Interleaved rotary embedding over the first `dims` of the last axis
    of x (S, H, D)."""
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, dims/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr, xp = x[..., :dims], x[..., dims:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(xr.shape), xp], -1)


def round_kv(x, kv_dtype: str):
    """A K or V tensor (S, KV, D) as the stated KV storage holds it. int8:
    symmetric over D, one scale per (token, KV head), absmax / 127 floored
    at 1e-6 and rounded to bf16 before the ints are computed against it."""
    if kv_dtype == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if kv_dtype == "int8":
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0,
                        1e-6).astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(f"unknown KV storage {kv_dtype!r}")


def dequant(w):
    """A weight as served, in float32: an int8 leaf `{"int8_q", "s"}` is
    int8_q x s (per-channel scale at the weight's rank), others upcast."""
    if isinstance(w, dict):
        return w["int8_q"].astype(jnp.float32) * w["s"]
    return w.astype(jnp.float32)


def attention(q, k, v, n_kv: int):
    """Causal GQA: q (S, H, D), k/v (S, KV, D); query head h reads KV head
    h // (H / KV)."""
    s_len, h, d = q.shape
    g = h // n_kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qb = q.reshape(s_len // Q_BLOCK, Q_BLOCK, h, d)
    cols = jnp.arange(s_len)

    def block(args):
        qi, i = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = _mm("qhd,khd->hqk", qi, k) * d ** -0.5
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
    return out.reshape(s_len, h, d)


@functools.partial(jax.jit, static_argnames=("shape", "kv_dtype"))
def layer(x, layers, i, *, shape: Shape, kv_dtype: str):
    """One decoder layer of the plain model over x (S, d_model)."""
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, 0, keepdims=False), layers)
    w = {k: dequant(v) for k, v in lw.items()}
    pos = jnp.arange(x.shape[0])
    a = rms_norm(x, w["attn_norm"], shape.norm_eps)
    q = _mm("sd,dhk->shk", a, w["wq"])
    k = _mm("sd,dhk->shk", a, w["wk"])
    v = _mm("sd,dhk->shk", a, w["wv"])
    if shape.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q, pos, shape.rope_dims, shape.rope_theta)
    k = rope(k, pos, shape.rope_dims, shape.rope_theta)
    o = attention(q, round_kv(k, kv_dtype), round_kv(v, kv_dtype),
                  shape.n_kv_heads)
    x = x + _mm("shk,hkd->sd", o, w["wo"])
    m = rms_norm(x, w["ffn_norm"], shape.norm_eps)
    f = jax.nn.silu(_mm("sd,df->sf", m, w["w1"])) * _mm("sd,df->sf", m,
                                                       w["w3"])
    return x + _mm("sf,fd->sd", f, w["w2"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, rows, *, eps: float):
    h = rms_norm(x[rows], norm, eps)
    return _mm("pd,vd->pv", h, head.astype(jnp.float32))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits(params, shape: Shape, kv_dtype: str, tokens: np.ndarray,
           rows: np.ndarray) -> np.ndarray:
    """(len(rows), vocab) float32 logits of the plain model over `tokens`,
    read at positions `rows`. Causal attention makes row p's logits those
    of the prefix tokens[:p+1]; the padding to a bucket never reaches
    them."""
    n = tokens.shape[0]
    padded = -(-n // SEQ_BUCKET) * SEQ_BUCKET
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens
    x = _embed(params["embed"], jnp.asarray(toks))
    for i in range(shape.n_layers):
        x = layer(x, params["layers"], jnp.int32(i), shape=shape,
                  kv_dtype=kv_dtype)
    head = params["embed"] if shape.tie_embeddings else params["lm_head"]
    read = np.full((-(-len(rows) // ROW_BUCKET) * ROW_BUCKET,), rows[-1],
                   np.int32)
    read[:len(rows)] = rows
    out = _head(x, params["final_norm"], head, jnp.asarray(read),
                eps=shape.norm_eps)
    return np.asarray(out[:len(rows), :shape.vocab], np.float64)
