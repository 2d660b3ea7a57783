"""Plain float32 pieces that every reference (references/<name>.py) shares:
no cache, no kernels, no batching.

Every matmul runs at `Precision.HIGHEST`, since a float32 matmul on a TPU
otherwise runs in bfloat16. Weights are read as they are served: bf16
upcast exactly, an int8 leaf `{"int8_q", "s"}` as int8_q x s in float32,
which is exact. A K/V row rounds through the stated KV storage before
attention reads it, as a served cache holds it.

A reference runs over one sequence, padded to a multiple of `SEQ_BUCKET`
(`embed`), and reads its logits at the rows asked for, padded to a
multiple of `ROW_BUCKET` (`head_logits`), so that its jitted programs see
few shapes. Causal attention keeps the padding from reaching a real row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
SEQ_BUCKET = 1024        # sequences pad to a multiple of this
ROW_BUCKET = 256         # and the rows read to a multiple of this


def mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI)


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


def attention(q, k, v, scale: float):
    """Causal attention in blocks of `Q_BLOCK` query rows: q (S, H, Dk),
    k (S, KV, Dk), v (S, KV, Dv); query head h reads KV head h // (H / KV).
    Returns (S, H, Dv)."""
    s_len, h, _ = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qb = q.reshape((s_len // Q_BLOCK, Q_BLOCK) + q.shape[1:])
    cols = jnp.arange(s_len)

    def block(args):
        qi, i = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = mm("qhd,khd->hqk", qi, k) * scale
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0])))
    return out.reshape((s_len, h, v.shape[-1]))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def embed(table, tokens: np.ndarray):
    """(padded S, d_model) float32 rows of `table` for `tokens`, padded
    with token 0 to a multiple of `SEQ_BUCKET`."""
    n = tokens.shape[0]
    toks = np.zeros((-(-n // SEQ_BUCKET) * SEQ_BUCKET,), np.int32)
    toks[:n] = tokens
    return _embed(table, jnp.asarray(toks))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, rows, *, eps: float):
    h = rms_norm(x[rows], norm, eps)
    return mm("pd,vd->pv", h, head.astype(jnp.float32))


def head_logits(x, norm, head, rows: np.ndarray, *, eps: float,
                vocab: int) -> np.ndarray:
    """(len(rows), vocab) float64 logits at positions `rows` of the final
    hidden states x: the final RMSNorm, then the output layer `head`
    (vocab, d_model)."""
    read = np.full((-(-len(rows) // ROW_BUCKET) * ROW_BUCKET,), rows[-1],
                   np.int32)
    read[:len(rows)] = rows
    out = _head(x, norm, head, jnp.asarray(read), eps=eps)
    return np.asarray(out[:len(rows), :vocab], np.float64)
