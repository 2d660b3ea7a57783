"""Seeded random weights, made on the device in the type they are served in.

The program's parameter tree (`model.init`'s structure, read with
`jax.eval_shape`) is filled leaf by leaf name, in one jitted call from the
seed. Each stacked layer weight is drawn one layer at a time (`lax.map`),
so the float32 draw of a whole stack is never resident.

Projections are served in bf16, or in int8 as the program's quantized
leaf `{"int8_q": int8, "s": float32}`: symmetric, one scale per output
channel over the projection's contraction axes, the scale keeping the
weight's rank with those axes reduced to 1. The int8 weights are
quantized from the float32 draw inside the same `lax.map`, so no bf16 copy
of the model is ever made (`int4`, for the control, is the same draw
on a coarser grid). Embeddings, output layer, norms and biases stay
bf16 either way.

Scales are those of a trained model's activations: every projection is
drawn at its true fan-in (d_model into q/k/v, heads x head_dim into the
output projection), embeddings at 0.02. The program's own init takes the
fan-in of a (d_model, heads, head_dim) weight from its head axis, which
saturates every softmax at these widths.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# per-layer contraction axes (the layer axis removed) of each projection:
# its fan-in is their product
CONTRACT: Dict[str, Tuple[int, ...]] = {
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w1": (0,), "w3": (0,), "w2": (0,)}
EMBED_STD = 0.02
BIAS_STD = 0.1
NORMS = ("attn_norm", "ffn_norm", "final_norm")
# quantized projections: the levels each side of zero of each grid, all
# stored as int8
LEVELS = {"int8": 127, "int4": 7}


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole seed, however large."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def quantize(w, axes: Tuple[int, ...], levels: int):
    """Symmetric quantization over `axes` (the contraction axes): ints in
    [-levels, levels] as int8, one float32 scale per output channel, kept
    at the weight's rank."""
    absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    s = jnp.maximum(absmax, 1e-8) / levels
    q = jnp.clip(jnp.round(w / s), -levels, levels).astype(jnp.int8)
    return {"int8_q": q, "s": s}


def _draw_layer(key, shape, name: str, dtype: str):
    fan_in = math.prod(shape[a] for a in CONTRACT[name])
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if dtype in LEVELS:
        return quantize(w, CONTRACT[name], LEVELS[dtype])
    if dtype == "bf16":
        return w.astype(jnp.bfloat16)
    raise ValueError(f"weights are made in bf16, int8 or int4, not "
                     f"{dtype!r}")


def builder(model, dtype: str = "bf16"):
    """key -> the program's parameter tree, seeded, its projections in
    `dtype` (to be jitted). "int4" is the control of an int8 deployment:
    the same draw on the int4 grid of each channel (15 levels, a step of
    absmax / 7), stored as the program's int8 leaf so that the program
    serves it on its own int8 path."""
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    cfg = model.cfg
    if cfg.n_heads_padded != cfg.n_heads or cfg.kv_pad != cfg.n_kv_heads:
        raise ValueError("padded attention heads are not modelled by the "
                         "benchmark's weights or reference")

    def build(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            if name in CONTRACT:
                keys = jax.random.split(k, sds.shape[0])
                leaves.append(jax.lax.map(
                    lambda kk, n=name, s=sds.shape[1:]: _draw_layer(
                        kk, s, n, dtype), keys))
            elif name in NORMS:
                leaves.append(jnp.ones(sds.shape, jnp.bfloat16))
            elif name in ("embed", "lm_head"):
                leaves.append((EMBED_STD * jax.random.normal(
                    k, sds.shape, jnp.float32)).astype(jnp.bfloat16))
            elif name in ("bq", "bk", "bv"):
                leaves.append((BIAS_STD * jax.random.normal(
                    k, sds.shape, jnp.float32)).astype(jnp.bfloat16))
            else:
                raise ValueError(f"no rule to make weight {name!r}")
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def make_params(model, seed: int, dtype: str = "bf16"):
    """The program's parameter tree, seeded, on the device, its
    projections in `dtype`."""
    params = jax.jit(builder(model, dtype))(jax.random.key(jax_seed(seed)))
    jax.block_until_ready(params)
    return params
