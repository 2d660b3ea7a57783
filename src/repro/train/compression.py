"""Compression-aware gradient synchronization (paper innovation I2 → ICI).

The paper's UCIe extension compresses die-to-die payloads; the pod-scale
analogue compresses the *data-parallel gradient reduction*: gradients are
block-quantized to int8 (+f32 per-block scales ≈ 4.03× payload reduction)
with an **error-feedback** residual [Seide et al. 2014; 1-bit Adam lineage]
so the quantization error is re-injected next step and convergence is
preserved.

Two integration points:
  * `compress_decompress(grads, state)` — in-graph QDQ + error feedback;
    composes with any reduction (used by the default GSPMD train step, and
    the honest-traffic variant below).
  * `compressed_ring_allreduce(x, axis)` — a shard_map ring all-reduce whose
    ppermute payloads really are int8: the HLO collective bytes drop ~4×,
    which is how the hillclimb variant moves the collective roofline term.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def init_error_state(params) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def quantize_leaf(g: jnp.ndarray, block: int = 256):
    q, s, n = kops.quantize_blocks(g.astype(jnp.float32), block=block)
    return q, s, n


def dequantize_leaf(q, s, n, shape, block: int = 256):
    return kops.dequantize_blocks(q, s, n, shape, dtype=jnp.float32)


def compress_decompress(grads, error_state=None, *, block: int = 256):
    """Quantize-dequantize each gradient leaf with error feedback.

    Returns (grads_hat, new_error_state). Used as `grad_transform` in the
    train step: the reduction then carries int8-precision values.
    """
    leaves, treedef = jax.tree.flatten(grads)
    err_leaves = (jax.tree.leaves(error_state) if error_state is not None
                  else [jnp.zeros_like(l, dtype=jnp.float32) for l in leaves])
    new_g, new_e = [], []
    for g, e in zip(leaves, err_leaves):
        gf = g.astype(jnp.float32) + e
        q, s, n = quantize_leaf(gf, block)
        ghat = dequantize_leaf(q, s, n, gf.shape, block)
        new_g.append(ghat.astype(g.dtype))
        new_e.append(gf - ghat)
    return jax.tree.unflatten(treedef, new_g), jax.tree.unflatten(treedef, new_e)


def _ring_allreduce_int8(x: jnp.ndarray, axis_name: str, block: int = 256):
    """Inside shard_map: reduce-scatter + all-gather ring where every hop
    moves int8 blocks + f32 scales instead of f32 values."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    me = jax.lax.axis_index(axis_name)                 # traced device index
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk_at(chunks, idx):
        return jax.lax.dynamic_index_in_dim(chunks, idx % n, 0,
                                            keepdims=False)

    # pad flat so it splits into n equal chunks of whole blocks
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    chunk = -(-size // n)
    chunk = -(-chunk // block) * block
    flat = jnp.pad(flat, (0, chunk * n - size))
    chunks = flat.reshape(n, chunk)

    # --- reduce-scatter phase ------------------------------------------------
    # step t: device i sends its partial of chunk (i+1-t), receives the
    # partial of chunk (i-t) and adds its own copy. After n-1 steps device i
    # owns the FULL reduction of chunk (i+2) mod n.
    acc = chunk_at(chunks, me + 1)
    for step in range(n - 1):
        q, s, _ = kops.quantize_blocks(acc, block=block)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        recv = kops.dequantize_blocks(q, s, chunk, (chunk,))
        acc = chunk_at(chunks, me - step) + recv
    # --- all-gather phase ------------------------------------------------------
    # relative slot r holds absolute chunk (me+2+r) mod n; slots are STATIC:
    # own → r=0; after `step+1` hops we hold device (me-1-step)'s chunk,
    # absolute (me+1-step) → r = n-1-step.
    rel = [None] * n
    q, s, _ = kops.quantize_blocks(acc, block=block)
    rel[0] = kops.dequantize_blocks(q, s, chunk, (chunk,))
    cur_q, cur_s = q, s
    for step in range(n - 1):
        cur_q = jax.lax.ppermute(cur_q, axis_name, perm)
        cur_s = jax.lax.ppermute(cur_s, axis_name, perm)
        rel[n - 1 - step] = kops.dequantize_blocks(cur_q, cur_s, chunk,
                                                   (chunk,))
    stacked = jnp.stack(rel)                           # (n, chunk), relative
    absolute = jnp.roll(stacked, me + 2, axis=0)       # abs p at index p
    full = absolute.reshape(-1)[:size]
    return full.reshape(x.shape).astype(x.dtype)


def compressed_ring_allreduce(x: jnp.ndarray, axis_name: str,
                              block: int = 256) -> jnp.ndarray:
    """Public entry — call inside shard_map over `axis_name`."""
    return _ring_allreduce_int8(x, axis_name, block)


def payload_ratio(shape, block: int = 256) -> float:
    """Compressed/uncompressed byte ratio for one f32 tensor."""
    import math
    n = math.prod(shape)
    blocks = -(-n // block)
    return (blocks * block * 1 + blocks * 4) / (n * 4)
