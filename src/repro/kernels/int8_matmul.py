"""Pallas TPU kernel: weight-only INT8 × bf16 matmul with per-channel scales.

The paper's NPU chiplets are 15 TOPS INT8 (§II); this is that datapath on the
MXU: int8 weights are upcast in-register on the way into the systolic array,
accumulation is fp32 in a VMEM scratch tile, and the per-output-channel scale
is fused into the epilogue.

Blocks (`pick_blocks`): each of bm/bn/bk is the largest multiple of its TPU
tile quantum (8 rows on M; 128 lanes on N and K) that divides the dimension
and stays within the preferred size, or else the WHOLE dimension — the two
forms the compiler accepts for a block's last two dims. So a d_model of 960
(a multiple of neither 128 nor 512) runs as one whole-K block instead of
being refused. A choice whose double-buffered working set would exceed
`VMEM_BUDGET` is refused (None), and the caller decides what a refusal means.

The scale operand rides as a (1, N) row with (1, bn) blocks: a 1-D (N,)
operand is refused on TPU (its XLA layout T(1024) does not match Mosaic's
T(128)).

Grid: (M/bm, N/bn, K/bk), K innermost ('arbitrary') so the fp32 accumulator
tile lives across the K sweep; M/N are 'parallel'.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# preferred (bm, bn, bk): MXU-aligned, deep enough in K to amortize the
# accumulate loop
PREFERRED_BLOCKS = (128, 512, 512)
# double-buffered x/w/scale tiles + output tile + f32 accumulator must fit
# the kernel's scoped VMEM (16 MiB on v5e) with headroom
VMEM_BUDGET = 12 * 2**20


def _block(dim: int, pref: int, quantum: int) -> int:
    """Largest multiple of `quantum` <= pref dividing dim, else all of dim."""
    for b in range(min(pref, dim) // quantum * quantum, 0, -quantum):
        if dim % b == 0:
            return b
    return dim


def pick_blocks(m: int, n: int, k: int, x_bytes: int = 2
                ) -> Optional[Tuple[int, int, int]]:
    """(bm, bn, bk) for an (m, k) x (k, n) product, or None when the only
    legal blocks would overflow VMEM_BUDGET — the kernel's shape contract."""
    bm = _block(m, PREFERRED_BLOCKS[0], 8)
    bn = _block(n, PREFERRED_BLOCKS[1], 128)
    bk = _block(k, PREFERRED_BLOCKS[2], 128)
    vmem = (2 * (bm * bk * x_bytes + bk * bn + bn * 4)
            + 2 * bm * bn * x_bytes + bm * bn * 4)
    return (bm, bn, bk) if vmem <= VMEM_BUDGET else None


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, bk) bf16
    w = w_ref[...].astype(x.dtype)                  # (bk, bn) int8 → MXU dtype
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(x: jnp.ndarray, w_q: jnp.ndarray, scales: jnp.ndarray,
                *, bm: Optional[int] = None, bn: Optional[int] = None,
                bk: Optional[int] = None,
                interpret: bool = False) -> jnp.ndarray:
    """x (M,K) bf16/f32 · w_q (K,N) int8 · scales (N,) f32 → (M,N) x.dtype.

    Blocks default to `pick_blocks`; explicit ones are clamped to the dims."""
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2 and scales.shape == (n,), (x.shape, w_q.shape, scales.shape)
    if bm is None or bn is None or bk is None:
        picked = pick_blocks(m, n, k, x.dtype.itemsize)
        assert picked is not None, ("no block fits VMEM", m, n, k)
        bm, bn, bk = (b if b is not None else p
                      for b, p in zip((bm, bn, bk), picked))
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, h: (i, h)),
            pl.BlockSpec((bk, bn), lambda i, j, h: (h, j)),
            pl.BlockSpec((1, bn), lambda i, j, h: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, h: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        name="int8_matmul",
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, w_q, scales.reshape(1, n).astype(jnp.float32))
