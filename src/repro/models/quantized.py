"""Weight-only INT8 quantization pass + quantization-aware einsum dispatch.

The paper's compute currency is INT8 — the dual NPU chiplets are specified at
15 TOPS INT8 each (§II) — and this module is what routes the serving decode
hot path onto that datapath:

  * `quantize_params` converts a params pytree's projection weights (QKV/O,
    FFN, MoE experts per expert, encdec self+cross) to symmetric int8 with a
    per-output-channel f32 scale, leaving embeddings, LM head, router,
    norms and biases in their original dtype (standard weight-only practice:
    those are either gathers, tiny, or routing-sensitive).
  * `qeinsum` is a drop-in for `jnp.einsum(eq, x, w)` at the projection call
    sites: plain arrays pass straight through (one isinstance check at trace
    time); quantized weights dispatch to the Pallas `kernels/int8_matmul`
    on TPU (int8 upcast in-register on the way into the MXU, f32
    accumulation, scale fused into the epilogue) and to a jnp dequant-matmul
    reference elsewhere — the CPU-exact oracle for the engine equivalence
    tests. MoE expert weights carry a leading expert dim shared with the
    activations; that pattern dispatches through `jax.vmap` of the same
    kernel (one grid batch dim per expert).
  * `quantize_kv_rows` is the KV-cache row quantizer shared by the dense and
    paged int8 KV write paths (models/transformer, models/encdec, the serve
    engine's paste programs): per-token-per-head symmetric int8 over the head
    dim, scale stored in bf16 — the scale rides one value per (position, kv
    head), so the pool overhead is 2/(2·D) over bf16 and the quantized values
    are identical regardless of cache layout, which is what makes the paged
    int8 engine token-exact against the dense int8 oracle.

Quantized leaves are plain dicts `{"int8_q": int8, "s": f32}` (pytree-native:
they slice through the layer-stack lax.scan and ride jit donation unchanged).
`s` keeps the weight's rank with contraction dims reduced to 1, so any
consumer can rebroadcast it onto the matmul output.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_QKEY = "int8_q"

# family → {param key: contraction axes of the stacked weight}
# (axis 0 is always the layer stack; MoE expert weights contract over their
#  axis-2 `d` so the scale keeps the expert dim — per-expert channels.)
_ATTN_AXES = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2)}
# MLA (models/mla.py): the d_model-sized projections quantize like GQA's;
# the absorbed per-head up-projections wk_b/wv_b stay f32 — they ride plain
# einsums inside the latent attention math and their FLOPs/bytes are noise
# (r × Hp × head_dim vs d_model × Hp × head_dim).
_MLA_AXES = {"wq": (1,), "wq_a": (1,), "wq_b": (1,), "wkv_a": (1,),
             "wo": (1, 2)}
_FFN_AXES = {"w1": (1,), "w3": (1,), "w2": (1,)}
_MOE_AXES = {"w1": (2,), "w3": (2,), "w2": (2,),
             "shared_w1": (1,), "shared_w3": (1,), "shared_w2": (1,)}
_CROSS_AXES = {"c" + k: v for k, v in _ATTN_AXES.items()}


def is_quantized(w) -> bool:
    return isinstance(w, dict) and _QKEY in w


def quantize_weight_channelwise(w: jnp.ndarray,
                                axes: Tuple[int, ...]) -> Dict[str, jnp.ndarray]:
    """Symmetric int8 over `axes` (the contraction dims), keepdims f32 scale.

    One quantizer for every weight path: delegates to
    kernels/ref.quantize_channelwise_ref (which the 2-D QDQ helpers also
    use), packed as the pytree leaf `qeinsum` consumes."""
    from repro.kernels.ref import quantize_channelwise_ref
    q, s = quantize_channelwise_ref(w, axes)
    return {_QKEY: q, "s": s}


def _quantize_block(block: dict, axes_table: Dict[str, Tuple[int, ...]]) -> dict:
    return {k: (quantize_weight_channelwise(v, axes_table[k])
                if k in axes_table else v)
            for k, v in block.items()}


def quantize_params(params, cfg):
    """Weight-only int8 pass over an attention-family params pytree.

    dense/vlm: layer QKV/O + FFN.  moe: + experts (per expert) and shared
    experts; the router stays f32 (top-k selection is precision-sensitive and
    its FLOPs are noise).  encdec: encoder + decoder self- and cross-attention
    projections and FFNs.  Embeddings / LM head / norms / biases untouched.
    """
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        table = dict(_MLA_AXES if cfg.attn_kind == "mla" else _ATTN_AXES)
        table.update(_MOE_AXES if fam == "moe" else _FFN_AXES)
        return dict(params, layers=_quantize_block(params["layers"], table))
    if fam == "encdec":
        enc_table = dict(_ATTN_AXES, **_FFN_AXES)
        dec_table = dict(_ATTN_AXES, **_FFN_AXES, **_CROSS_AXES)
        return dict(params,
                    enc=_quantize_block(params["enc"], enc_table),
                    dec=_quantize_block(params["dec"], dec_table))
    raise ValueError(
        f"weight-only int8 applies to attention families, not {fam!r}")


# ---------------------------------------------------------------------------
# Quantization-aware einsum
# ---------------------------------------------------------------------------

def _parse(eq: str):
    lhs, out = eq.replace(" ", "").split("->")
    xs, ws = lhs.split(",")
    contract = [c for c in ws if c not in out]
    batch = [c for c in ws if c in xs and c in out]
    wout = [c for c in ws if c in out and c not in batch]
    return xs, ws, out, contract, batch, wout


def _scale_for_output(s: jnp.ndarray, ws: str, out: str, out_shape):
    """Rebroadcast a keepdims per-channel scale onto the einsum output."""
    w_letters = [c for c in out if c in ws]
    s2 = jnp.einsum(f"{ws}->{''.join(w_letters)}", s)  # squeeze+transpose
    shape = [out_shape[i] if out[i] in ws else 1 for i in range(len(out))]
    return s2.reshape(shape)


def _pallas_2d(x, q, s_flat, *, interpret: Optional[bool]):
    from repro.kernels import ops as kops
    kw = {} if interpret is None else {"interpret": interpret}
    return kops.int8_matmul(x, q, s_flat, **kw)


def qeinsum(eq: str, x: jnp.ndarray, w, *, impl: str = "auto",
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """`jnp.einsum(eq, x, w)` where `w` may be a quantized `{int8_q, s}` leaf.

    impl: 'auto' uses the Pallas int8_matmul on TPU (jnp dequant-matmul
    elsewhere); 'pallas' forces the kernel (interpret mode off-TPU — tests);
    'jnp' forces the reference. The jnp path upcasts the int8 weight into the
    dot (XLA fuses the convert — the weight is never materialized in float)
    and applies the per-channel scale to the f32 accumulator, mirroring the
    kernel's epilogue.

    On TPU a kernel refusal (an einsum pattern or a shape the kernel cannot
    tile) raises instead of quietly running the jnp path, so a quantized
    projection can never leave the int8 datapath unnoticed. Off-TPU the
    refusal falls back to the jnp reference — the CPU oracle.
    """
    if not is_quantized(w):
        return jnp.einsum(eq, x, w)
    q, s = w[_QKEY], w["s"]
    xs, ws, out, contract, batch, wout = _parse(eq)
    on_tpu = jax.default_backend() == "tpu"
    if impl == "pallas" or (impl == "auto" and on_tpu):
        got = _try_pallas(
            x, q, s, xs, ws, out, contract, batch, wout,
            interpret=interpret if interpret is not None else not on_tpu)
        if got is not None:
            return got
        if on_tpu:
            raise ValueError(
                f"int8_matmul cannot run {eq!r} with x {x.shape} and w "
                f"{q.shape} on TPU (einsum pattern or block fit); refusing "
                "to fall back to the jnp dequant path")
    acc = jnp.einsum(eq, x, q.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    acc = acc * _scale_for_output(s, ws, out, acc.shape)
    return acc.astype(x.dtype)


def _try_pallas(x, q, s, xs, ws, out, contract, batch, wout, *, interpret):
    """Reshape-to-2D dispatch onto kernels/int8_matmul; None when the einsum
    pattern or the kernel's block contract (`pick_blocks`) doesn't fit.

    Handled patterns (every projection call site in models/):
      no batch dim:  xs = <x-out><contract>, ws = <contract><wout>,
                     out = <x-out><wout>              (QKV/O, FFN, lm-style)
      one batch dim: the same with a shared leading letter on all three
                     operands — vmapped over it       (MoE expert weights)
    """
    c, b = "".join(contract), "".join(batch)
    if len(b) > 1 or not c:
        return None
    if b:
        if not (xs[0] == b and ws[0] == b and out[0] == b):
            return None
        xs, ws, out = xs[1:], ws[1:], out[1:]
    if not (xs.endswith(c) and ws[:len(c)] == c):
        return None
    x_out = xs[:len(xs) - len(c)]
    if ws[len(c):] != "".join(wout) or out != x_out + "".join(wout):
        return None

    from repro.kernels.int8_matmul import pick_blocks

    def dims(x_shape, q_size):
        m = k = 1
        for d in x_shape[:len(x_out)]:
            m *= d
        for d in x_shape[len(x_out):]:
            k *= d
        return m, q_size // k, k

    def flat_mm(xe, qe, se):
        m, n, k = dims(xe.shape, qe.size)
        out2 = _pallas_2d(xe.reshape(m, k), qe.reshape(k, n),
                          se.reshape(n), interpret=interpret)
        return out2.reshape(xe.shape[:len(x_out)] + qe.shape[len(c):])

    if not b:
        if pick_blocks(*dims(x.shape, q.size), x.dtype.itemsize) is None:
            return None
        return flat_mm(x, q, s.reshape(q.shape[len(c):]))
    # batched (expert) path: shapes are uniform over the batch dim — check
    # the block contract on the slice shapes, then vmap the kernel (one
    # leading grid dim per expert)
    if pick_blocks(*dims(x.shape[1:], q[0].size), x.dtype.itemsize) is None:
        return None
    return jax.vmap(lambda xe, qe, se: flat_mm(
        xe, qe, se.reshape(qe.shape[len(c):])))(x, q, s)


# ---------------------------------------------------------------------------
# INT8 KV-cache row quantization (shared by dense + paged layouts)
# ---------------------------------------------------------------------------

# bf16, not f16: the TPU kernels cannot load f16 tiles on v5e ("Invalid
# vector type for load"), while bf16 loads natively. The scale is rounded
# before the ints are computed against it, so its 2^-9 relative rounding
# moves the int8 grid, not the reconstruction error (still ~s/2), and a
# 2-byte scale keeps the int8 pool at (D+2)/(2D) of bf16.
SCALE_DTYPE = jnp.bfloat16


def quantize_kv_rows(kv: jnp.ndarray):
    """(..., D) K/V rows → (int8 rows, SCALE_DTYPE per-row scale (...,)).

    Per-token-per-head symmetric int8 over the head dim. The scale is rounded
    to storage dtype BEFORE the ints are computed against it, so
    `q * s` reconstructs within s/2 of the input no matter which layout
    (dense rows or page pool) stored the bytes — layout-independence is what
    the paged-vs-dense engine equivalence tests assert token-exactly.
    """
    kvf = kv.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(kvf), axis=-1)
    # floor the SCALE (not the absmax) at a positive value: an all-zero row
    # must quantize to (0, tiny) — a zero scale would turn the next
    # dequant-divide into NaN
    s = jnp.maximum(absmax / 127.0, 1e-6).astype(SCALE_DTYPE)
    sf = s.astype(jnp.float32)
    q = jnp.clip(jnp.round(kvf / sf[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv_rows(q: jnp.ndarray, s: jnp.ndarray,
                       dtype=jnp.float32) -> jnp.ndarray:
    """Exact inverse map used by BOTH the jnp reference attention path and
    (inlined) the Pallas kernel's tile loads: q.astype(f32) * s.astype(f32)."""
    return (q.astype(jnp.float32)
            * s.astype(jnp.float32)[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Token-divergence quality guard (bench + tests)
# ---------------------------------------------------------------------------

def token_divergence(a, b) -> float:
    """1 - matching_prefix/len over two greedy token streams (0 = identical).

    Greedy decode amplifies any logit perturbation after the first flip, so
    the guard is on the PREFIX — the run of tokens the int8 engine reproduces
    before the first divergence — not positionwise equality after it.
    """
    n = max(len(a), len(b))
    if n == 0:
        return 0.0
    match = 0
    for ta, tb in zip(a, b):
        if ta != tb:
            break
        match += 1
    return 1.0 - match / n
