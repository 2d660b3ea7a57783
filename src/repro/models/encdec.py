"""Encoder-decoder transformer (seamless-m4t-medium backbone).

The audio frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, S_enc, d_model). 12-layer bidirectional
encoder + 12-layer causal decoder with per-layer cross-attention. Decode
shapes grow the decoder self-attention cache; cross-attention K/V are
computed once at prefill and cached.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.common import ParamDef, act_fn, glu_act, rms_norm
from repro.models.quantized import SCALE_DTYPE, qeinsum
from repro.models.transformer import (
    ExecOptions, _expand_kv, _kv_round_of, _pools_of, attn_block, attn_schema,
    chunked_ce_loss, embed_tokens, head_mask, lm_head_weights,
    paged_kv_shapes, remat_wrap,
)


def _ffn_params(L, d, f):
    return {
        "w1": ParamDef((L, d, f), ("layers", "embed", "ff")),
        "w3": ParamDef((L, d, f), ("layers", "embed", "ff")),
        "w2": ParamDef((L, f, d), ("layers", "ff", "embed")),
    }


def schema(cfg) -> Dict[str, Any]:
    d, h, kv, hd, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                       cfg.d_ff)
    Le, Ld, v = cfg.n_enc_layers, cfg.n_dec_layers, cfg.padded_vocab
    enc = {"attn_norm": ParamDef((Le, d), ("layers", None), init="ones"),
           "ffn_norm": ParamDef((Le, d), ("layers", None), init="ones")}
    enc.update(attn_schema(cfg, Le))
    enc.update(_ffn_params(Le, d, f))
    dec = {"attn_norm": ParamDef((Ld, d), ("layers", None), init="ones"),
           "cross_norm": ParamDef((Ld, d), ("layers", None), init="ones"),
           "ffn_norm": ParamDef((Ld, d), ("layers", None), init="ones")}
    dec.update(attn_schema(cfg, Ld))
    dec.update(attn_schema(cfg, Ld, prefix="c"))
    dec.update(_ffn_params(Ld, d, f))
    return {
        "embed": ParamDef((v, d), ("vocab", "embed"), init="small_normal"),
        "enc_norm": ParamDef((d,), (None,), init="ones"),
        "final_norm": ParamDef((d,), (None,), init="ones"),
        "enc": enc,
        "dec": dec,
    }


def _cross_attn_full(x, p, cfg, opts, enc_out, kv_round=None):
    """Full cross attention (train/prefill). Returns (out, (ck, cv)).

    `kv_round` (prefill with a lossy cross cache, i.e. kv_dtype='bf16' —
    int8 pools keep the cross cache f32) rounds the attended ck/cv through
    the storage dtype, so the monolithic prefill sees the same cross K/V
    the decode steps and the chunked prefill read back from the cache."""
    c = opts.constrain
    q = qeinsum("bsd,dhk->bshk", x, p["cwq"])
    ck = qeinsum("bsd,dhk->bshk", enc_out, p["cwk"])
    cv = qeinsum("bsd,dhk->bshk", enc_out, p["cwv"])
    ka, va = (ck, cv) if kv_round is None else (
        ck.astype(kv_round).astype(ck.dtype),
        cv.astype(kv_round).astype(cv.dtype))
    kx, vx = _expand_kv(ka, va, cfg)
    qp = c(q[:, :, :, None, :], "batchlike", None, "heads_flat", None, None)
    o = attn_mod.attention(qp, kx, vx, causal=False, scale=cfg.head_dim ** -0.5,
                           impl=opts.attn_impl, q_chunk=opts.q_chunk,
                           kv_chunk=opts.kv_chunk, unroll=opts.unroll_scans)
    o = o[:, :, :, 0, :] * head_mask(cfg, x.dtype)[None, None, :, None]
    return qeinsum("bshk,hkd->bsd", o, p["cwo"]), (ck, cv)


def _cross_attn_cached(x, p, cfg, opts, cache, mode):
    """Cross-attention against the slot's cached ck/cv rows — 'decode' runs
    the single-query kernel at the fixed cross depth; 'chunk' runs full
    non-causal attention over the chunk's C rows. (Cross-attention is NOT
    part of the shared self-attention core: it has no rope, no causal mask
    and no cache writes — only the projections below.)"""
    b = x.shape[0]
    kvp, gp = cfg.padded_kv_group
    hm = head_mask(cfg, x.dtype)[None, None, :, None]
    scale = cfg.head_dim ** -0.5
    cq = qeinsum("bsd,dhk->bshk", x, p["cwq"])
    if mode == "decode":
        cqg = cq.reshape(b, 1, kvp, gp, cfg.head_dim)
        se = cache["ck"].shape[1]
        co = attn_mod.decode_attention(cqg, cache["ck"], cache["cv"],
                                       jnp.full((b,), se, jnp.int32),
                                       scale=scale)
        co = co.reshape(b, 1, cfg.n_heads_padded, cfg.head_dim)
    else:  # chunk
        ckx, cvx = _expand_kv(cache["ck"].astype(x.dtype),
                              cache["cv"].astype(x.dtype), cfg)
        qp = cq[:, :, :, None, :]
        co = attn_mod.attention(qp, ckx, cvx, causal=False, scale=scale,
                                impl=opts.attn_impl, q_chunk=opts.q_chunk,
                                kv_chunk=opts.kv_chunk,
                                unroll=opts.unroll_scans)
        co = co[:, :, :, 0, :]
    return qeinsum("bshk,hkd->bsd", co * hm, p["cwo"])


def encode(params, frames, cfg, opts: ExecOptions):
    x = opts.constrain(frames, "batchlike", None, None)
    positions = jnp.arange(frames.shape[1])[None, :]

    def body(h, lp):
        h = opts.constrain(h, "batchlike", opts.seq_axis, None)
        a, _ = attn_block(rms_norm(h, lp["attn_norm"]), lp, cfg, opts,
                          positions=positions, mode="train", causal=False)
        h = h + a
        hn = rms_norm(h, lp["ffn_norm"])
        act = act_fn(glu_act(cfg.activation))
        ff = act(qeinsum("bsd,df->bsf", hn, lp["w1"])) \
            * qeinsum("bsd,df->bsf", hn, lp["w3"])
        ff = opts.constrain(ff, "batchlike", None, "ff")
        return h + qeinsum("bsf,fd->bsd", ff, lp["w2"]), None

    from repro.models.common import scan_or_unroll
    x, _ = scan_or_unroll(remat_wrap(body, opts.remat), x, params["enc"],
                          unroll=opts.unroll_scans)
    return rms_norm(x, params["enc_norm"])


def _dec_layer(h, lp, cfg, opts, positions, enc_out, mode, cache,
               kv_round=None, chunk=None):
    c = opts.constrain
    if mode != "decode":
        h = c(h, "batchlike", opts.seq_axis, None)
    # decoder self-attention IS the unified core (transformer.attn_block) —
    # QKV/rope/round/write/attend land there exactly once for every mode.
    # The decoder prefill with a lossy (bf16/int8) KV cache attends the
    # values the cache will store (transformer._round_kv); encdec rope is the
    # full-fraction default, so the shared rope call is identical.
    a, new_cache = attn_block(rms_norm(h, lp["attn_norm"]), lp, cfg, opts,
                              positions=positions, mode=mode, cache=cache,
                              kv_round=kv_round if mode == "prefill" else None,
                              chunk=chunk)
    h = h + a
    xn = rms_norm(h, lp["cross_norm"])
    if mode in ("train", "prefill"):
        # the cross CACHE stays f32 under int8 KV (cache_shape), so only a
        # bf16 kv_round actually rounds the cross attention inputs
        cross_round = kv_round if (mode == "prefill"
                                   and kv_round is not None
                                   and kv_round != jnp.int8) else None
        ca, (ck, cv) = _cross_attn_full(xn, lp, cfg, opts, enc_out,
                                        kv_round=cross_round)
        if mode == "prefill":
            new_cache = dict(new_cache, ck=ck, cv=cv)
    else:  # decode / chunk: read the slot's cached cross K/V
        ca = _cross_attn_cached(xn, lp, cfg, opts, cache, mode)
    h = h + ca
    hn = rms_norm(h, lp["ffn_norm"])
    act = act_fn(glu_act(cfg.activation))
    ff = act(qeinsum("bsd,df->bsf", hn, lp["w1"])) \
        * qeinsum("bsd,df->bsf", hn, lp["w3"])
    ff = c(ff, "batchlike", None, "ff")
    return h + qeinsum("bsf,fd->bsd", ff, lp["w2"]), new_cache


def decode_stack(params, tokens, cfg, opts, enc_out, *, mode, cache=None,
                 positions=None, kv_round=None):
    x = embed_tokens(params, tokens, cfg, opts)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])[None, :]

    def body(h, xs):
        lp, lc = xs
        return _dec_layer(h, lp, cfg, opts, positions, enc_out, mode, lc,
                          kv_round)

    from repro.models.common import scan_or_unroll
    x, new_cache = scan_or_unroll(
        remat_wrap(body, opts.remat if mode == "train" else "none"),
        x, (params["dec"], cache), unroll=opts.unroll_scans)
    return rms_norm(x, params["final_norm"]), new_cache


def train_loss(params, batch, cfg, opts: ExecOptions):
    enc_out = encode(params, batch["frames"], cfg, opts)
    hidden, _ = decode_stack(params, batch["tokens"], cfg, opts, enc_out,
                             mode="train")
    loss = chunked_ce_loss(hidden, lm_head_weights(params, cfg),
                           batch["labels"], cfg, opts)
    return loss, {"loss": loss}


def prefill_cache(params, batch, cfg, opts: ExecOptions):
    """Cache-only prefill (no LM-head) for the serve engine's replay path."""
    enc_out = encode(params, batch["frames"], cfg, opts)
    _, cache = decode_stack(params, batch["tokens"], cfg, opts, enc_out,
                            mode="prefill", kv_round=_kv_round_of(batch))
    b, s = batch["tokens"].shape
    return dict(cache, pos=jnp.full((b,), s, jnp.int32))


def prefill_cross(params, batch, cfg, opts: ExecOptions):
    """Encoder + per-layer cross K/V only — the admission-time half of a
    CHUNKED encdec prefill. The decoder's cross K/V depend on the frames
    alone (written once, read every step), so the engine computes them once
    per request, pastes them into the slot's dense cross cache, and the
    per-tick `prefill_chunk` calls read them back — the encoder never stalls
    the decode batch more than once per request."""
    enc_out = encode(params, batch["frames"], cfg, opts)

    def body(_, lp):
        ck = qeinsum("bsd,dhk->bshk", enc_out, lp["cwk"])
        cv = qeinsum("bsd,dhk->bshk", enc_out, lp["cwv"])
        return None, (ck, cv)

    from repro.models.common import scan_or_unroll
    _, (ck, cv) = scan_or_unroll(body, None, params["dec"],
                                 unroll=opts.unroll_scans)
    return {"ck": ck, "cv": cv}          # (L, B, S_enc, KVp, D)


def prefill_chunk(params, batch, cache, cfg, opts: ExecOptions):
    """One fixed-size chunk of paged decoder prefill (see
    transformer.prefill_chunk for the contract). Cross-attention reads the
    slot's dense cross K/V, pasted at admission by `prefill_cross`; batch
    additionally carries `slot` () int32 to address them."""
    tokens = batch["tokens"]
    start, length = batch["start"], batch["length"]
    slot = batch["slot"]
    b, C = tokens.shape
    positions = start[:, None] + jnp.arange(C)[None, :]
    x = embed_tokens(params, tokens, cfg, opts)
    ck_s = jax.lax.dynamic_index_in_dim(cache["ck"], slot, 1, keepdims=True)
    cv_s = jax.lax.dynamic_index_in_dim(cache["cv"], slot, 1, keepdims=True)
    chunk = {"start": start, "length": length, "page_row": batch["page_row"]}

    def dyn(t, i):
        return jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)

    def body(carry, xs):
        h, pools = carry
        lp, ck, cv, i = xs                       # ck/cv: (1, S_enc, KVp, D)
        layer_cache = {key: dyn(val, i) for key, val in pools.items()}
        layer_cache["ck"], layer_cache["cv"] = ck, cv
        h, new_cache = _dec_layer(h, lp, cfg, opts, positions, None, "chunk",
                                  layer_cache, chunk=chunk)
        pools = {key: jax.lax.dynamic_update_index_in_dim(
            val, new_cache[key], i, 0) for key, val in pools.items()}
        return (h, pools), None

    from repro.models.common import scan_or_unroll
    (_, pools), _ = scan_or_unroll(
        body, (x, _pools_of(cache)),
        (params["dec"], ck_s, cv_s, jnp.arange(cfg.n_dec_layers)),
        unroll=opts.unroll_scans)
    return dict(cache, **pools)


def prefill(params, batch, cfg, opts: ExecOptions):
    enc_out = encode(params, batch["frames"], cfg, opts)
    hidden, cache = decode_stack(params, batch["tokens"], cfg, opts, enc_out,
                                 mode="prefill", kv_round=_kv_round_of(batch))
    logits = jnp.einsum("bsd,vd->bsv", hidden[:, -1:, :],
                        lm_head_weights(params, cfg)).astype(jnp.float32)
    b, s = batch["tokens"].shape
    cache = dict(cache, pos=jnp.full((b,), s, jnp.int32))
    return logits, cache


def decode_step(params, batch, cache, cfg, opts: ExecOptions):
    """Self KV rides the scan carry (in-place DUS); cross K/V are read-only
    xs (no ys re-emission) — avoids double-buffering either cache."""
    positions = cache["pos"]
    page_table = cache.get("page_table")
    x = embed_tokens(params, batch["tokens"], cfg, opts)

    def dyn(t, i):
        return jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)

    def body(carry, xs):
        h, pools = carry
        lp, ck, cv, i = xs
        layer_cache = {key: dyn(val, i) for key, val in pools.items()}
        layer_cache["ck"], layer_cache["cv"] = ck, cv
        if page_table is not None:
            layer_cache["page_table"] = page_table
        h, new_cache = _dec_layer(h, lp, cfg, opts, positions[:, None],
                                  None, "decode", layer_cache)
        pools = {key: jax.lax.dynamic_update_index_in_dim(
            val, new_cache[key], i, 0) for key, val in pools.items()}
        return (h, pools), None

    from repro.models.common import scan_or_unroll
    (x, pools), _ = scan_or_unroll(
        body, (x, _pools_of(cache)),
        (params["dec"], cache["ck"], cache["cv"],
         jnp.arange(cfg.n_dec_layers)),
        unroll=opts.unroll_scans)
    x = rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x,
                        lm_head_weights(params, cfg)).astype(jnp.float32)
    new_cache = dict(pools, ck=cache["ck"], cv=cache["cv"],
                     pos=positions + 1)
    if page_table is not None:
        new_cache["page_table"] = page_table
    return logits, new_cache


def cache_shape(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
                page_size=None, n_pages=None):
    """Self-attention K/V go paged when `page_size` is given (shared sizing
    contract: transformer.paged_kv_shapes); cross K/V stay dense per slot —
    they are written once at prefill at a fixed (cross_len) depth, so paging
    would buy nothing and cost a second table. dtype=jnp.int8 quantizes the
    self-attention K/V only (plus 'ks'/'vs' bf16 row scales); cross K/V keep
    f32 — written once, read every step, and a second dequant operand per
    layer would buy back ~cross_len/max_len of the savings at best."""
    L, kv, hd, se = cfg.n_dec_layers, cfg.kv_pad, cfg.head_dim, cfg.cross_len
    cross_dtype = jnp.float32 if dtype == jnp.int8 else dtype
    cross = {
        "ck": jax.ShapeDtypeStruct((L, batch, se, kv, hd), cross_dtype),
        "cv": jax.ShapeDtypeStruct((L, batch, se, kv, hd), cross_dtype),
    }
    if page_size is None:
        self_kv = {
            "k": jax.ShapeDtypeStruct((L, batch, max_len, kv, hd), dtype),
            "v": jax.ShapeDtypeStruct((L, batch, max_len, kv, hd), dtype),
            "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }
        if dtype == jnp.int8:
            for key in ("ks", "vs"):
                self_kv[key] = jax.ShapeDtypeStruct(
                    (L, batch, max_len, kv), SCALE_DTYPE)
    else:
        self_kv = paged_kv_shapes(L, batch, max_len, kv, hd, dtype,
                                  page_size, n_pages)
    return {**self_kv, **cross}
