"""Grouped-query attention with KV cache, qk-norm, RoPE and chunked long-seq path.

The reference path is pure jnp/einsum so the dry-run's cost analysis is exact;
``ctx.attn_impl == "flash"`` dispatches to the Pallas flash-attention template
(the paper's "RTL template" analogue — see kernels/flash_attention).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import ModelConfig
from repro.model.layers import (Ctx, PSpec, apply_rope, rms_head_norm,
                                rope_angles, shard_axis)

# Sequences longer than this use the q-chunked (flash-style, O(S) memory) path.
FULL_ATTN_MAX_SEQ = 1024
Q_CHUNK = 512


def attn_schema(cfg: ModelConfig, tp: int = 16, cross: bool = False,
                d_in: int = 0, d_out: int = 0, n_heads: int = 0,
                n_kv_heads: int = 0):
    d = d_in or cfg.d_model
    h = n_heads or cfg.n_heads
    kv = n_kv_heads or cfg.n_kv_heads
    hd = cfg.hd
    ha, kva = shard_axis(h, tp), shard_axis(kv, tp)
    # If q-heads shard but kv-heads don't, keep kv replicated (GQA reality on
    # a 16-way TP axis); if q-heads don't shard (whisper 6H, internvl2 14H),
    # the whole attention block is replicated (tiny models — see DESIGN.md).
    sch = {
        "wq": PSpec((d, h * hd), P(None, ha)),
        "wk": PSpec((d, kv * hd), P(None, kva)),
        "wv": PSpec((d, kv * hd), P(None, kva)),
        "wo": PSpec((h * hd, d_out or d), P(ha, None)),
    }
    if cfg.qk_norm:
        sch["q_norm"] = PSpec((hd,), P(), init="ones")
        sch["k_norm"] = PSpec((hd,), P(), init="ones")
    return sch


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


def attention_core(
    q: jax.Array,           # (B, Sq, H, hd)
    k: jax.Array,           # (B, Sk, H, hd)  (already GQA-repeated)
    v: jax.Array,           # (B, Sk, H, hd)
    ctx: Ctx,
    causal: bool,
    q_offset: jax.Array | int = 0,   # position of q[.., 0]: one, or (B,)
    kv_len: Optional[jax.Array] = None,  # valid cache length (decode)
    scale: Optional[float] = None,       # default: head_dim ** -0.5
) -> jax.Array:
    """Softmax attention; dispatches ref-einsum / chunked / Pallas template."""
    if (ctx.attn_impl == "flash" and causal and q.shape[1] == k.shape[1]
            and scale is None):
        from repro.kernels.flash_attention import ops as flash_ops

        return flash_ops.flash_attention(q, k, v, causal=True)
    if ctx.attn_impl == "template_stub":
        # negligible-cost placeholder keeping all data deps + output shape;
        # the hillclimb adds the flash template's analytic flops/bytes
        # (see experiments/hillclimb.py §template model)
        return (q + jnp.mean(k, axis=1, keepdims=True).mean(
            axis=2, keepdims=True) + jnp.mean(v, axis=1, keepdims=True).mean(
            axis=2, keepdims=True)).astype(v.dtype)
    # auto-dispatch: un-repeated K/V (fewer kv heads) -> grouped GQA path
    block = _attn_block_grouped if k.shape[2] != q.shape[2] else _attn_block
    if scale is None:
        scale = q.shape[-1] ** -0.5
    sq, sk = q.shape[1], k.shape[1]
    if sq <= FULL_ATTN_MAX_SEQ or sq != sk:
        return block(q, k, v, scale, causal, q_offset, kv_len)
    # q-chunked flash-style path: O(S) live memory, exact softmax per row.
    n_chunks = (sq + Q_CHUNK - 1) // Q_CHUNK
    q_pad = q
    if sq % Q_CHUNK:
        q_pad = jnp.pad(q, ((0, 0), (0, n_chunks * Q_CHUNK - sq),
                            (0, 0), (0, 0)))

    def chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q_pad, i * Q_CHUNK, Q_CHUNK, axis=1)
        return block(qs, k, v, scale, causal, i * Q_CHUNK, kv_len)

    body = jax.checkpoint(chunk) if ctx.mode == "train" else chunk
    out = jnp.concatenate([body(i) for i in range(n_chunks)], axis=1)
    return out[:, :sq]


def _causal_mask(q_offset, sq: int, sk: int) -> jax.Array:
    """(B|1, Sq, Sk): key position <= query position, where ``q_offset`` is
    the absolute position of the first query, one for all rows or (B,)."""
    qpos = (jnp.reshape(jnp.asarray(q_offset), (-1, 1, 1))
            + jnp.arange(sq)[None, :, None])
    return jnp.arange(sk)[None, None, :] <= qpos


def _attn_block(q, k, v, scale, causal, q_offset, kv_len):
    sq, sk = q.shape[1], k.shape[1]
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = None
    if causal:
        mask = _causal_mask(q_offset, sq, sk)[:, None]   # (B|1,1,Sq,Sk)
    if kv_len is not None:
        valid = jnp.arange(sk)[None, :] < jnp.reshape(kv_len, (-1, 1))
        valid = valid[:, None, None, :]  # (B,1,1,Sk)
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def _attn_block_grouped(q, k, v, scale, causal, q_offset, kv_len):
    """GQA without repeated K/V: q folded to (B,Sq,KV,G,hd) and contracted
    against the raw (B,Sk,KV,hd) cache — removes the G× K/V traffic blowup
    the repeat-based reference pays (the dominant decode HBM term)."""
    B, sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, sq, KV, G, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = _causal_mask(q_offset, sq, k.shape[1])[:, None, None]
        #                                              (B|1,1,1,Sq,Sk)
    if kv_len is not None:
        valid = jnp.arange(k.shape[1])[None, :] < jnp.reshape(kv_len, (-1, 1))
        valid = valid[:, None, None, None, :]          # (B,1,1,1,Sk)
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return o.reshape(B, sq, H, hd)


def attn_apply(
    p,
    h: jax.Array,            # (B, S, D) — normed input
    ctx: Ctx,
    cache: Optional[Dict[str, jax.Array]] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,     # False: encoder self-attention
    use_rope: bool = True,
    scale: Optional[float] = None,   # score scale; default head_dim ** -0.5
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention. Returns (out, updated_cache).

    Cache layout: {"k": (B, S_max, KV, hd), "v": ..., "pos": (B,) int32}.
    Head counts are derived from the param shapes so the zamba2 shared block
    (2·d_model input, d_model output, score scale (head_dim/2)^-½) and
    whisper cross-attention reuse this code path.
    """
    cfg = ctx.cfg
    dt = ctx.compute_dtype
    hd = cfg.hd
    H = p["wq"].shape[1] // hd
    KV = p["wk"].shape[1] // hd
    hx = h.astype(dt)

    q = _split_heads(hx @ p["wq"].astype(dt), H, hd)
    if cross_kv is not None:
        k, v = cross_kv  # (B, S_enc, KV, hd) — precomputed by the encoder
    else:
        k = _split_heads(hx @ p["wk"].astype(dt), KV, hd)
        v = _split_heads(hx @ p["wv"].astype(dt), KV, hd)

    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        if cross_kv is None:
            k = rms_head_norm(p["k_norm"], k)

    causal = causal and cross_kv is None
    new_cache = None
    kv_len = None
    q_offset = 0

    if cross_kv is None and cfg.rope_theta > 0 and use_rope:
        assert ctx.positions is not None
        cos, sin = rope_angles(ctx.positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if cross_kv is None and ctx.mode in ("prefill", "decode"):
        if ctx.mode == "decode":
            assert cache is not None, "decode requires a KV cache"
            # scatter the new K/V at position `pos`, then attend over the
            # cache (in-place dynamic-update-slice: O(1) extra traffic with
            # buffer donation, matching a production decode engine). A step
            # of S > 1 tokens per sequence continues a prefill through the
            # cache: its queries attend causally from each row's `pos`.
            pos = cache["pos"]  # (B,) current lengths
            S = h.shape[1]

            def upd(buf, new):
                f = lambda b1, n1, p1: jax.lax.dynamic_update_slice(
                    b1, n1, (p1, jnp.int32(0), jnp.int32(0))
                )
                return jax.vmap(f)(buf, new, pos)

            k_cache = upd(cache["k"].astype(dt), k)
            v_cache = upd(cache["v"].astype(dt), v)
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos + S}
            k, v = k_cache, v_cache
            if S == 1:
                kv_len = pos + 1
                causal = False  # masking handled via kv_len
            else:
                q_offset = pos
        else:  # prefill: return the populated cache
            new_cache = {
                "k": k,
                "v": v,
                "pos": jnp.full((h.shape[0],), h.shape[1], jnp.int32),
            }

    if not ctx.par.gqa_grouped:        # baseline: materialized repeat
        k = _repeat_kv(k, H // KV)
        v = _repeat_kv(v, H // KV)
    o = attention_core(q, k, v, ctx, causal=causal, q_offset=q_offset,
                       kv_len=kv_len, scale=scale)
    o = o.reshape(h.shape[0], h.shape[1], H * hd)
    out = (o @ p["wo"].astype(dt)).astype(h.dtype)
    return out, new_cache


def cache_schema(cfg: ModelConfig, batch: int, seq: int, tp: int, dp_axes,
                 seq_shard: bool = False):
    """Abstract KV-cache schema for one attention layer (serving)."""
    kva = shard_axis(cfg.n_kv_heads, tp)
    # batch over dp when it divides; otherwise shard the long seq axis over
    # "data" (flash-decoding style — XLA inserts the partial-softmax combine).
    if batch >= 16:
        if seq_shard and kva is None:
            # kv heads don't divide tp -> cache otherwise REPLICATED over
            # "model": shard the seq axis there instead (flash-decoding
            # layout; §Perf cell B)
            kspec = P(dp_axes, "model", None, None)
        else:
            kspec = P(dp_axes, None, kva, None)
    else:
        kspec = P(None, "data", kva, None)
    return {
        "k": PSpec((batch, seq, cfg.n_kv_heads, cfg.hd), kspec, dtype=jnp.bfloat16),
        "v": PSpec((batch, seq, cfg.n_kv_heads, cfg.hd), kspec, dtype=jnp.bfloat16),
        "pos": PSpec((batch,), P(), dtype=jnp.int32, init="zeros"),
    }
