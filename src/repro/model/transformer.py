"""Unified layer-stack assembly for all 10 assigned families + the LSTM.

A model is a sequence of *groups* of homogeneous blocks. Parameters for a
group are stacked with a leading layer axis (one pytree leaf per tensor, so
checkpointing/resharding see a flat stable structure); the stack is applied
either **unrolled** (python loop — exact ``cost_analysis``; the dry-run
default) or via ``lax.scan`` (fast compile; ``ParallelismConfig.scan_layers``).

Block kinds:
  attn      — pre-norm attention + MLP (dense archs; d_ff per group)
  moe       — pre-norm attention + MoE FFN (incl. shared experts)
  mamba2    — pre-norm Mamba2 (zamba2 hybrid); before the Mamba layer at each
              of ``hybrid_layer_ids`` zamba2 runs one of ``num_mem_blocks``
              *shared* attention+MLP blocks (alternating) on
              concat(h, h_emb0), with a per-call MLP adapter, and adds its
              output through a per-layer linear to that Mamba layer's input
  rwkv6     — RWKV6 time-mix + channel-mix
  enc/dec   — whisper encoder (non-causal) and decoder (causal + cross-attn)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import ModelConfig
from repro.model import frontend as fe
from repro.model import moe as moe_mod
from repro.model import rwkv as rwkv_mod
from repro.model import ssm as ssm_mod
from repro.model.attention import attn_apply, attn_schema, cache_schema
from repro.model.layers import (Ctx, PSpec, apply_mlp, apply_norm,
                                embed_schema, embed_tokens, lm_logits,
                                mlp_schema, norm_schema, shard_axis,
                                tree_map_pspec)

# ---------------------------------------------------------------------------
# Group structure
# ---------------------------------------------------------------------------


def group_structure(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """[(block_kind, count)] — the stable decomposition of the layer stack."""
    if cfg.family == "audio":
        assert cfg.encoder is not None
        return [("enc", cfg.encoder.n_layers), ("dec", cfg.n_layers)]
    if cfg.family == "moe":
        m = cfg.moe
        groups: List[Tuple[str, int]] = []
        if m.first_dense:
            groups.append(("attn_dense", m.first_dense))
        groups.append(("moe", cfg.n_layers - m.first_dense))
        return groups
    if cfg.family == "hybrid":
        return [("mamba2", cfg.n_layers)]
    if cfg.family == "ssm":
        return [("rwkv6", cfg.n_layers)]
    return [("attn", cfg.n_layers)]


def block_schema(cfg: ModelConfig, kind: str, tp: int):
    if kind in ("attn", "attn_dense"):
        d_ff = cfg.moe.d_ff_dense if (kind == "attn_dense" and cfg.moe) else cfg.d_ff
        return {
            "norm1": norm_schema(cfg),
            "attn": attn_schema(cfg, tp),
            "norm2": norm_schema(cfg),
            "mlp": mlp_schema(cfg, d_ff=d_ff, tp=tp),
        }
    if kind == "moe":
        return {
            "norm1": norm_schema(cfg),
            "attn": attn_schema(cfg, tp),
            "norm2": norm_schema(cfg),
            "moe": moe_mod.moe_schema(cfg, tp),
        }
    if kind == "mamba2":
        return {"norm1": norm_schema(cfg), "mamba": ssm_mod.mamba_schema(cfg, tp)}
    if kind == "rwkv6":
        return {
            "ln1": norm_schema(cfg),
            "att": rwkv_mod.rwkv_time_schema(cfg, tp),
            "ln2": norm_schema(cfg),
            "ffn": rwkv_mod.rwkv_channel_schema(cfg, tp),
        }
    if kind == "enc":
        return {
            "norm1": norm_schema(cfg),
            "attn": attn_schema(cfg, tp),
            "norm2": norm_schema(cfg),
            "mlp": mlp_schema(cfg, tp=tp),
        }
    if kind == "dec":
        return {
            "norm1": norm_schema(cfg),
            "self_attn": attn_schema(cfg, tp),
            "norm2": norm_schema(cfg),
            "cross_attn": attn_schema(cfg, tp),
            "norm3": norm_schema(cfg),
            "mlp": mlp_schema(cfg, tp=tp),
        }
    raise ValueError(kind)


def shared_block_schema(cfg: ModelConfig, tp: int):
    """zamba2: ``num_mem_blocks`` shared blocks (attention on concat(h, emb0),
    width 2·d_model, to d_model; then a GeGLU MLP at d_model), and for each
    hybrid call k its rank-``adapter_rank`` adapter on the MLP's gate/up
    projection and its linear into the Mamba layer's input. Blocks, adapters
    and linears are tuples, not stacks: every call picks its own by a static
    index, so no weight is sliced out of a stack (a copy per step)."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    fa = shard_axis(f, tp)
    block = {
        "norm_in": norm_schema(cfg, d=2 * d),
        "attn": attn_schema(cfg, tp, d_in=2 * d, d_out=d),
        "norm_ff": norm_schema(cfg),
        "mlp": {"w_gate": PSpec((d, f), P(None, fa)),
                "w_up": PSpec((d, f), P(None, fa)),
                "wo": PSpec((f, d), P(fa, None))},
    }
    adapter = {"a": PSpec((d, r), P()),
               "b_gate": PSpec((r, f), P(None, fa)),
               "b_up": PSpec((r, f), P(None, fa))}
    n_calls = len(cfg.hybrid_layer_ids)
    return {"blocks": (block,) * cfg.num_mem_blocks,
            "adapters": (adapter,) * n_calls,
            "linear": (PSpec((d, d), P()),) * n_calls}


def _stack(n: int, tree):
    """Prepend a layer axis (replicated) to every PSpec leaf."""
    return tree_map_pspec(
        lambda s: dataclasses.replace(
            s, shape=(n,) + tuple(s.shape), pspec=P(None, *tuple(s.pspec))
        ),
        tree,
    )


def param_schema(cfg: ModelConfig, tp: int = 16):
    if cfg.family == "lstm":
        from repro.model.lstm import lstm_schema

        return lstm_schema(cfg)
    if cfg.family == "conv1d":
        from repro.model.conv1d import conv1d_schema

        return conv1d_schema(cfg)
    sch: Dict[str, Any] = {"embed": embed_schema(cfg, tp)}
    for gi, (kind, count) in enumerate(group_structure(cfg)):
        sch[f"g{gi}"] = _stack(count, block_schema(cfg, kind, tp))
    if cfg.family == "hybrid" and cfg.hybrid_layer_ids:
        sch["shared"] = shared_block_schema(cfg, tp)
    if cfg.family == "ssm":
        sch["ln0"] = norm_schema(cfg)
    if cfg.frontend:
        sch["frontend"] = fe.frontend_schema(cfg, tp)
    if cfg.family == "audio":
        sch["enc_norm"] = norm_schema(cfg)
    sch["final_norm"] = norm_schema(cfg)
    return sch


# ---------------------------------------------------------------------------
# Cache schema (serving)
# ---------------------------------------------------------------------------


def model_cache_schema(cfg: ModelConfig, batch: int, seq: int, mesh_cfg,
                       tp: int = 16, stacked: bool = False,
                       seq_shard: bool = False):
    """Abstract cache pytree for prefill/decode of `batch` seqs of `seq` max.

    ``stacked=True`` returns the scan-layers layout: one entry per group with
    a leading layer axis (``{"g0": ..., "shared": ...}``) instead of the
    per-layer tuple.
    """
    if stacked:
        return _stacked_cache_schema(cfg, batch, seq, mesh_cfg, tp, seq_shard)
    dp = mesh_cfg.dp_axes
    layers: List[Any] = []
    for kind, count in group_structure(cfg):
        for _ in range(count):
            if kind in ("attn", "attn_dense", "moe"):
                layers.append(cache_schema(cfg, batch, seq, tp, dp,
                                           seq_shard=seq_shard))
            elif kind == "mamba2":
                layers.append(ssm_mod.mamba_state_schema(cfg, batch, dp, tp))
            elif kind == "rwkv6":
                layers.append(rwkv_mod.rwkv_state_schema(cfg, batch, dp, tp))
            elif kind == "enc":
                layers.append(None)           # encoder is stateless
            elif kind == "dec":
                c = cache_schema(cfg, batch, seq, tp, dp,
                                 seq_shard=seq_shard)
                enc_pos = cfg.encoder.n_positions
                kva = c["k"].pspec[2]
                bspec = c["k"].pspec[0] if batch >= 16 else None
                c = dict(c)
                c["ck"] = PSpec((batch, enc_pos, cfg.n_kv_heads, cfg.hd),
                                P(bspec, None, kva, None), dtype=jnp.bfloat16)
                c["cv"] = PSpec((batch, enc_pos, cfg.n_kv_heads, cfg.hd),
                                P(bspec, None, kva, None), dtype=jnp.bfloat16)
                layers.append(c)
    out: Dict[str, Any] = {"layers": tuple(layers)}
    if cfg.family == "hybrid" and cfg.hybrid_layer_ids:
        # one KV cache per call: each call attends over a different input
        out["shared"] = tuple(
            cache_schema(cfg, batch, seq, tp, dp)
            for _ in cfg.hybrid_layer_ids
        )
    return out


def _group_cache_entry(cfg, kind, batch, seq, mesh_cfg, tp,
                       seq_shard=False):
    dp = mesh_cfg.dp_axes
    if kind in ("attn", "attn_dense", "moe"):
        return cache_schema(cfg, batch, seq, tp, dp, seq_shard=seq_shard)
    if kind == "mamba2":
        return ssm_mod.mamba_state_schema(cfg, batch, dp, tp)
    if kind == "rwkv6":
        return rwkv_mod.rwkv_state_schema(cfg, batch, dp, tp)
    if kind == "enc":
        return None
    if kind == "dec":
        c = dict(cache_schema(cfg, batch, seq, tp, dp, seq_shard=seq_shard))
        enc_pos = cfg.encoder.n_positions
        kva = c["k"].pspec[2]
        bspec = c["k"].pspec[0] if batch >= 16 else None
        for key in ("ck", "cv"):
            c[key] = PSpec((batch, enc_pos, cfg.n_kv_heads, cfg.hd),
                           P(bspec, None, kva, None), dtype=jnp.bfloat16)
        return c
    raise ValueError(kind)


def _stacked_cache_schema(cfg, batch, seq, mesh_cfg, tp, seq_shard=False):
    out: Dict[str, Any] = {}
    for gi, (kind, count) in enumerate(group_structure(cfg)):
        entry = _group_cache_entry(cfg, kind, batch, seq, mesh_cfg, tp,
                                   seq_shard)
        out[f"g{gi}"] = None if entry is None else _stack(count, entry)
    if cfg.family == "hybrid" and cfg.hybrid_layer_ids:
        dp = mesh_cfg.dp_axes
        out["shared"] = _stack(len(cfg.hybrid_layer_ids),
                               cache_schema(cfg, batch, seq, tp, dp))
    return out


# ---------------------------------------------------------------------------
# Block applies
# ---------------------------------------------------------------------------


def _apply_attn_block(p, x, ctx: Ctx, cache, d_ff_override=None):
    a, new_cache = attn_apply(p["attn"], apply_norm(p["norm1"], x, ctx.cfg), ctx,
                              cache=cache)
    x = ctx.constrain(x + a)
    m = apply_mlp(p["mlp"], apply_norm(p["norm2"], x, ctx.cfg), ctx.cfg, ctx)
    return ctx.constrain(x + m), new_cache, jnp.float32(0.0)


def _apply_moe_block(p, x, ctx: Ctx, cache):
    a, new_cache = attn_apply(p["attn"], apply_norm(p["norm1"], x, ctx.cfg), ctx,
                              cache=cache)
    x = ctx.constrain(x + a)
    m, aux = moe_mod.moe_apply(p["moe"], apply_norm(p["norm2"], x, ctx.cfg),
                               ctx.cfg, ctx)
    return ctx.constrain(x + m), new_cache, aux


def _apply_mamba_block(p, x, ctx: Ctx, cache, mix=None):
    """Pre-norm Mamba2, residual from ``x``. At a zamba2 hybrid layer
    ``mix`` (the shared block's output) is added to the Mamba input only."""
    u = x if mix is None else x + mix
    m, new_cache = ssm_mod.mamba_apply(p["mamba"],
                                       apply_norm(p["norm1"], u, ctx.cfg), ctx,
                                       state=cache)
    return ctx.constrain(x + m), new_cache, jnp.float32(0.0)


def _apply_rwkv_block(p, x, ctx: Ctx, cache):
    a, st_a = rwkv_mod.rwkv_time_mix(p["att"], apply_norm(p["ln1"], x, ctx.cfg),
                                     ctx, state=cache)
    x = ctx.constrain(x + a)
    f, st_f = rwkv_mod.rwkv_channel_mix(p["ffn"],
                                        apply_norm(p["ln2"], x, ctx.cfg), ctx,
                                        state=cache)
    new_cache = None
    if st_a is not None or st_f is not None:
        new_cache = {**(st_a or {}), **(st_f or {})}
        if cache is not None:  # keep untouched entries (pytree stability)
            for k in cache:
                new_cache.setdefault(k, cache[k])
    return ctx.constrain(x + f), new_cache, jnp.float32(0.0)


def _apply_enc_block(p, x, ctx: Ctx):
    a, _ = attn_apply(p["attn"], apply_norm(p["norm1"], x, ctx.cfg), ctx,
                      causal=False)
    x = ctx.constrain(x + a)
    m = apply_mlp(p["mlp"], apply_norm(p["norm2"], x, ctx.cfg), ctx.cfg, ctx)
    return ctx.constrain(x + m)


def _apply_dec_block(p, x, ctx: Ctx, cache, enc_kv):
    a, new_cache = attn_apply(p["self_attn"],
                              apply_norm(p["norm1"], x, ctx.cfg), ctx,
                              cache=cache)
    x = ctx.constrain(x + a)
    c, _ = attn_apply(p["cross_attn"], apply_norm(p["norm2"], x, ctx.cfg), ctx,
                      cross_kv=enc_kv)
    x = ctx.constrain(x + c)
    m = apply_mlp(p["mlp"], apply_norm(p["norm3"], x, ctx.cfg), ctx.cfg, ctx)
    return ctx.constrain(x + m), new_cache, jnp.float32(0.0)


def _apply_shared_block(sp, k: int, x, emb0, ctx: Ctx, cache):
    """zamba2 hybrid call ``k``: block ``k % num_mem_blocks`` on
    concat(x, emb0), no residual inside; returns (its output through the
    call's linear — the term added to the Mamba layer's input —, the call's
    KV cache)."""
    cfg = ctx.cfg
    dt = ctx.compute_dtype
    with jax.named_scope("zamba2.shared_block"):
        bp, ad = sp["blocks"][k % cfg.num_mem_blocks], sp["adapters"][k]
        u = apply_norm(bp["norm_in"], jnp.concatenate([x, emb0], axis=-1), cfg)
        a, new_cache = attn_apply(bp["attn"], u, ctx, cache=cache,
                                  scale=(cfg.hd / 2) ** -0.5)
        g = apply_norm(bp["norm_ff"], a, cfg).astype(dt)
        mp = bp["mlp"]
        low = g @ ad["a"].astype(dt)
        gate = g @ mp["w_gate"].astype(dt) + low @ ad["b_gate"].astype(dt)
        up = g @ mp["w_up"].astype(dt) + low @ ad["b_up"].astype(dt)
        t = (jax.nn.gelu(gate, approximate=False) * up) @ mp["wo"].astype(dt)
        out = (t @ sp["linear"][k].astype(dt)).astype(x.dtype)
    return ctx.constrain(out), new_cache


# ---------------------------------------------------------------------------
# Full model apply
# ---------------------------------------------------------------------------


def _maybe_ckpt(fn, ctx: Ctx):
    if ctx.mode != "train" or ctx.cfg.remat == "none":
        return fn
    if ctx.cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def apply_model(
    params,
    batch: Dict[str, jax.Array],
    ctx: Ctx,
    cache: Optional[Dict[str, Any]] = None,
    return_hidden: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Returns (logits (B,S,V) f32 — or final hidden states if
    ``return_hidden`` (for memory-bounded chunked CE) —, new_cache, aux)."""
    cfg = ctx.cfg
    tokens = batch["tokens"]
    B, S = tokens.shape

    if ctx.positions is None:
        if ctx.mode == "decode":
            pos0 = _decode_positions(cfg, cache, ctx, B)
            ctx = dataclasses.replace(
                ctx, positions=jnp.reshape(pos0, (B, 1)) + jnp.arange(S)[None])
        else:
            ctx = dataclasses.replace(
                ctx, positions=jnp.broadcast_to(jnp.arange(S)[None], (B, S)))

    x = embed_tokens(params["embed"], tokens, cfg, ctx)
    if cfg.family == "ssm":
        x = apply_norm(params["ln0"], x, cfg)
    aux = jnp.float32(0.0)

    # --- modality frontends (stub embeddings from input_specs) -------------
    if cfg.frontend == "vision" and "patches" in batch:
        vis = fe.project_vision(params["frontend"], batch["patches"], ctx)
        nf = min(cfg.n_frontend_tokens, S)   # short-seq smoke guards
        x = jnp.concatenate([vis[:, :nf].astype(x.dtype), x[:, nf:]], axis=1)

    enc_out = None
    if cfg.family == "audio" and "frames" in batch:
        enc_ctx = dataclasses.replace(
            ctx, mode="train" if ctx.mode == "train" else "prefill",
            positions=jnp.broadcast_to(
                jnp.arange(batch["frames"].shape[1])[None],
                (B, batch["frames"].shape[1])))
        e = fe.embed_audio(params["frontend"], batch["frames"], ctx)
        for gi, (kind, count) in enumerate(group_structure(cfg)):
            if kind != "enc":
                continue
            stacked = params[f"g{gi}"]
            if ctx.par.scan_layers:
                def enc_body(e_c, p_l):
                    return _apply_enc_block(p_l, e_c, enc_ctx), None

                if ctx.mode == "train" and cfg.remat != "none":
                    enc_body = jax.checkpoint(enc_body)
                e, _ = jax.lax.scan(enc_body, e, stacked)
            else:
                for i in range(count):
                    pl = jax.tree.map(lambda a: a[i], stacked)
                    e = _maybe_ckpt(
                        lambda p_, e_: _apply_enc_block(p_, e_, enc_ctx), ctx
                    )(pl, e)
        enc_out = apply_norm(params["enc_norm"], e, cfg)

    if ctx.par.scan_layers:
        x, new_cache, aux_s = _apply_groups_scanned(params, x, ctx, cache,
                                                    enc_out)
        aux = aux + aux_s
        x = apply_norm(params["final_norm"], x, cfg)
        logits = x if return_hidden else head_logits(params, x, ctx)
        if ctx.mode not in ("prefill", "decode"):
            new_cache = None
        return logits, new_cache, aux

    emb0 = x if cfg.family == "hybrid" else None
    calls = {layer: k for k, layer in enumerate(cfg.hybrid_layer_ids)}
    caches = cache["layers"] if cache is not None else None
    shared_caches = list(cache.get("shared", ())) if cache is not None else []
    new_layer_caches: List[Any] = []
    new_shared_caches: List[Any] = []

    li = 0          # global layer index (cache slot)
    for gi, (kind, count) in enumerate(group_structure(cfg)):
        if kind == "enc":
            li += count
            new_layer_caches.extend([None] * count)
            continue
        stacked = params[f"g{gi}"]
        for i in range(count):
            pl = jax.tree.map(lambda a: a[i], stacked)
            c_in = caches[li] if caches is not None else None
            if kind in ("attn", "attn_dense"):
                fn = _maybe_ckpt(
                    lambda p_, x_, c_: _apply_attn_block(p_, x_, ctx, c_), ctx)
                x, c_new, a_ = fn(pl, x, c_in)
            elif kind == "moe":
                fn = _maybe_ckpt(
                    lambda p_, x_, c_: _apply_moe_block(p_, x_, ctx, c_), ctx)
                x, c_new, a_ = fn(pl, x, c_in)
            elif kind == "mamba2":
                mix = None
                if li in calls:
                    k = calls[li]
                    mix, sc_new = _apply_shared_block(
                        params["shared"], k, x, emb0, ctx,
                        shared_caches[k] if shared_caches else None)
                    new_shared_caches.append(sc_new)
                fn = _maybe_ckpt(
                    lambda p_, x_, c_, m_: _apply_mamba_block(p_, x_, ctx, c_,
                                                              m_), ctx)
                x, c_new, a_ = fn(pl, x, c_in, mix)
            elif kind == "rwkv6":
                fn = _maybe_ckpt(
                    lambda p_, x_, c_: _apply_rwkv_block(p_, x_, ctx, c_), ctx)
                x, c_new, a_ = fn(pl, x, c_in)
            elif kind == "dec":
                enc_kv = None
                if enc_out is not None:
                    kvd = _dec_cross_kv(pl["cross_attn"], enc_out, ctx)
                elif c_in is not None and "ck" in c_in:
                    kvd = (c_in["ck"].astype(ctx.compute_dtype),
                           c_in["cv"].astype(ctx.compute_dtype))
                else:
                    raise ValueError("whisper decode needs frames or cache")
                fn = _maybe_ckpt(
                    lambda p_, x_, c_, kv_: _apply_dec_block(p_, x_, ctx, c_, kv_),
                    ctx)
                x, c_new, a_ = fn(pl, x, {k: v for k, v in (c_in or {}).items()
                                          if k in ("k", "v", "pos")} or None,
                                  kvd)
                if c_new is not None:
                    c_new = dict(c_new)
                    c_new["ck"], c_new["cv"] = kvd
            else:
                raise ValueError(kind)
            aux = aux + a_
            new_layer_caches.append(c_new)
            li += 1

    x = apply_norm(params["final_norm"], x, cfg)
    if return_hidden:
        logits = x
    else:
        logits = head_logits(params, x, ctx)

    new_cache = None
    if ctx.mode in ("prefill", "decode"):
        new_cache = {"layers": tuple(new_layer_caches)}
        if new_shared_caches:
            new_cache["shared"] = tuple(new_shared_caches)
    return logits, new_cache, aux


def _block_apply_fn(kind: str):
    if kind in ("attn", "attn_dense"):
        return lambda p, x, ctx, c, enc: _apply_attn_block(p, x, ctx, c)
    if kind == "moe":
        return lambda p, x, ctx, c, enc: _apply_moe_block(p, x, ctx, c)
    if kind == "mamba2":
        return lambda p, x, ctx, c, enc: _apply_mamba_block(p, x, ctx, c)
    if kind == "rwkv6":
        return lambda p, x, ctx, c, enc: _apply_rwkv_block(p, x, ctx, c)
    raise ValueError(kind)


def _apply_groups_scanned(params, x, ctx: Ctx, cache, enc_out):
    """scan-over-layers path (``ParallelismConfig.scan_layers``) — fast
    compile for the full-config dry-run proof; per-layer costs are recovered
    by the reduced-L extrapolation compiles (launch/dryrun.py)."""
    cfg = ctx.cfg
    aux_total = jnp.float32(0.0)
    serving = ctx.mode in ("prefill", "decode")
    new_cache: Dict[str, Any] = {}

    for gi, (kind, count) in enumerate(group_structure(cfg)):
        pstack = params[f"g{gi}"]
        c_g = cache.get(f"g{gi}") if cache is not None else None
        if kind == "enc":
            new_cache[f"g{gi}"] = None
            continue  # encoder ran in the prologue
        if cfg.family == "hybrid":
            x, nc_g, nc_sh, aux_g = _scan_hybrid(params, pstack, x, ctx,
                                                 cache)
            new_cache[f"g{gi}"] = nc_g
            if nc_sh is not None:
                new_cache["shared"] = nc_sh
            aux_total = aux_total + aux_g
            continue

        blk = _block_apply_fn(kind) if kind != "dec" else None

        def body(x_c, xs):
            if c_g is not None:
                p_l, c_l = xs
            else:
                p_l, c_l = xs, None
            if kind == "dec":
                if enc_out is not None:
                    kvd = _dec_cross_kv(p_l["cross_attn"], enc_out, ctx)
                else:
                    kvd = (c_l["ck"].astype(ctx.compute_dtype),
                           c_l["cv"].astype(ctx.compute_dtype))
                sc = {k: v for k, v in (c_l or {}).items()
                      if k in ("k", "v", "pos")} or None
                y, c_new, a_ = _apply_dec_block(p_l, x_c, ctx, sc, kvd)
                if c_new is not None:
                    c_new = dict(c_new, ck=kvd[0].astype(jnp.bfloat16),
                                 cv=kvd[1].astype(jnp.bfloat16))
            else:
                y, c_new, a_ = blk(p_l, x_c, ctx, c_l, enc_out)
            if not serving:
                c_new = None
            return y, (c_new, a_)

        if ctx.mode == "train" and cfg.remat != "none":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots
                if cfg.remat == "dots" else None)
        xs = (pstack, c_g) if c_g is not None else pstack
        x, (c_stacked, auxs) = jax.lax.scan(body, x, xs)
        new_cache[f"g{gi}"] = c_stacked
        aux_total = aux_total + jnp.sum(auxs)
    return x, (new_cache if serving else None), aux_total


def _index(tree, i):
    """``tree`` with every leaf indexed by ``i`` on its leading axis."""
    return jax.tree.map(lambda a: a[i], tree)


def _scan_hybrid(params, pstack, x, ctx: Ctx, cache):
    """zamba2 under scan-over-layers: the plain Mamba layers between two
    hybrid calls are one ``lax.scan`` each; each hybrid layer (its shared
    block call and its Mamba layer) is unrolled, so calls at irregular
    positions and alternating blocks need no dynamic indexing."""
    cfg = ctx.cfg
    emb0 = x
    serving = ctx.mode in ("prefill", "decode")
    c_g = cache.get("g0") if cache is not None else None
    c_sh = cache.get("shared") if cache is not None else None
    states, shared_states = [], []

    def body(x_c, xs):
        p_l, c_l = xs if c_g is not None else (xs, None)
        y, c_new, _ = _apply_mamba_block(p_l, x_c, ctx, c_l)
        return y, (c_new if serving else None)

    if ctx.mode == "train" and cfg.remat != "none":
        body = jax.checkpoint(body)

    def segment(lo, hi, x_c):
        """Plain Mamba layers [lo, hi) as one scan."""
        if hi <= lo:
            return x_c
        xs = _index(pstack, slice(lo, hi))
        if c_g is not None:
            xs = (xs, _index(c_g, slice(lo, hi)))
        x_c, st = jax.lax.scan(body, x_c, xs)
        states.append(st)
        return x_c

    hybrid = _maybe_ckpt(
        lambda p_, x_, c_, m_: _apply_mamba_block(p_, x_, ctx, c_, m_), ctx)
    lo = 0
    for k, layer in enumerate(cfg.hybrid_layer_ids):
        x = segment(lo, layer, x)
        c_l = _index(c_g, layer) if c_g is not None else None
        sc = _index(c_sh, k) if c_sh is not None else None
        mix, sc_new = _apply_shared_block(params["shared"], k, x, emb0, ctx, sc)
        x, c_new, _ = hybrid(_index(pstack, layer), x, c_l, mix)
        states.append(jax.tree.map(lambda a: a[None], c_new))
        shared_states.append(sc_new)
        lo = layer + 1
    x = segment(lo, cfg.n_layers, x)

    if not serving:
        return x, None, None, jnp.float32(0.0)
    nc_g = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *states)
    nc_sh = (jax.tree.map(lambda *a: jnp.stack(a), *shared_states)
             if shared_states else None)
    return x, nc_g, nc_sh, jnp.float32(0.0)


def head_logits(params, x: jax.Array, ctx: Ctx) -> jax.Array:
    """LM head with vocab-sharded output constraint."""
    cfg = ctx.cfg
    logits = lm_logits(params["embed"], x, cfg, ctx)
    if ctx.mesh is not None and ctx.mesh.size > 1:
        from jax.sharding import NamedSharding

        va = "model" if cfg.padded_vocab % ctx.tp_size == 0 else None
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(ctx.mesh, P(ctx.dp, None, va)))
    return logits


def _dec_cross_kv(p_cross, enc_out, ctx: Ctx):
    dt = ctx.compute_dtype
    hd = ctx.cfg.hd
    KV = p_cross["wk"].shape[1] // hd
    B, Se, _ = enc_out.shape
    k = (enc_out.astype(dt) @ p_cross["wk"].astype(dt)).reshape(B, Se, KV, hd)
    v = (enc_out.astype(dt) @ p_cross["wv"].astype(dt)).reshape(B, Se, KV, hd)
    return k, v


def pad_cache(cache, target_len: int):
    """Pad every attention KV cache in `cache` to `target_len` slots.

    Prefill returns caches sized to the prompt; decode scatters new K/V at
    ``pos`` so the buffers must be pre-extended to the serving max length.
    SSM/RWKV states (no seq axis) pass through untouched.
    """
    def pad_entry(c):
        if not (isinstance(c, dict) and "k" in c and "v" in c):
            return c
        out = dict(c)
        for key in ("k", "v"):
            buf = c[key]
            extra = target_len - buf.shape[1]
            if extra > 0:
                pad = [(0, 0)] * buf.ndim
                pad[1] = (0, extra)
                out[key] = jnp.pad(buf, pad)
        return out

    new = {"layers": tuple(pad_entry(c) for c in cache["layers"])}
    if "shared" in cache:
        new["shared"] = tuple(pad_entry(c) for c in cache["shared"])
    return new


def _decode_positions(cfg: ModelConfig, cache, ctx: Ctx, B: int) -> jax.Array:
    """Current sequence lengths (B,) from whichever cache entry tracks them."""
    if ctx.par.scan_layers:
        for gi, (kind, count) in enumerate(group_structure(cfg)):
            if kind in ("attn", "attn_dense", "moe", "dec"):
                return cache[f"g{gi}"]["pos"][0]
        if "shared" in cache:
            return cache["shared"]["pos"][0]
        return jnp.zeros((B,), jnp.int32)
    ai = _first_attn_idx(cfg)
    if ai is not None:
        return cache["layers"][ai]["pos"]
    if cache.get("shared"):
        return cache["shared"][0]["pos"]
    return jnp.zeros((B,), jnp.int32)   # rwkv: positions unused


def _first_attn_idx(cfg: ModelConfig) -> Optional[int]:
    li = 0
    for kind, count in group_structure(cfg):
        if kind in ("attn", "attn_dense", "moe", "dec"):
            return li
        li += count
    return None
