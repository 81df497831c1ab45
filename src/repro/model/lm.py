"""Step functions + abstract input specs — the single entry point used by the
trainer, the server, and the multi-pod dry-run.

Everything here is built from the same :mod:`repro.model.layers` PSpec
schemas, so ``init_params`` (smoke), ``abstract_params`` (dry-run) and
``in_shardings`` can never diverge.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.types import (MeshConfig, ModelConfig, ParallelismConfig,
                              ShapeConfig)
from repro.model.layers import Ctx, abstract_params, init_params, pspecs, tree_map_pspec
from repro.model.transformer import (apply_model, model_cache_schema,
                                     param_schema)
from repro.optim.adamw import (AdamWConfig, adamw_update, init_opt_state,
                               opt_state_schema)

__all__ = [
    "param_schema", "make_train_step", "make_prefill_step", "make_decode_step",
    "input_specs", "batch_pspecs", "cross_entropy", "Stepper",
]


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, targets: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """logits (B,S,V) f32, targets (B,S) int32 (-1 = masked). -> (loss, n_tok)."""
    mask = (targets >= 0)
    t = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
    ce = (lse - gold) * mask
    n = jnp.maximum(mask.sum(), 1)
    return ce.sum() / n, n


# Positions per CE chunk: bounds live f32 logits to (B, CE_CHUNK, V).
CE_CHUNK = 512


def chunked_ce_loss(hidden: jax.Array, targets: jax.Array,
                    head_fn) -> Tuple[jax.Array, jax.Array]:
    """Memory-bounded LM loss: the (B,S,V) logits tensor is never alive at
    once — per-chunk logits+CE under ``jax.checkpoint`` (bwd recomputes the
    chunk's logits instead of keeping them)."""
    B, S, _ = hidden.shape
    ck = min(CE_CHUNK, S)

    def chunk_loss(h_c, t_c):
        logits = head_fn(h_c)
        mask = (t_c >= 0)
        t = jnp.maximum(t_c, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return ((lse - gold) * mask).sum(), mask.sum()

    chunk_loss = jax.checkpoint(chunk_loss)
    tot, n = jnp.float32(0.0), jnp.int32(0)
    for i in range(0, S, ck):
        li, ni = chunk_loss(jax.lax.dynamic_slice_in_dim(hidden, i, min(ck, S - i), 1),
                            jax.lax.dynamic_slice_in_dim(targets, i, min(ck, S - i), 1))
        tot, n = tot + li, n + ni
    n = jnp.maximum(n, 1)
    return tot / n, n


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def _mk_ctx(cfg, mesh_cfg, mode, mesh, par, attn_impl=None):
    return Ctx(cfg=cfg, mesh_cfg=mesh_cfg, mode=mode, mesh=mesh, par=par,
               attn_impl=attn_impl or par.attn_impl)


def make_loss_fn(cfg: ModelConfig, mesh_cfg: MeshConfig,
                 par: ParallelismConfig, mesh: Optional[Mesh]):
    if cfg.family in ("lstm", "conv1d"):
        if cfg.family == "lstm":
            from repro.model.lstm import lstm_apply as apply_fn
        else:
            from repro.model.conv1d import conv1d_apply as apply_fn

        def window_loss(params, batch):
            pred, _ = apply_fn(params, batch["x"], cfg)
            loss = jnp.mean(jnp.square(pred - batch["y"]))
            return loss, {"loss": loss}

        return window_loss

    def loss_fn(params, batch):
        ctx = _mk_ctx(cfg, mesh_cfg, "train", mesh, par)
        hidden, _, aux = apply_model(params, batch, ctx, return_hidden=True)
        from repro.model.transformer import head_logits

        if cfg.ce_chunked:
            ce, n_tok = chunked_ce_loss(hidden, batch["targets"],
                                        lambda h: head_logits(params, h, ctx))
        else:
            ce, n_tok = cross_entropy(head_logits(params, hidden, ctx),
                                      batch["targets"])
        loss = ce + aux
        return loss, {"loss": ce, "aux": aux, "n_tok": n_tok}

    return loss_fn


def make_train_step(cfg: ModelConfig, mesh_cfg: MeshConfig,
                    par: ParallelismConfig, opt_cfg: AdamWConfig,
                    mesh: Optional[Mesh] = None):
    """(params, opt_state, batch) -> (params', opt_state', metrics)."""
    loss_fn = make_loss_fn(cfg, mesh_cfg, par, mesh)

    if par.grad_compression and mesh is not None and mesh.size > 1:
        # int8-ring gradient reduction: manual over DP, auto over model
        from repro.optim.compress import make_compressed_grad_fn

        def step_c(params, opt_state, batch):
            bspec = {k: P(mesh_cfg.dp_axes, *([None] * (v.ndim - 1)))
                     for k, v in batch.items()}
            grad_fn = make_compressed_grad_fn(loss_fn, mesh, mesh_cfg, bspec)
            loss, metrics, grads = grad_fn(params, batch)
            new_params, new_opt, info = adamw_update(grads, opt_state,
                                                     params, opt_cfg)
            return new_params, new_opt, dict(metrics, **info)

        return step_c

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, info = adamw_update(grads, opt_state, params,
                                                 opt_cfg)
        metrics = dict(metrics, **info)
        return new_params, new_opt, metrics

    return step


def make_prefill_step(cfg: ModelConfig, mesh_cfg: MeshConfig,
                      par: ParallelismConfig, mesh: Optional[Mesh] = None):
    """(params, batch) -> (last_logits (B,V), cache).

    For the window families (lstm/conv1d) "prefill" is one window
    inference: (params, batch) -> (pred (B, out_features), state) — the
    deployable step the XLA target translates for ``infer_1`` shapes,
    mirroring what the RTL target lowers.
    """
    if cfg.family in ("lstm", "conv1d"):
        if cfg.family == "lstm":
            from repro.model.lstm import lstm_apply as apply_fn
        else:
            from repro.model.conv1d import conv1d_apply as apply_fn

        def window_step(params, batch):
            return apply_fn(params, batch["x"], cfg)

        return window_step

    def step(params, batch):
        ctx = _mk_ctx(cfg, mesh_cfg, "prefill", mesh, par)
        logits, cache, _ = apply_model(params, batch, ctx)
        return logits[:, -1], cache

    return step


def make_decode_step(cfg: ModelConfig, mesh_cfg: MeshConfig,
                     par: ParallelismConfig, mesh: Optional[Mesh] = None):
    """(params, tokens (B,1), cache) -> (logits (B,V), cache')."""

    def step(params, tokens, cache):
        ctx = _mk_ctx(cfg, mesh_cfg, "decode", mesh, par)
        logits, new_cache, _ = apply_model(params, {"tokens": tokens}, ctx,
                                           cache=cache)
        return logits[:, -1], new_cache

    return step


# ---------------------------------------------------------------------------
# Abstract input specs (dry-run stand-ins; no allocation)
# ---------------------------------------------------------------------------


def _batch_axis(mesh_cfg: MeshConfig, batch: int) -> Optional[Tuple[str, ...]]:
    dp = mesh_cfg.dp_axes
    n = 1
    for a in dp:
        n *= mesh_cfg.axis_size(a)
    return dp if (n > 1 and batch % n == 0) else None


def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig,
                 mesh_cfg: MeshConfig) -> Dict[str, P]:
    ba = _batch_axis(mesh_cfg, shape.global_batch)
    if cfg.family in ("lstm", "conv1d"):
        return {"x": P(ba, None, None), "y": P(ba, None)}
    specs: Dict[str, P] = {"tokens": P(ba, None)}
    if shape.kind == "train":
        specs["targets"] = P(ba, None)
    if shape.kind in ("train", "prefill"):
        if cfg.frontend == "vision":
            specs["patches"] = P(ba, None, None)
        if cfg.frontend == "audio":
            specs["frames"] = P(ba, None, None)
    return specs


def input_specs(cfg: ModelConfig,
                shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "lstm":
        c = cfg.lstm
        return {"x": jax.ShapeDtypeStruct((B, c.seq_len, c.in_features),
                                          jnp.float32),
                "y": jax.ShapeDtypeStruct((B, c.out_features), jnp.float32)}
    if cfg.family == "conv1d":
        c = cfg.conv1d
        return {"x": jax.ShapeDtypeStruct((B, c.seq_len, c.channels),
                                          jnp.float32),
                "y": jax.ShapeDtypeStruct((B, c.out_features), jnp.float32)}
    sds: Dict[str, jax.ShapeDtypeStruct] = {}
    tok_s = shape.step_tokens if shape.kind == "decode" else S
    sds["tokens"] = jax.ShapeDtypeStruct((B, tok_s), jnp.int32)
    if shape.kind == "train":
        sds["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if shape.kind in ("train", "prefill"):
        if cfg.frontend == "vision":
            sds["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
        if cfg.frontend == "audio":
            sds["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder.n_positions, cfg.frontend_dim), jnp.float32)
    return sds


# ---------------------------------------------------------------------------
# Stepper — bundles schemas, shardings and jitted callables for one cell
# ---------------------------------------------------------------------------


@dataclass
class Stepper:
    """Everything needed to lower/run one (arch × shape × mesh) cell."""

    cfg: ModelConfig
    shape: ShapeConfig
    mesh_cfg: MeshConfig
    par: ParallelismConfig
    mesh: Optional[Mesh] = None
    opt_cfg: AdamWConfig = AdamWConfig()

    def __post_init__(self):
        tp = self.mesh_cfg.axis_size("model")
        self.schema = param_schema(self.cfg, tp=tp)
        self.param_pspecs = pspecs(self.schema)

    # --- abstract (dry-run) -------------------------------------------------
    def abstract_inputs(self):
        sds = input_specs(self.cfg, self.shape)
        params = abstract_params(self.schema,
                                 dtype_override=jnp.dtype(self.par.param_dtype))
        if self.shape.kind == "train":
            opt = tree_map_pspec(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                opt_state_schema(self.schema, self.mesh_cfg))
            return {"params": params, "opt_state": opt, "batch": sds}
        out = {"params": params, "batch": sds}
        if self.shape.kind == "decode":
            # activation entries (bf16 in the schema: KV, conv windows) are
            # kept at the compute dtype, as prefill and decode write them
            cdt = jnp.dtype(self.par.compute_dtype)
            out["cache"] = tree_map_pspec(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, cdt if s.dtype == jnp.bfloat16 else s.dtype),
                self.cache_schema())
        return out

    def cache_schema(self):
        tp = self.mesh_cfg.axis_size("model")
        return model_cache_schema(self.cfg, self.shape.global_batch,
                                  self.shape.seq_len, self.mesh_cfg, tp=tp,
                                  stacked=self.par.scan_layers,
                                  seq_shard=self.par.seq_shard_decode)

    def shardings(self, tree_schema):
        assert self.mesh is not None
        return tree_map_pspec(
            lambda s: NamedSharding(self.mesh, s.pspec), tree_schema)

    # --- step functions -----------------------------------------------------
    def train_fn(self):
        return make_train_step(self.cfg, self.mesh_cfg, self.par,
                               self.opt_cfg, self.mesh)

    def prefill_fn(self):
        return make_prefill_step(self.cfg, self.mesh_cfg, self.par, self.mesh)

    def decode_fn(self):
        return make_decode_step(self.cfg, self.mesh_cfg, self.par, self.mesh)

    # --- concrete init (smoke scale only) ------------------------------------
    def init(self, seed: int = 0):
        params = init_params(self.schema, jax.random.PRNGKey(seed))
        opt = init_opt_state(params)
        return params, opt
