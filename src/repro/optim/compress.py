"""int8 ring all-reduce for gradients — the cross-pod wire-byte reducer.

A ring reduce-scatter + all-gather with int8 payloads (per-block f32 scales
sent alongside, re-quantized each hop): per-device wire bytes ≈ 2·size·1 B
vs ≈ 8·size for the f32 ring all-reduce XLA inserts — a 4× reduction on the
gradient collective, applied hierarchically (f32 over the fast intra-pod
"data" axis if desired, int8 over the slow "pod" axis).

Used inside a *partially-manual* ``jax.shard_map`` (manual over the DP axes,
auto over "model"), so the model-parallel sharding of the gradients is
untouched. Error feedback is available (``ef`` argument) for step-over-step
bias correction; the trainer integration keeps it optional because the
residual costs one params-sized f32 buffer.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.shardmap import axis_size, shard_map


def _quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-20) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.reshape(1)


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_vec(x: jax.Array, axis: str) -> jax.Array:
    """int8 ring all-reduce of a flat f32 vector inside a manual region."""
    n = axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    m = -(-x.size // n)
    xp = jnp.pad(x.reshape(-1), (0, n * m - x.size)).reshape(n, m)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # ---- ring reduce-scatter (int8 wire, requantized partial sums) -------
    cur = jnp.take(xp, idx, axis=0)                    # partial of block idx
    for s in range(n - 1):
        q, sc = _quant(cur)
        q = jax.lax.ppermute(q, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        rb = (idx - s - 1) % n
        cur = _dequant(q, sc) + jnp.take(xp, rb, axis=0)
    own = (idx + 1) % n                                # block this rank owns

    # ---- ring all-gather of the reduced blocks (int8 wire) ---------------
    out = jnp.zeros((n, m), jnp.float32)
    q, sc = _quant(cur)
    out = jax.lax.dynamic_update_slice_in_dim(out, _dequant(q, sc)[None],
                                              own, axis=0)
    for s in range(n - 1):
        q = jax.lax.ppermute(q, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        blk = (own - s - 1) % n
        out = jax.lax.dynamic_update_slice_in_dim(out, _dequant(q, sc)[None],
                                                  blk, axis=0)
    return out.reshape(-1)[: x.size].reshape(x.shape)


def compressed_psum_tree(tree: Any, axis: str,
                         ef: Optional[Any] = None) -> Tuple[Any, Any]:
    """Flatten a grad pytree into one vector, ring-reduce it, unflatten.

    Returns (summed_tree, new_ef). With ``ef`` the local quantization error
    of the *input* quantization is fed back next step (error feedback).
    """
    leaves, tdef = jax.tree.flatten(tree)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1)
                            for l in leaves])
    if ef is not None:
        flat = flat + ef
    summed = compressed_psum_vec(flat, axis)
    new_ef = None
    if ef is not None:
        # residual = what this device failed to contribute exactly
        q, sc = _quant(flat)
        new_ef = flat - _dequant(q, sc)
    outs = []
    off = 0
    for sz, shp in zip(sizes, shapes):
        outs.append(summed[off: off + sz].reshape(shp))
        off += sz
    return jax.tree.unflatten(tdef, outs), new_ef


def compressed_psum_butterfly(x: jax.Array, axis: str) -> jax.Array:
    """Recursive-doubling (butterfly) all-reduce with int8 payloads.

    Unlike the flat ring, this never reshapes the operand, so gradients that
    are TP-sharded along "model" keep their sharding (the ppermute runs over
    the DP axis only) — no model-axis all-gathers are induced. Wire bytes:
    log2(n)·size·1 B vs ~8·size for the f32 ring (≈2× for n=16, and the
    payload dtype drops 4× on the slow axis).
    """
    n = axis_size(axis)
    if n == 1:
        return x
    acc = x.astype(jnp.float32)
    r = 1
    while r < n:
        perm = [(i, i ^ r) for i in range(n)]
        q, sc = _quant(acc)
        q = jax.lax.ppermute(q, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        acc = acc + _dequant(q, sc)
        r <<= 1
    return acc


def compressed_psum_tree_butterfly(tree: Any, axis: str) -> Any:
    return jax.tree.map(lambda g: compressed_psum_butterfly(g, axis), tree)


def make_compressed_grad_fn(loss_fn, mesh, mesh_cfg, batch_pspec_tree):
    """Wrap value_and_grad in a partially-manual shard_map:
    manual over the DP axes (batch split, compressed grad reduction),
    auto over "model" (TP sharding untouched)."""
    dp_axes = tuple(mesh_cfg.dp_axes)

    def local_step(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        # hierarchical reduction: f32 psum over fast intra-pod axis, int8
        # butterfly over the slowest (outermost) axis. Butterfly (not ring):
        # it preserves each leaf's TP sharding — the flat ring was measured
        # to induce model-axis all-gathers (EXPERIMENTS.md §Perf cell C).
        if len(dp_axes) > 1:
            grads = jax.tree.map(lambda g: jax.lax.psum(g, dp_axes[1:]),
                                 grads)
        grads = compressed_psum_tree_butterfly(grads, dp_axes[0])
        grads = jax.tree.map(
            lambda g: g / axis_size(dp_axes[0]), grads)
        if len(dp_axes) > 1:
            grads = jax.tree.map(
                lambda g: g / axis_size(dp_axes[1:][0]), grads)
        loss = jax.lax.pmean(loss, dp_axes)
        metrics = jax.tree.map(lambda v: jax.lax.pmean(v, dp_axes), metrics)
        return loss, metrics, grads

    in_specs = (P(), batch_pspec_tree)
    out_specs = (P(), P(), P())
    # check_vma=False: the ring all-reduce produces identical values on all
    # devices, but value-based replication can't be inferred through ppermute
    return shard_map(local_step, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(dp_axes),
                         check_vma=False)
