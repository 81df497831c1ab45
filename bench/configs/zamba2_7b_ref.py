"""Plain reference of the Zamba2 forward pass (Zyphra/Zamba2-7B-Instruct),
and its seeded weights, for the configuration
``bench/configs/zamba2_7b.json``; it imports nothing of the program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one session's ids in, logits at
every position out; no cache, no kernel, no batching; the SSM is the
sequential recurrence. The equations are those of ``transformers``'
``models/zamba2/modeling_zamba2.py`` (4.57.6). With d the hidden size,
e the embedding of the ids and h starting at e, layer l is::

    if l is the k-th entry of hybrid_layer_ids:        # block b = k mod M
        u = RMSNorm_in[b](concat(h, e))                    # width 2d
        a = softmax_causal(RoPE(u Wq[b]) RoPE(u Wk[b])^T (hd/2)^-1/2)
            (u Wv[b]) Wo[b]                                # 2d -> d
        g = RMSNorm_ff[b](a)                               # no residual
        p = g Wgu[b] + (g A[k]) B[k]                       # adapter k
        x = h + ((gelu(p[:f]) * p[f:]) Wdown[b]) Wlin[k]
    else:
        x = h
    h = h + Mamba2[l](RMSNorm[l](x))                       # residual from h
    logits = RMSNorm_f(h) E^T                              # tied head

and Mamba2: in_proj -> [z | x | B (G groups) | C | dt]; causal depthwise
conv (with bias) over [x, B, C], then SiLU; dt = softplus(dt + dt_bias);
h_t = exp(dt A) h_{t-1} + dt x_t B_{g(head)}; y = C_{g(head)} h_t + D x;
RMSNorm of y * silu(z) per group; out_proj.

Departures from the published description, each deliberate:

* dt is not clamped: the published (CUDA) path's ``time_step_limit`` is
  (0, inf); only ``transformers``' pure-torch fallback clamps dt at
  ``time_step_min``.
* the head is tied to the embedding (``tie_word_embeddings``, the
  ``transformers`` default; the published config does not set it);
* weights are held at whatever dtype they come in (the program's bf16)
  and computed in float32; ``weight_dtype`` rounds them first (the control).

Parameters use the published layout (``in_proj`` and ``gate_up_proj``
fused), as ``x @ W`` (in, out), stacked per kind: ``mamba`` over layers,
``blocks`` over the ``num_mem_blocks`` shared blocks, ``adapters`` and
``linear`` over the hybrid calls. :func:`to_program` maps them onto the
program's parameter tree (``repro.model.transformer``; its shared blocks,
adapters and linears are tuples).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Dims(NamedTuple):
    """The sizes the forward pass reads, from a published-style config."""

    d: int
    heads: int
    hd: int
    ff: int
    d_inner: int
    m_heads: int
    m_hd: int
    n: int
    groups: int
    conv: int
    vocab: int
    rank: int
    blocks: int
    ids: Tuple[int, ...]
    layers: int
    eps: float
    theta: float


def dims(config: dict) -> Dims:
    d = int(config["hidden_size"])
    d_inner = int(config["mamba_expand"]) * d
    return Dims(
        d=d, heads=int(config["num_attention_heads"]),
        hd=int(config["attention_head_dim"]),
        ff=int(config["intermediate_size"]), d_inner=d_inner,
        m_heads=int(config["n_mamba_heads"]), m_hd=int(config["mamba_headdim"]),
        n=int(config["mamba_d_state"]), groups=int(config["mamba_ngroups"]),
        conv=int(config["mamba_d_conv"]), vocab=int(config["vocab_size"]),
        rank=int(config["adapter_rank"]), blocks=int(config["num_mem_blocks"]),
        ids=tuple(int(i) for i in config["hybrid_layer_ids"]),
        layers=int(config["num_hidden_layers"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]))


def make_params(config: dict, seed: int, dtype=None) -> dict:
    """Seeded weights in the published layout, drawn on the default device.

    The recipe of the published config: every matrix and the embedding
    normal(0, ``initializer_range`` 0.02); the depthwise conv's weights and
    bias uniform(±1/sqrt(d_conv)) (PyTorch's ``Conv1d`` default);
    A_log = log(1..n_mamba_heads); dt_bias = softplus^-1(dt), dt
    log-uniform in [time_step_min, time_step_max] floored at
    time_step_floor; D and every norm weight 1. ``dtype`` (default the
    config's ``weights.dtype``, else bfloat16) is the stored dtype.
    """
    dm = dims(config)
    dtype = jnp.dtype(dtype or config.get("weights", {}).get("dtype",
                                                             "bfloat16"))
    std = float(config.get("initializer_range", 0.02))
    state = np.random.SeedSequence([int(seed), 1]).generate_state(1)
    keys = iter(jax.random.split(jax.random.PRNGKey(int(state[0])), 64))
    L, K, M = dm.layers, len(dm.ids), dm.blocks
    di, H, gn = dm.d_inner, dm.m_heads, dm.groups * dm.n
    conv_dim = di + 2 * gn

    def normal(*shape):
        return jax.jit(lambda k: std * jax.random.normal(k, shape, dtype))(
            next(keys))

    def uniform(bound, *shape):
        return jax.jit(lambda k: jax.random.uniform(
            k, shape, dtype, -bound, bound))(next(keys))

    def ones(*shape):
        return jnp.ones(shape, dtype)

    tmin = float(config.get("time_step_min", 1e-3))
    tmax = float(config.get("time_step_max", 0.1))
    floor = float(config.get("time_step_floor", 1e-4))
    dt = jnp.exp(jax.random.uniform(next(keys), (L, H))
                 * (np.log(tmax) - np.log(tmin)) + np.log(tmin))
    dt = jnp.maximum(dt, floor)
    bound = dm.conv ** -0.5
    return {
        "embed": normal(dm.vocab, dm.d),
        "mamba": {
            "norm": ones(L, dm.d),
            "in_proj": normal(L, dm.d, 2 * di + 2 * gn + H),
            "conv_w": uniform(bound, L, dm.conv, conv_dim),
            "conv_b": uniform(bound, L, conv_dim),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
                (L, H)).astype(dtype),
            "D": ones(L, H),
            "gate_norm": ones(L, di),
            "out_proj": normal(L, di, dm.d)},
        "blocks": {
            "input_norm": ones(M, 2 * dm.d),
            "q": normal(M, 2 * dm.d, dm.heads * dm.hd),
            "k": normal(M, 2 * dm.d, dm.heads * dm.hd),
            "v": normal(M, 2 * dm.d, dm.heads * dm.hd),
            "o": normal(M, dm.heads * dm.hd, dm.d),
            "pre_ff_norm": ones(M, dm.d),
            "gate_up": normal(M, dm.d, 2 * dm.ff),
            "down": normal(M, dm.ff, dm.d)},
        "adapters": {"a": normal(K, dm.d, dm.rank),
                     "b": normal(K, dm.rank, 2 * dm.ff)},
        "linear": normal(K, dm.d, dm.d),
        "final_norm": ones(dm.d),
    }


def _f32(a, wd):
    return (a if wd is None else a.astype(wd)).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, heads, hd); rotate_half convention, positions 0..T-1."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("dm", "wd"))
def _shared_call(blocks, adapters, linear, b, k, h, emb, *, dm: Dims, wd):
    """Hybrid call k with block b: the term added to the Mamba input."""
    p = {n: _f32(v[b], wd) for n, v in blocks.items()}
    T = h.shape[0]
    u = _rms(jnp.concatenate([h, emb], -1), p["input_norm"], dm.eps)
    q = _rope((u @ p["q"]).reshape(T, dm.heads, dm.hd), dm.theta)
    kk = _rope((u @ p["k"]).reshape(T, dm.heads, dm.hd), dm.theta)
    v = (u @ p["v"]).reshape(T, dm.heads, dm.hd)
    s = jnp.einsum("qhd,khd->hqk", q, kk) * (dm.hd / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    a = a.reshape(T, dm.heads * dm.hd) @ p["o"]
    g = _rms(a, p["pre_ff_norm"], dm.eps)
    gu = g @ p["gate_up"] + (g @ _f32(adapters["a"][k], wd)) @ _f32(
        adapters["b"][k], wd)
    t = (jax.nn.gelu(gu[:, :dm.ff], approximate=False) * gu[:, dm.ff:]) \
        @ p["down"]
    return t @ _f32(linear[k], wd)


@partial(jax.jit, static_argnames=("dm", "wd"))
def _mamba_layer(mamba, layer, h, mix, *, dm: Dims, wd):
    """h + Mamba2(RMSNorm(h + mix)) of one layer; the recurrence is a scan
    over positions."""
    p = {n: _f32(v[layer], wd) for n, v in mamba.items()}
    T = h.shape[0]
    di, H, P, N, G = dm.d_inner, dm.m_heads, dm.m_hd, dm.n, dm.groups
    u = _rms(h + mix, p["norm"], dm.eps)
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N],
                  zxbcdt[:, 2 * di + 2 * G * N:])
    pad = jnp.concatenate([jnp.zeros((dm.conv - 1, xbc.shape[1])), xbc], 0)
    conv = p["conv_b"] + sum(pad[j:j + T] * p["conv_w"][j]
                             for j in range(dm.conv))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(T, H, P)
    Bm = xbc[:, di:di + G * N].reshape(T, G, N)
    Cm = xbc[:, di + G * N:].reshape(T, G, N)
    group = jnp.arange(H) // (H // G)                 # head -> its group
    dt = jax.nn.softplus(dt + p["dt_bias"])           # (T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, t):
        Bh, Ch = Bm[t][group], Cm[t][group]           # (H, N)
        state = (jnp.exp(dt[t] * A)[:, None, None] * state
                 + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, Ch)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), jnp.arange(T))
    y = (y + p["D"][None, :, None] * x).reshape(T, di)
    yg = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + 1e-5)
    return h + (yg.reshape(T, di) * p["gate_norm"]) @ p["out_proj"]


@partial(jax.jit, static_argnames=("dm", "wd"))
def _head(embed, final_norm, h, *, dm: Dims, wd):
    return _rms(h, _f32(final_norm, wd), dm.eps) @ _f32(embed, wd).T


def forward(config: dict, params: dict, tokens, *, weight_dtype=None):
    """Logits (T, vocab) float32 at every position of one session's ids
    ``tokens`` (T,); ``weight_dtype`` rounds every weight to that dtype
    first (the control)."""
    dm = dims(config)
    wd = None if weight_dtype is None else jnp.dtype(weight_dtype)
    calls = {layer: k for k, layer in enumerate(dm.ids)}
    with jax.default_matmul_precision("highest"):
        e = _f32(params["embed"][jnp.asarray(tokens)], wd)
        h = e
        for layer in range(dm.layers):
            mix = jnp.zeros_like(h)
            if layer in calls:
                k = calls[layer]
                mix = _shared_call(params["blocks"], params["adapters"],
                                   params["linear"], k % dm.blocks, k, h, e,
                                   dm=dm, wd=wd)
            h = _mamba_layer(params["mamba"], layer, h, mix, dm=dm, wd=wd)
        return _head(params["embed"], params["final_norm"], h, dm=dm, wd=wd)


def to_program(params: dict, vocab_padded: int) -> dict:
    """The program's parameter tree (``repro.model.transformer``) holding
    the same weights: fused projections split, norms as ``{"scale": ..}``,
    the embedding's rows padded to ``vocab_padded``."""
    m, b, ad = params["mamba"], params["blocks"], params["adapters"]
    di = m["out_proj"].shape[1]
    gn = (m["in_proj"].shape[2] - 2 * di - m["A_log"].shape[1]) // 2
    ip, cw, cb = m["in_proj"], m["conv_w"], m["conv_b"]
    cuts = (0, di, 2 * di, 2 * di + gn, 2 * di + 2 * gn, ip.shape[2])
    w_z, w_x, w_B, w_C, w_dt = (ip[..., a:z] for a, z in zip(cuts, cuts[1:]))
    xc = (0, di, di + gn, di + 2 * gn)
    conv = [cw[..., a:z] for a, z in zip(xc, xc[1:])]
    bias = [cb[..., a:z] for a, z in zip(xc, xc[1:])]
    ff = b["down"].shape[1]
    embed = params["embed"]
    if vocab_padded > embed.shape[0]:
        embed = jnp.pad(embed, ((0, vocab_padded - embed.shape[0]), (0, 0)))
    split = lambda tree, n: tuple(jax.tree.map(lambda a: a[i], tree)
                                  for i in range(n))
    return {
        "embed": {"embedding": embed},
        "g0": {"norm1": {"scale": m["norm"]}, "mamba": {
            "w_z": w_z, "w_x": w_x, "w_B": w_B, "w_C": w_C, "w_dt": w_dt,
            "conv_x": conv[0], "conv_B": conv[1], "conv_C": conv[2],
            "conv_x_bias": bias[0], "conv_B_bias": bias[1],
            "conv_C_bias": bias[2], "A_log": m["A_log"],
            "dt_bias": m["dt_bias"], "D": m["D"],
            "norm_scale": m["gate_norm"], "w_out": m["out_proj"]}},
        "shared": {
            "blocks": split({
                "norm_in": {"scale": b["input_norm"]},
                "attn": {"wq": b["q"], "wk": b["k"], "wv": b["v"],
                         "wo": b["o"]},
                "norm_ff": {"scale": b["pre_ff_norm"]},
                "mlp": {"w_gate": b["gate_up"][..., :ff],
                        "w_up": b["gate_up"][..., ff:], "wo": b["down"]}},
                b["q"].shape[0]),
            "adapters": split({"a": ad["a"], "b_gate": ad["b"][..., :ff],
                               "b_up": ad["b"][..., ff:]}, ad["a"].shape[0]),
            "linear": split(params["linear"], params["linear"].shape[0])},
        "final_norm": {"scale": params["final_norm"]},
    }
