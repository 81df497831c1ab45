"""Mamba2 (SSD) block — chunked state-space dual form, TPU-native.

The SSD algorithm is reformulated so that everything quadratic-in-chunk is a
batched einsum (MXU-friendly) and only the O(n_chunks) state carry is a
``lax.scan`` / segsum matmul.  This is the hardware adaptation of the paper's
"RTL template" idea for the SSM family: the chunk-local part has a Pallas
template (kernels/mamba2) and this file is the exact jnp reference the
template is validated against.

Layout notes (TP over the "model" axis):
- z/x/dt projections are column-sharded over d_inner / heads,
- B/C projections are per-group (``n_groups`` groups of N; head h reads
  group h // (H / n_groups)) and replicated,
- out_proj is row-sharded; XLA inserts the single block all-reduce.
The depthwise conv over [x, B, C] has a bias; the gated RMSNorm is taken
per group (d_inner / n_groups wide).
State cache (decode): {"ssm": (B,H,P,N) f32, "conv_x/B/C": rolling windows}.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import ModelConfig
from repro.model.layers import Ctx, PSpec, shard_axis

# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def mamba_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.headdim
    return d_inner, n_heads, s.headdim, s.d_state


def mamba_schema(cfg: ModelConfig, tp: int = 16):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, H, Pd, N = mamba_dims(cfg)
    gN = s.n_groups * N
    ia = shard_axis(d_inner, tp)
    ha = shard_axis(H, tp)
    w = s.conv_width
    return {
        "w_z": PSpec((d, d_inner), P(None, ia)),
        "w_x": PSpec((d, d_inner), P(None, ia)),
        "w_B": PSpec((d, gN), P(None, None)),
        "w_C": PSpec((d, gN), P(None, None)),
        "w_dt": PSpec((d, H), P(None, ha)),
        "conv_x": PSpec((w, d_inner), P(None, ia), scale=0.5),
        "conv_B": PSpec((w, gN), P(None, None), scale=0.5),
        "conv_C": PSpec((w, gN), P(None, None), scale=0.5),
        "conv_x_bias": PSpec((d_inner,), P(ia), init="zeros"),
        "conv_B_bias": PSpec((gN,), P(None), init="zeros"),
        "conv_C_bias": PSpec((gN,), P(None), init="zeros"),
        "A_log": PSpec((H,), P(ha), init="zeros"),       # A = -exp(A_log) = -1
        "dt_bias": PSpec((H,), P(ha), init="zeros"),
        "D": PSpec((H,), P(ha), init="ones"),
        "norm_scale": PSpec((d_inner,), P(ia), init="ones"),
        "w_out": PSpec((d_inner, d), P(ia, None)),
    }


def mamba_state_schema(cfg: ModelConfig, batch: int, dp_axes, tp: int = 16):
    s = cfg.ssm
    d_inner, H, Pd, N = mamba_dims(cfg)
    gN = s.n_groups * N
    ha = shard_axis(H, tp)
    ia = shard_axis(d_inner, tp)
    # batch-replicated states are tiny for B=1 (long_500k); shard otherwise
    bspec = dp_axes if batch >= 16 else None
    w = s.conv_width
    return {
        "ssm": PSpec((batch, H, Pd, N), P(bspec, ha, None, None),
                     dtype=jnp.float32, init="zeros"),
        "conv_x": PSpec((batch, w - 1, d_inner), P(bspec, None, ia),
                        dtype=jnp.bfloat16, init="zeros"),
        "conv_B": PSpec((batch, w - 1, gN), P(bspec, None, None),
                        dtype=jnp.bfloat16, init="zeros"),
        "conv_C": PSpec((batch, w - 1, gN), P(bspec, None, None),
                        dtype=jnp.bfloat16, init="zeros"),
    }


# ---------------------------------------------------------------------------
# Depthwise causal conv (width 4) — train/prefill (full seq) and decode (step)
# ---------------------------------------------------------------------------


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 prev: Optional[jax.Array] = None):
    """x: (B, S, C), w: (W, C) depthwise, b: (C,); ``prev`` (B, W-1, C) the
    inputs before x (zeros when None). Causal: y_t = silu(b + sum_k w[k]
    x_{t-W+1+k}). Returns (y, the last W-1 inputs: the next call's prev)."""
    W = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    pad = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    y = jnp.broadcast_to(b.astype(x.dtype), x.shape)
    for k in range(W):
        y = y + pad[:, k : k + x.shape[1], :] * w[k][None, None, :]
    return jax.nn.silu(y), pad[:, x.shape[1]:, :]


def _conv_step(x_t: jax.Array, prev: jax.Array, w: jax.Array, b: jax.Array):
    """x_t: (B, C); prev: (B, W-1, C) rolling window. Returns (y_t, new_prev)."""
    window = jnp.concatenate([prev, x_t[:, None, :]], axis=1)  # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(x_t.dtype), window[:, 1:, :]


# ---------------------------------------------------------------------------
# SSD chunked scan (the matmul-form state-space dual)
# ---------------------------------------------------------------------------


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., L) log-decays -> (..., L, L) lower-tri pairwise sums."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(
    x: jax.Array,        # (B, S, H, P)   pre-multiplied by nothing (raw)
    dt: jax.Array,       # (B, S, H)      post-softplus, f32
    A: jax.Array,        # (H,)           negative, f32
    Bm: jax.Array,       # (B, S, G, N)
    Cm: jax.Array,       # (B, S, G, N)
    chunk: int,
    h0: Optional[jax.Array] = None,      # (B, H, P, N) initial state
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N)).

    Steps 1/2/4 are chunk-parallel einsums (counted exactly by
    ``cost_analysis``); only step 3 (inter-chunk state carry, O(nc·N·P))
    is sequential via a small segsum matmul over the chunk axis.
    """
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    S0 = S
    if S % chunk:  # pad tail: dt=0 -> decay exp(0)=1, contribution dt*x=0
        extra = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, extra), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, extra), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, extra), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, extra), (0, 0), (0, 0)))
        S = S + extra
    nc = S // chunk
    rep = H // G

    cdt = x.dtype           # caller's compute dtype (bf16 on TPU, f32 on CPU)

    def to_chunks(t):
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:])

    xc = to_chunks(x).astype(cdt)                        # (B,c,l,H,P)
    dtc = to_chunks(dt.astype(jnp.float32))              # (B,c,l,H)
    Bc = to_chunks(Bm).astype(cdt)                       # (B,c,l,G,N)
    Cc = to_chunks(Cm).astype(cdt)                       # (B,c,l,G,N)
    # broadcast groups -> heads
    Bh = jnp.repeat(Bc, rep, axis=3)                     # (B,c,l,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)

    a = dtc * A.astype(jnp.float32)[None, None, None, :]  # (B,c,l,H) log-decay
    a_t = jnp.moveaxis(a, -1, 1)                          # (B,H,c,l)
    a_cs = jnp.cumsum(a_t, axis=-1)                       # inclusive

    xdt = xc * dtc.astype(cdt)[..., None]                 # dt·x  (B,c,l,H,P)

    # 1. intra-chunk (diagonal blocks): Y_diag[i] = sum_{j<=i} C_i·B_j L_ij xdt_j
    Lmat = jnp.exp(_segsum(a_t.reshape(Bsz, H, nc, chunk))).astype(cdt)
    scores = jnp.einsum("bclhn,bcshn->bhcls", Ch, Bh,
                        preferred_element_type=jnp.float32)
    scores = (scores * Lmat.astype(jnp.float32)).astype(cdt)
    y_diag = jnp.einsum("bhcls,bcshp->bclhp", scores, xdt,
                        preferred_element_type=jnp.float32)

    # 2. chunk-final states: state_c = sum_j exp(a_end - a_j) B_j xdt_j
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs).astype(cdt)   # (B,H,c,l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, xdt,
                        preferred_element_type=jnp.float32)     # (B,c,H,P,N)

    # 3. inter-chunk recurrence over the (small) chunk axis
    chunk_decay = a_cs[..., -1]                                  # (B,H,c)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, Pd, N), jnp.float32)
    states = jnp.concatenate([h0[:, None].astype(jnp.float32),
                              states.astype(jnp.float32)], axis=1)
    pad_decay = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))   # (B,H,c+1)
    dmat = jnp.exp(_segsum(pad_decay))                           # (B,H,c+1,c+1)
    dmat = jnp.where(jnp.isfinite(dmat), dmat, 0.0)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", dmat, states,
                            preferred_element_type=jnp.float32)
    h_prev, h_final = new_states[:, :-1], new_states[:, -1]      # (B,c,H,P,N)

    # 4. state -> output for each position (decay from chunk start)
    out_decay = jnp.exp(a_cs).astype(cdt)                        # (B,H,c,l)
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch,
                       h_prev.astype(cdt), out_decay,
                       preferred_element_type=jnp.float32)

    y = (y_diag + y_off).reshape(Bsz, S, H, Pd)
    return y[:, :S0], h_final


def ssd_step(
    x: jax.Array,        # (B, H, P)
    dt: jax.Array,       # (B, H) f32 post-softplus
    A: jax.Array,        # (H,)
    Bm: jax.Array,       # (B, G, N)
    Cm: jax.Array,       # (B, G, N)
    h: jax.Array,        # (B, H, P, N) f32
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the recurrence. Returns (y (B,H,P), h')."""
    G = Bm.shape[1]
    rep = x.shape[1] // G
    Bh = jnp.repeat(Bm, rep, axis=1).astype(jnp.float32)   # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(jnp.float32)
    da = jnp.exp(dt * A[None, :])                           # (B,H)
    xf = x.astype(jnp.float32)
    h_new = h * da[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", xf * dt[..., None], Bh
    )
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Ch)
    return y.astype(x.dtype), h_new


# ---------------------------------------------------------------------------
# Full block apply
# ---------------------------------------------------------------------------


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   groups: int = 1, eps: float = 1e-5) -> jax.Array:
    """RMSNorm of y·silu(z), taken over each of ``groups`` equal slices of
    the last axis."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    yg = yf.reshape(*yf.shape[:-1], groups, yf.shape[-1] // groups)
    ms = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
    yn = (yg * jax.lax.rsqrt(ms + eps)).reshape(yf.shape)
    return (yn * scale.astype(jnp.float32)).astype(y.dtype)


def mamba_apply(
    p,
    hx: jax.Array,                       # (B, S, D) normed input
    ctx: Ctx,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    cfg = ctx.cfg
    s = cfg.ssm
    dt_ = ctx.compute_dtype
    d_inner, H, Pd, N = mamba_dims(cfg)
    gN = s.n_groups * N
    B, S, _ = hx.shape
    hc = hx.astype(dt_)

    z = hc @ p["w_z"].astype(dt_)                        # (B,S,d_inner)
    x = hc @ p["w_x"].astype(dt_)
    Bm = hc @ p["w_B"].astype(dt_)                       # (B,S,gN)
    Cm = hc @ p["w_C"].astype(dt_)
    dt_raw = hc @ p["w_dt"].astype(dt_)                  # (B,S,H)
    dt_f = jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    # the state-space part (conv + SSD), named for device-time attribution
    with jax.named_scope("mamba2.ssd"):
        new_state = None
        if ctx.mode == "decode" and S == 1:
            assert state is not None
            xs, cx = _conv_step(x[:, 0], state["conv_x"].astype(dt_), p["conv_x"],
                                p["conv_x_bias"])
            Bs, cB = _conv_step(Bm[:, 0], state["conv_B"].astype(dt_), p["conv_B"],
                                p["conv_B_bias"])
            Cs, cC = _conv_step(Cm[:, 0], state["conv_C"].astype(dt_), p["conv_C"],
                                p["conv_C_bias"])
            y, h_new = ssd_step(
                xs.reshape(B, H, Pd), dt_f[:, 0], A,
                Bs.reshape(B, s.n_groups, N), Cs.reshape(B, s.n_groups, N),
                state["ssm"],
            )
            y = y + p["D"].astype(jnp.float32)[None, :, None] * xs.reshape(B, H, Pd)
            y = y.reshape(B, 1, d_inner).astype(dt_)
            new_state = {"ssm": h_new, "conv_x": cx.astype(x.dtype),
                         "conv_B": cB.astype(x.dtype),
                         "conv_C": cC.astype(x.dtype)}
        else:
            # train / prefill from zeros; a decode step of S > 1 tokens
            # continues from the cached conv windows and SSM state
            prev = state if ctx.mode == "decode" else None
            conv = {n: _causal_conv(t, p[f"conv_{n}"].astype(dt_),
                                    p[f"conv_{n}_bias"],
                                    prev[f"conv_{n}"] if prev else None)
                    for n, t in (("x", x), ("B", Bm), ("C", Cm))}
            xc, Bc, Cc = (conv[n][0] for n in ("x", "B", "C"))
            h0 = state["ssm"] if state is not None else None
            y4, h_final = ssd_chunked(
                xc.reshape(B, S, H, Pd), dt_f, A,
                Bc.reshape(B, S, s.n_groups, N), Cc.reshape(B, S, s.n_groups, N),
                chunk=min(s.chunk, S), h0=h0,
            )
            y4 = y4 + (p["D"].astype(jnp.float32)[None, None, :, None]
                       * xc.reshape(B, S, H, Pd).astype(jnp.float32)).astype(y4.dtype)
            y = y4.reshape(B, S, d_inner).astype(dt_)
            if ctx.mode in ("prefill", "decode"):
                new_state = {"ssm": h_final,
                             **{f"conv_{n}": conv[n][1].astype(x.dtype)
                                for n in ("x", "B", "C")}}

    yn = _gated_rmsnorm(y, z, p["norm_scale"], groups=s.n_groups)
    out = (yn @ p["w_out"].astype(dt_)).astype(hx.dtype)
    return out, new_state


# ---------------------------------------------------------------------------
# Pure-recurrence oracle (smoke-scale ground truth for ssd_chunked)
# ---------------------------------------------------------------------------


def ssd_reference(x, dt, A, Bm, Cm, h0=None):
    """Naive per-step recurrence. x:(B,S,H,P) dt:(B,S,H) B/C:(B,S,G,N)."""
    Bsz, S, H, Pd = x.shape
    N = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, Pd, N), jnp.float32)

    def step(h, t):
        y, h_new = ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        return h_new, y

    h_final, ys = jax.lax.scan(step, h0, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1), h_final
