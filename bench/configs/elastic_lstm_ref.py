"""Plain reference of the elastic-lstm design, and its weights.

The fixed-point semantics written out once more in float64 NumPy, from
the design's own statement (gate-fused LSTM cell over the window, hard
sigmoid / hard tanh, Q-format requantisation after every MAC and
product, linear head), and nothing of the program imported. Every value
is a short dyadic fraction, so float64 holds it exactly, and
``numpy.round`` rounds half to even as the hardware's shift does.
"""
from __future__ import annotations

import numpy as np


def q(v, fmt):
    """Round half to even onto Q(total, frac), saturating."""
    total, frac = fmt
    s = 2.0 ** frac
    return np.clip(np.round(v * s), -(2 ** (total - 1)),
                   2 ** (total - 1) - 1) / s


def hard_sigmoid(v):
    return np.clip(0.2 * v + 0.5, 0.0, 1.0)


def hard_tanh(v):
    return np.clip(v, -1.0, 1.0)


def make_params(config: dict, rng: np.random.Generator) -> dict:
    """Seeded float32 weights in the layout the program takes."""
    c = config["lstm"]
    bias = config["weights"]["bias_scale"]
    h = c["hidden"]
    cells = []
    for i in range(c["n_layers"]):
        d_in = c["in_features"] if i == 0 else h
        rows = d_in + h
        cells.append({
            "w": (rng.standard_normal((rows, 4 * h)) / np.sqrt(rows))
            .astype(np.float32),
            "b": (rng.standard_normal(4 * h) * bias).astype(np.float32)})
    return {"cells": cells,
            "head_w": (rng.standard_normal((h, c["out_features"]))
                       / np.sqrt(h)).astype(np.float32),
            "head_b": (rng.standard_normal(c["out_features"]) * bias)
            .astype(np.float32)}


def forward(config: dict, params: dict, x, *, w_fmt=None) -> np.ndarray:
    """Output codes (at the state format) of the design on float windows
    ``x``; ``w_fmt`` overrides the weight format (the control)."""
    f = config["formats"]
    A, C = tuple(f["act_fmt"]), tuple(f["state_fmt"])
    W = tuple(w_fmt or f["w_fmt"])
    acc = (32, A[1] + W[1])                  # accumulator: bias scale
    seq = q(np.asarray(x, np.float64), A)
    for cell in params["cells"]:
        w = q(np.asarray(cell["w"], np.float64), W)
        b = q(np.asarray(cell["b"], np.float64), acc)
        hid = w.shape[-1] // 4
        batch = seq.shape[:-2]
        h = np.zeros(batch + (hid,))
        c = np.zeros(batch + (hid,))
        outs = []
        for t in range(seq.shape[-2]):
            xh = np.concatenate([seq[..., t, :], h], axis=-1)
            z = q(xh @ w + b, A)
            i, fg, g, o = (z[..., k * hid:(k + 1) * hid] for k in range(4))
            si, sf, so = (q(hard_sigmoid(v), A) for v in (i, fg, o))
            tg = q(hard_tanh(g), A)
            c = q(sf * c + si * tg, C)
            h = q(so * q(hard_tanh(q(c, A)), A), A)
            outs.append(h)
        seq = np.stack(outs, axis=-2)
    hw = q(np.asarray(params["head_w"], np.float64), W)
    hb = q(np.asarray(params["head_b"], np.float64), acc)
    y = q(seq[..., -1, :] @ hw + hb, C)
    return np.round(y * 2.0 ** C[1]).astype(np.int64)
