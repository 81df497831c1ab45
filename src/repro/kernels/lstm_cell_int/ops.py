"""Public wrapper for the fused integer LSTM-window template."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.lstm_cell_int.kernel import (CellSpec,
                                                lstm_window_int_pallas)


@partial(jax.jit, static_argnames=("spec", "block_b", "interpret"))
def lstm_window_int(x: jax.Array, w: jax.Array, b: jax.Array,
                    sig_table: jax.Array, tanh_table: jax.Array,
                    *, spec: CellSpec, interpret: bool,
                    block_b: int = 128) -> jax.Array:
    """(B,S,d_in) int codes × fused int gate weights -> (B, S, hidden) int32.

    One template dispatch per window: pads the batch to the block size, runs
    the fused kernel (weights + biases VMEM-resident, both ROMs in SMEM),
    slices the padding back off. Padded rows compute on zero inputs and are
    discarded — rows are independent, so real rows are bit-identical to the
    unpadded run. ``interpret`` is the caller's
    :func:`repro.kernels.use_interpret` — a static argument, so a program
    traced for the chip is never replayed in interpret mode or vice versa.
    """
    B = x.shape[0]
    bb = min(block_b, B)
    pad = (-B) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    out = lstm_window_int_pallas(x, w, b, sig_table, tanh_table, spec=spec,
                                 block_b=bb, interpret=interpret)
    return out[:B]
