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
    slices the padding back off. Padded windows compute on zero inputs and
    are discarded — windows are independent, so real ones are bit-identical
    to the unpadded run.

    The kernel's layout follows the static batch size
    (:func:`~repro.kernels.lstm_cell_int.kernel.pick_schedule`): up to about
    40 windows at hidden 20 the batch sits on sublanes and the gates on
    lanes (``rows``); above that the batch sits on lanes and the gates on
    sublanes (``lanes``), so each compare-select ROM sweep — the kernel's
    dominant cost — covers only the gate rows that read that ROM. Either
    way the arguments and the result keep the layouts above; the re-lay
    happens inside this jitted call. ``interpret`` is the caller's
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
