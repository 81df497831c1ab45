"""Fused *integer* LSTM-window template — the emulator's hot path.

The RTL emulator's original schedule dispatched one interpreted MAC
``pallas_call`` per timestep per cell and gathered the activation LUTs from
host-side tables between dispatches. This kernel is the single-dispatch
replacement, mirroring the f32 ``kernels/lstm_cell`` template: the gate
matrix W ((d_in+hid) × 4·hid, split into its x and h rows) and the
accumulator-scale bias are pinned in VMEM for the whole window, *both*
activation ROMs sit in SMEM, the int32 (h, c) state lives in VMEM scratch,
and a ``fori_loop`` iterates the timesteps in-kernel — requant
(round-half-even shift + saturate) and ROM lookups included. One dispatch
per cell per window instead of ``seq_len``, zero intermediate HBM traffic.

Both pieces are written in the forms the TPU compiler accepts:

* the gate MACs are int8×int8→int32 MXU matmuls over int8 limbs of the
  operands (:func:`~repro.quant.fixedpoint.int_matmul`; one matmul per
  operand pair for formats of at most 8 bits);
* a ROM lookup is a compare-select sweep over the table's addresses, one
  scalar SMEM read per address — exact for any int32 table word, where
  a vector gather does not lower.

Semantics are DESIGN.md §4, integer for integer — the same
``fxp_requant_int`` primitive as the per-step reference paths, so the
bit-exactness contract carries over unchanged.

Grid: (B/bb,) batch tiles; time is a ``fori_loop`` inside the kernel.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.fixedpoint import FxpFormat, fxp_requant_int, int_matmul

#: ROM addresses compared per loop iteration (Mosaic unrolls the body)
_ROM_UNROLL = 8


@dataclass(frozen=True)
class CellSpec:
    """Static metadata of one lstm_cell node — hashable, jit-static.

    Everything the fused kernel needs beyond the operand arrays: the window
    geometry, the three Q-formats' requant parameters, and the LUT address
    offsets (ROM tables are indexed by ``code - lo``, offset-binary order).
    """

    seq_len: int
    d_in: int
    hidden: int
    act_fmt: FxpFormat               # A: x, h, gate post-LUT values
    state_fmt: FxpFormat             # C: cell state
    w_fmt: FxpFormat                 # W: gate matrix codes
    sig_lo: int                      # sigmoid ROM address offset
    tanh_lo: int                     # tanh ROM address offset


def rom_lookup(codes: jax.Array, rom_ref, lo: int) -> jax.Array:
    """``rom[codes - lo]`` by compare-select over every ROM address.

    ``rom_ref`` is a 1-D int32 ref (SMEM in the kernel). Codes are
    saturated to the ROM's input format, so every address is in range.
    """
    depth = rom_ref.shape[0]
    unroll = min(_ROM_UNROLL, depth)
    idx = codes - lo

    def body(j, acc):
        for u in range(unroll):
            k = j * unroll + u
            acc = jnp.where(idx == k, rom_ref[k], acc)
        return acc

    return jax.lax.fori_loop(0, depth // unroll, body, jnp.zeros_like(idx))


def _lstm_int_kernel(x_ref, wx_ref, wh_ref, b_ref, sig_ref, tanh_ref, o_ref,
                     h_ref, c_ref, *, spec: CellSpec):
    A, C = spec.act_fmt, spec.state_fmt
    af, wf, cf = A.frac_bits, spec.w_fmt.frac_bits, C.frac_bits
    H = spec.hidden
    bits = dict(x_bits=A.total_bits, w_bits=spec.w_fmt.total_bits)
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    wx = wx_ref[...]                                 # (d_in, 4*hid)
    wh = wh_ref[...]                                 # (hid, 4*hid)
    b = b_ref[...]                                   # (1, 4*hid)

    def step(t, _):
        x_t = x_ref[:, t, :]                         # (bb, d_in)
        acc = (int_matmul(x_t, wx, **bits) + int_matmul(h_ref[...], wh, **bits)
               + b)
        z = fxp_requant_int(acc, af + wf, A)         # acc -> act fmt
        sz = rom_lookup(z, sig_ref, spec.sig_lo)     # every gate, one sweep
        tz = rom_lookup(z, tanh_ref, spec.tanh_lo)
        si, sf, so = sz[:, :H], sz[:, H:2 * H], sz[:, 3 * H:]
        tg = tz[:, 2 * H:3 * H]
        # align si*tg (scale 2·af) to sf*c (scale af+cf): << (cf - af)
        term = sf * c_ref[...] + jax.lax.shift_left(si * tg, cf - af)
        c = fxp_requant_int(term, af + cf, C)
        c_a = fxp_requant_int(c, cf, A)
        tc = rom_lookup(c_a, tanh_ref, spec.tanh_lo)
        h = fxp_requant_int(so * tc, 2 * af, A)
        h_ref[...] = h
        c_ref[...] = c
        o_ref[:, t, :] = h
        return 0

    jax.lax.fori_loop(0, spec.seq_len, step, 0)


def lstm_window_int_pallas(
    x: jax.Array,           # (B, S, d_in) int codes at act_fmt
    w: jax.Array,           # (d_in + hidden, 4*hidden) int32
    b: jax.Array,           # (4*hidden,) int32, accumulator scale
    sig_table: jax.Array,   # (2**act_bits,) int32 ROM
    tanh_table: jax.Array,  # (2**act_bits,) int32 ROM
    *, spec: CellSpec, block_b: int, interpret: bool,
) -> jax.Array:
    """Returns the full hidden sequence (B, S, hidden) int32."""
    B, S, d_in = x.shape
    assert (S, d_in) == (spec.seq_len, spec.d_in), ((S, d_in), spec)
    H = spec.hidden
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_lstm_int_kernel, spec=spec),
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, S, d_in), lambda i: (i, 0, 0)),
            pl.BlockSpec((d_in, 4 * H), lambda i: (0, 0)),  # VMEM-resident
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
            smem,
            smem,
        ],
        out_specs=pl.BlockSpec((bb, S, H), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bb, H), jnp.int32),
            pltpu.VMEM((bb, H), jnp.int32),
        ],
        interpret=interpret,
        name="lstm_window_int",
    )(x.astype(jnp.int32), w[:d_in], w[d_in:], b.reshape(1, -1), sig_table,
      tanh_table)
