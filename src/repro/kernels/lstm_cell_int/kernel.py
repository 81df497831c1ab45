"""Fused *integer* LSTM-window template — the emulator's hot path.

The RTL emulator's original schedule dispatched one interpreted MAC
``pallas_call`` per timestep per cell and gathered the activation LUTs from
host-side tables between dispatches. This kernel is the single-dispatch
replacement, mirroring the f32 ``kernels/lstm_cell`` template: the gate
matrix W (split into its x and h parts) and the accumulator-scale bias are
pinned in VMEM for the whole window, *both* activation ROMs sit in SMEM,
the int32 (h, c) state lives in VMEM scratch, and a ``fori_loop`` iterates
the timesteps in-kernel — requant (round-half-even shift + saturate) and
ROM lookups included. One dispatch per cell per window instead of
``seq_len``, zero intermediate HBM traffic.

Both pieces are written in the forms the TPU compiler accepts:

* the gate MACs are int8×int8→int32 MXU matmuls over int8 limbs of the
  operands (:func:`~repro.quant.fixedpoint.int_matmul`; one matmul per
  operand pair for formats of at most 8 bits);
* a ROM lookup is a compare-select sweep over the table's addresses, one
  scalar SMEM read per address — exact for any int32 table word, where
  a vector gather does not lower.

The sweeps set the kernel's pace: every address of a 2**act_bits-word ROM
costs a compare and a select on every vreg the looked-up codes occupy,
while the gate matmul (K = d_in + hidden) is next to free on the MXU. So
the kernel has two data layouts, and picks the one whose sweeps touch
fewer vregs (:func:`pick_schedule`):

* ``rows`` — batch on sublanes, gates on lanes: z is (bb, 4·hidden). Both
  ROMs sweep the whole of z (the gate columns share a vreg row, so a
  slice saves nothing), and the tanh ROM sweeps c. Cheap for a few
  windows: B = 1 touches one sublane row per array.
* ``lanes`` — batch on lanes, gates on sublanes: zᵀ = Wᵀ·[x_t; h] is
  (4·Hp, bb), the gate rows re-laid as i, f, o, g, each padded to Hp, a
  multiple of 8 sublanes. The sigmoid ROM sweeps only the i/f/o rows, the
  tanh ROM only the g rows and c. At hidden 20 that is 15 vregs per 128
  windows against the rows layout's 48.

Semantics are DESIGN.md §4, integer for integer — the same
``fxp_requant_int`` primitive as the per-step reference paths, and the
same limbs in the matmul, so the bit-exactness contract carries over
unchanged in both layouts.

Grid: (B/bb,) batch tiles; time is a ``fori_loop`` inside the kernel.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import get_metrics
from repro.quant.fixedpoint import FxpFormat, fxp_requant_int, int_matmul

#: ROM addresses compared per loop iteration (Mosaic unrolls the body)
_ROM_UNROLL = 8
#: one vreg: 8 sublanes × 128 lanes of int32
_SUBLANES, _LANES = 8, 128


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


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_schedule(batch: int, spec: CellSpec) -> str:
    """``"lanes"`` or ``"rows"``: the layout whose ROM sweeps touch fewer
    vregs per ROM address, for a call of ``batch`` windows.

    Per batch tile of bb = min(128, batch) windows:

    * rows: ceil(bb/8) sublane groups × (2·ceil(4H/128) + ceil(H/128)) —
      both ROMs over all of z, tanh over c; 3·ceil(bb/8) for 4H ≤ 128;
    * lanes: 5·ceil(H/8) — sigmoid over 3 padded gates, tanh over the g
      gate and over c, whatever bb is.

    Ties keep ``rows``. At hidden 20 that is 3·ceil(B/8) against 15, so
    rows up to B = 40 and lanes above. The ROM depth (2**act_bits words)
    multiplies both counts alike, so it does not move the threshold.
    """
    H = spec.hidden
    rows = _cdiv(min(_LANES, batch), _SUBLANES) * (
        2 * _cdiv(4 * H, _LANES) + _cdiv(H, _LANES))
    lanes = 5 * _cdiv(H, _SUBLANES)
    return "lanes" if lanes < rows else "rows"


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


def _cell_update(si, sf, so, tg, c, tanh_ref, spec: CellSpec):
    """(h, c) of one timestep from the looked-up gates and the old state —
    the same integer ops in either layout."""
    A, C = spec.act_fmt, spec.state_fmt
    af, cf = A.frac_bits, C.frac_bits
    # align si*tg (scale 2·af) to sf*c (scale af+cf): << (cf - af)
    term = sf * c + jax.lax.shift_left(si * tg, cf - af)
    c = fxp_requant_int(term, af + cf, C)
    c_a = fxp_requant_int(c, cf, A)
    tc = rom_lookup(c_a, tanh_ref, spec.tanh_lo)
    h = fxp_requant_int(so * tc, 2 * af, A)
    return h, c


def _lstm_rows_kernel(x_ref, wx_ref, wh_ref, b_ref, sig_ref, tanh_ref, o_ref,
                      h_ref, c_ref, *, spec: CellSpec):
    A = spec.act_fmt
    af, wf = A.frac_bits, spec.w_fmt.frac_bits
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
        h, c = _cell_update(sz[:, :H], sz[:, H:2 * H], sz[:, 3 * H:],
                            tz[:, 2 * H:3 * H], c_ref[...], tanh_ref, spec)
        h_ref[...] = h
        c_ref[...] = c
        o_ref[:, t, :] = h
        return 0

    jax.lax.fori_loop(0, spec.seq_len, step, 0)


def _lstm_lanes_kernel(x_ref, wx_ref, wh_ref, b_ref, sig_ref, tanh_ref, o_ref,
                       h_ref, c_ref, *, spec: CellSpec):
    A = spec.act_fmt
    af, wf = A.frac_bits, spec.w_fmt.frac_bits
    H, Hp = spec.hidden, h_ref.shape[0]
    # the weights are the left operand here: x_bits is W's width
    bits = dict(x_bits=spec.w_fmt.total_bits, w_bits=A.total_bits)
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    wx = wx_ref[...]                                 # (4*Hp, d_in)
    wh = wh_ref[...]                                 # (4*Hp, Hp)
    b = b_ref[...]                                   # (4*Hp, 1)

    def step(t, _):
        acc = (int_matmul(wx, x_ref[t], **bits)      # x_ref[t]: (d_in, bb)
               + int_matmul(wh, h_ref[...], **bits) + b)
        z = fxp_requant_int(acc, af + wf, A)         # rows i, f, o, g
        s = rom_lookup(z[:3 * Hp], sig_ref, spec.sig_lo)
        tg = rom_lookup(z[3 * Hp:], tanh_ref, spec.tanh_lo)
        h, c = _cell_update(s[:Hp], s[Hp:2 * Hp], s[2 * Hp:], tg, c_ref[...],
                            tanh_ref, spec)
        h_ref[...] = h
        c_ref[...] = c
        o_ref[t] = h[:H]
        return 0

    jax.lax.fori_loop(0, spec.seq_len, step, 0)


def _gate_rows(a: jax.Array, hidden: int, padded: int) -> jax.Array:
    """Last axis of 4 gates i, f, g, o -> i, f, o, g, each zero-padded from
    ``hidden`` to ``padded``."""
    i, f, g, o = jnp.split(a, 4, axis=-1)
    pad = [(0, 0)] * (a.ndim - 1) + [(0, padded - hidden)]
    return jnp.concatenate([jnp.pad(q, pad) for q in (i, f, o, g)], axis=-1)


def lstm_window_int_pallas(
    x: jax.Array,           # (B, S, d_in) int codes at act_fmt
    w: jax.Array,           # (d_in + hidden, 4*hidden) int32
    b: jax.Array,           # (4*hidden,) int32, accumulator scale
    sig_table: jax.Array,   # (2**act_bits,) int32 ROM
    tanh_table: jax.Array,  # (2**act_bits,) int32 ROM
    *, spec: CellSpec, block_b: int, interpret: bool,
) -> jax.Array:
    """Returns the full hidden sequence (B, S, hidden) int32.

    The layout comes from :func:`pick_schedule` on the static batch size;
    each trace counts ``rtl.kernels.lstm_window_int.schedule.<layout>``.
    In the lanes layout W, b, x and the result are re-laid around the
    kernel here; ``block_b`` must then be a multiple of 128 or all of B.
    """
    B, S, d_in = x.shape
    assert (S, d_in) == (spec.seq_len, spec.d_in), ((S, d_in), spec)
    H = spec.hidden
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    schedule = pick_schedule(B, spec)
    get_metrics().counter(
        f"rtl.kernels.lstm_window_int.schedule.{schedule}").inc()
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    x = x.astype(jnp.int32)
    if schedule == "rows":
        kernel = _lstm_rows_kernel
        operands = (x, w[:d_in], w[d_in:], b.reshape(1, -1))
        in_specs = [
            pl.BlockSpec((bb, S, d_in), lambda i: (i, 0, 0)),
            pl.BlockSpec((d_in, 4 * H), lambda i: (0, 0)),  # VMEM-resident
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
        ]
        out_spec = pl.BlockSpec((bb, S, H), lambda i: (i, 0, 0))
        out_shape = (B, S, H)
        state = (bb, H)
    else:
        assert bb == B or bb % _LANES == 0, (B, bb)
        kernel = _lstm_lanes_kernel
        Hp = _cdiv(H, _SUBLANES) * _SUBLANES
        wg = _gate_rows(w, H, Hp)                    # (d_in + H, 4*Hp)
        # the padded h rows hold junk: zero weights keep it out of z
        wh = jnp.pad(wg[d_in:], ((0, Hp - H), (0, 0)))
        operands = (x.transpose(1, 2, 0), wg[:d_in].T, wh.T,
                    _gate_rows(b, H, Hp).reshape(-1, 1))
        in_specs = [
            pl.BlockSpec((S, d_in, bb), lambda i: (0, 0, i)),
            pl.BlockSpec((4 * Hp, d_in), lambda i: (0, 0)),  # VMEM-resident
            pl.BlockSpec((4 * Hp, Hp), lambda i: (0, 0)),
            pl.BlockSpec((4 * Hp, 1), lambda i: (0, 0)),
        ]
        out_spec = pl.BlockSpec((S, H, bb), lambda i: (0, 0, i))
        out_shape = (S, H, B)
        state = (Hp, bb)
    out = pl.pallas_call(
        functools.partial(kernel, spec=spec),
        grid=(B // bb,),
        in_specs=in_specs + [smem, smem],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM(state, jnp.int32),
                        pltpu.VMEM(state, jnp.int32)],
        interpret=interpret,
        name="lstm_window_int",
    )(*operands, sig_table, tanh_table)
    return out if schedule == "rows" else out.transpose(2, 0, 1)
