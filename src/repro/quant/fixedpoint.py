"""Fixed-point (Q-format) quantization — the paper's core optimization.

ElasticAI-Creator translates models to RTL with fixed-point arithmetic
(power-of-two scales, so the FPGA needs only shifts, no multipliers for
rescaling). We reproduce exactly that: Q(total_bits, frac_bits) with
round-to-nearest and saturation, plus a straight-through estimator so the
same graph is trainable (QAT).

On TPU the analogue of the DSP-slice int MAC is the int8 MXU path — see
``repro.quant.ptq`` and ``kernels/quant_matmul`` for that (beyond-paper)
variant; this module is the paper-faithful one.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class FxpFormat:
    """Q(total_bits, frac_bits): 1 sign bit, total-frac-1 integer bits."""

    total_bits: int = 8
    frac_bits: int = 6

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def lo(self) -> int:
        return -(2 ** (self.total_bits - 1))

    @property
    def hi(self) -> int:
        return 2 ** (self.total_bits - 1) - 1

    @property
    def resolution(self) -> float:
        return 1.0 / self.scale

    @property
    def max_value(self) -> float:
        return self.hi / self.scale

    def __str__(self) -> str:
        return f"Q{self.total_bits}.{self.frac_bits}"


def fxp_quantize(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """Round-to-nearest, saturating. Returns the *dequantized* f32 value."""
    q = jnp.round(x.astype(jnp.float32) * fmt.scale)
    q = jnp.clip(q, fmt.lo, fmt.hi)
    return q / fmt.scale


def fxp_to_int(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    """The integer codes an RTL template would hold in BRAM."""
    q = jnp.round(x.astype(jnp.float32) * fmt.scale)
    q = jnp.clip(q, fmt.lo, fmt.hi)
    dtype = jnp.int8 if fmt.total_bits <= 8 else jnp.int16 \
        if fmt.total_bits <= 16 else jnp.int32
    return q.astype(dtype)


def fxp_requant_int(v: jax.Array, from_frac: int, fmt: FxpFormat) -> jax.Array:
    """Integer-domain rescale: the exact counterpart of ``fxp_quantize``.

    ``v`` holds integer codes at scale ``2**from_frac``; the result holds the
    codes of ``fxp_quantize(v / 2**from_frac, fmt)`` at scale
    ``2**fmt.frac_bits`` — same round-to-nearest-even and saturation, computed
    entirely in int32 (a shift + comparator, which is what the RTL emits).
    Exactness holds whenever ``|v| < 2**24`` so the float reference's f32
    arithmetic is itself exact (see DESIGN.md §4).
    """
    v = v.astype(jnp.int32)
    s = from_frac - fmt.frac_bits
    if s > 0:                       # narrow: round-half-even right shift
        q0 = jax.lax.shift_right_arithmetic(v, s)
        rem = v - jax.lax.shift_left(q0, s)
        half = 1 << (s - 1)
        inc = (rem > half) | ((rem == half) & ((q0 & 1) == 1))
        q = q0 + inc.astype(jnp.int32)
    elif s < 0:                     # widen: exact left shift
        q = jax.lax.shift_left(v, -s)
    else:
        q = v
    return jnp.clip(q, fmt.lo, fmt.hi)


def int8_limbs(v: jax.Array, bits: int) -> list:
    """Split signed ``bits``-wide integer codes into int8 limbs.

    ``v == sum(limb[j] << 7*j)``: the low limbs are 7-bit unsigned slices,
    the top limb is the signed remainder, which fits int8 whenever ``v``
    lies in its format's range. One limb for ``bits <= 8``.
    """
    n = 1 if bits <= 8 else 1 + -(-(bits - 8) // 7)
    v = v.astype(jnp.int32)
    limbs = [(jax.lax.shift_right_arithmetic(v, 7 * j) & 0x7F)
             .astype(jnp.int8) for j in range(n - 1)]
    limbs.append(jax.lax.shift_right_arithmetic(v, 7 * (n - 1))
                 .astype(jnp.int8))
    return limbs


def int_matmul(x: jax.Array, w: jax.Array, *, x_bits: int,
               w_bits: int) -> jax.Array:
    """Exact ``x @ w`` of integer codes, accumulated in int32.

    Both operands are split into int8 limbs (:func:`int8_limbs`) and every
    limb pair is one int8×int8→int32 matmul — the form the TPU's MXU takes
    (it refuses int32×int32) — recombined by left shifts. Operands of at
    most 8 bits are one matmul. The result equals the int32 matmul
    whenever the operands lie in their formats' ranges; int32 wraparound is
    modular, so shifted partial sums may overflow as long as the final
    accumulator fits, which the §4 envelope guarantees. A word outside its
    format (an SEU-flipped high bit) is read by its low ``7·(limbs-1)+8``
    bits, so on a corrupted design this can differ from the int32 matmul;
    both then differ from the golden response.
    """
    acc = None
    for i, xl in enumerate(int8_limbs(x, x_bits)):
        for j, wl in enumerate(int8_limbs(w, w_bits)):
            p = jax.lax.dot_general(xl, wl, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            if i + j:
                p = jax.lax.shift_left(p, 7 * (i + j))
            acc = p if acc is None else acc + p
    return acc


@jax.custom_vjp
def fxp_fake_quant(x: jax.Array, scale: jax.Array, lo: float, hi: float):
    q = jnp.clip(jnp.round(x * scale), lo, hi)
    return q / scale


def _fq_fwd(x, scale, lo, hi):
    return fxp_fake_quant(x, scale, lo, hi), (x, scale, lo, hi)


def _fq_bwd(res, g):
    x, scale, lo, hi = res
    # STE with saturation masking: no gradient where the value clipped
    inside = (x * scale >= lo) & (x * scale <= hi)
    return (jnp.where(inside, g, 0.0), None, None, None)


fxp_fake_quant.defvjp(_fq_fwd, _fq_bwd)


def fake_quant(x: jax.Array, fmt: FxpFormat) -> jax.Array:
    return fxp_fake_quant(x.astype(jnp.float32), jnp.float32(fmt.scale),
                          float(fmt.lo), float(fmt.hi))


def pick_frac_bits(x: jax.Array, total_bits: int) -> int:
    """Largest frac_bits such that amax still fits (power-of-two scale)."""
    amax = float(jnp.max(jnp.abs(x)))
    if amax == 0.0:
        return total_bits - 1
    import math

    int_bits = max(0, math.ceil(math.log2(amax + 1e-12) + 1e-9) + 1)
    return max(0, min(total_bits - 1, total_bits - 1 - int_bits))


def quant_error(x: jax.Array, fmt: FxpFormat) -> float:
    """RMS quantization error — reported in the creator's stage-1 report."""
    return float(jnp.sqrt(jnp.mean(jnp.square(x - fxp_quantize(x, fmt)))))
