"""FPISA: floating-point arithmetic on integer registers (paper core).

Implements, in pure JAX:

* ``encode`` / ``decode``      — FP <-> (exponent, signed two's-complement
                                 mantissa) "integer plane" representation (Fig. 3).
* ``fpisa_add_full``           — the full FPISA addition (requires the paper's
                                 RSAW shift-and-add extension on a switch; free
                                 on a TPU VPU). Aligns whichever operand is
                                 smaller (Sec. 3.2).
* ``fpisa_a_add``              — FPISA-A: only the *incoming* mantissa is ever
                                 shifted; left-shift into headroom when the
                                 incoming exponent is larger by <= headroom,
                                 overwrite beyond that (Sec. 4.3).
* ``renormalize``              — delayed renormalization: CLZ + shift + exponent
                                 fixup + pack (Sec. 3.2 "Renormalize and Assemble").
* ``fpisa_sum_sequential``     — scan-based accumulation over a worker axis;
                                 bit-faithful to the switch's packet-arrival
                                 semantics (the paper's own accuracy eval uses
                                 an equivalent software library).
* ``block_encode`` / ``block_decode`` — block-floating-point planes used by the
                                 production integer-domain all-reduce
                                 (core/allreduce.py): one shared exponent per
                                 block, mantissas aligned to it with a
                                 worker-count-dependent pre-shift so an int32
                                 reduction can never overflow.

All ops are elementwise/vectorized and usable inside Pallas kernel bodies.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import numerics as nx
from repro.core.numerics import BF16, FP16, FP32, FORMATS, FpFormat

__all__ = [
    "FP32",
    "FP16",
    "BF16",
    "FORMATS",
    "FpFormat",
    "Planes",
    "PACKED_DTYPE",
    "BITS_DTYPE",
    "to_bits",
    "from_bits",
    "encode",
    "encode_bits",
    "decode",
    "renormalize",
    "renormalize_bits",
    "fpisa_add_full",
    "fpisa_a_add",
    "fpisa_sum_sequential",
    "block_encode",
    "block_decode",
    "block_max_exponent",
]


class Planes(NamedTuple):
    """Decoupled integer representation of an FP tensor (Fig. 3)."""

    exp: jax.Array  # int32, biased exponent in [0, 2^exp_bits - 1]
    man: jax.Array  # int32, two's-complement signed mantissa (implied 1 made explicit)


# ---------------------------------------------------------------------------
# Packed-bits extraction per format
# ---------------------------------------------------------------------------

PACKED_DTYPE = {"fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16}
BITS_DTYPE = {"fp32": jnp.int32, "fp16": jnp.int16, "bf16": jnp.int16}


def to_bits(x: jax.Array, fmt: FpFormat) -> jax.Array:
    """Convert to the packed format, then bitcast to the same-width signed int
    (int32 for fp32, int16 for the 16-bit formats).

    The Pallas kernels take and return these integer bits and do the float
    bitcast outside the kernel, because Mosaic on v5e cannot load or cast
    f16 vectors while int16/int32 tiles work."""
    return jnp.asarray(x, PACKED_DTYPE[fmt.name]).view(BITS_DTYPE[fmt.name])


def from_bits(bits: jax.Array, fmt: FpFormat) -> jax.Array:
    """Raw bits (any int dtype; the low ``total_bits`` are kept) -> packed FP."""
    return bits.astype(BITS_DTYPE[fmt.name]).view(PACKED_DTYPE[fmt.name])


def encode(x: jax.Array, fmt: FpFormat = FP32) -> Planes:
    """Extract (exp, signed mantissa) planes from packed FP values.

    The implied leading 1 is made explicit; the sign is folded into the
    mantissa as two's complement (paper Sec. 3.1). Denormals flush to zero;
    NaN/Inf are not representable in-switch and are clamped to the largest
    finite value of the format (documented deviation — the paper assumes
    finite inputs).
    """
    return encode_bits(to_bits(x, fmt), fmt)


def encode_bits(bits: jax.Array, fmt: FpFormat = FP32) -> Planes:
    """``encode`` on the raw bits that ``to_bits`` gives. Only the low
    ``total_bits`` are read, so a sign-extending widening is harmless."""
    bits = bits.astype(jnp.int32)
    total = fmt.total_bits
    sign = (bits >> (total - 1)) & 1
    exp = (bits >> fmt.man_bits) & fmt.exp_mask
    man = bits & fmt.man_mask

    is_denorm = exp == 0
    is_special = exp == fmt.exp_mask  # inf / nan
    # clamp specials to max finite
    exp = jnp.where(is_special, fmt.exp_mask - 1, exp)
    man = jnp.where(is_special, fmt.man_mask, man)

    mag = jnp.where(is_denorm, 0, man | fmt.implied_one).astype(jnp.int32)
    exp = jnp.where(is_denorm, 0, exp).astype(jnp.int32)
    signed = jnp.where(sign == 1, -mag, mag).astype(jnp.int32)
    return Planes(exp=exp, man=signed)


def renormalize(planes: Planes, fmt: FpFormat = FP32) -> jax.Array:
    """Delayed renormalization + assembly back to the packed format.

    Semantics follow the paper: two's-complement arithmetic shifts, i.e.
    round-toward-negative-infinity (Appendix A.1); exponent overflow clamps to
    +/-inf; underflow flushes to zero.
    """
    return from_bits(renormalize_bits(planes, fmt), fmt)


def renormalize_bits(planes: Planes, fmt: FpFormat = FP32) -> jax.Array:
    """``renormalize`` returning the packed value's raw bits as int32."""
    e, m = jnp.asarray(planes.exp, jnp.int32), jnp.asarray(planes.man, jnp.int32)
    neg = m < 0
    mag = jnp.abs(m).astype(jnp.uint32)

    k = nx.floor_log2_u32(mag)  # position of leading 1; -1 when zero
    shift = k - fmt.man_bits  # >0: too big, shift right; <0: shift left
    # Arithmetic shift on the *signed* mantissa implements round-to-neg-inf.
    m_shifted = jnp.where(shift >= 0, nx.arshift(m, shift), nx.lshift(m, -shift))
    # Rounding toward -inf can carry the magnitude up to exactly 2^(man_bits+1)
    # (negative inputs only); fix up with one extra exact shift.
    mag2 = jnp.abs(m_shifted).astype(jnp.uint32)
    carry = (mag2 >> jnp.uint32(fmt.man_bits + 1)) != 0
    m_shifted = jnp.where(carry, nx.arshift(m_shifted, 1), m_shifted)
    shift = shift + carry.astype(jnp.int32)

    new_e = e + shift
    man_bits_out = jnp.abs(m_shifted).astype(jnp.int32) & fmt.man_mask

    zero = m == 0
    underflow = new_e <= 0
    overflow = new_e >= fmt.exp_mask

    exp_out = jnp.clip(new_e, 0, fmt.exp_mask)
    exp_out = jnp.where(zero | underflow, 0, exp_out)
    exp_out = jnp.where(overflow, fmt.exp_mask, exp_out)
    man_out = jnp.where(zero | underflow | overflow, 0, man_bits_out)

    total = fmt.total_bits
    bits = (
        (neg.astype(jnp.int32) << (total - 1))
        | (exp_out << fmt.man_bits)
        | man_out
    )
    # zero: keep signless +0 (switch register cannot hold -0 distinctly)
    return jnp.where(zero, 0, bits)


def decode(planes: Planes, fmt: FpFormat = FP32) -> jax.Array:
    """Alias for renormalize — kept for symmetry with encode."""
    return renormalize(planes, fmt)


# ---------------------------------------------------------------------------
# Accumulator updates
# ---------------------------------------------------------------------------


class AddStats(NamedTuple):
    overwrite: jax.Array  # bool: FPISA-A dropped the old accumulator value
    overflow: jax.Array  # bool: int32 register overflow (headroom exceeded)


def _overflowed(a: jax.Array, b: jax.Array, s: jax.Array) -> jax.Array:
    """Signed-add overflow detect for s = a + b (int32, two's complement)."""
    return ((a ^ s) & (b ^ s)) < 0


def fpisa_add_full(acc: Planes, inp: Planes, fmt: FpFormat = FP32):
    """Full FPISA addition (needs the RSAW extension on a switch).

    Whichever operand has the smaller exponent gets right-shifted; the result
    keeps the larger exponent (paper Sec. 3.2, Fig. 4). Returns (Planes, AddStats).
    """
    d = inp.exp - acc.exp
    # d <= 0: incoming is smaller-or-equal -> shift incoming right.
    m_le = acc.man + nx.arshift(inp.man, -d)
    # d > 0: stored value smaller -> shift *stored* mantissa right (RSAW).
    m_gt = nx.arshift(acc.man, d) + inp.man

    le = d <= 0
    shifted_in = jnp.where(le, nx.arshift(inp.man, -d), inp.man)
    shifted_acc = jnp.where(le, acc.man, nx.arshift(acc.man, d))
    new_m = jnp.where(le, m_le, m_gt)
    new_e = jnp.where(le, acc.exp, inp.exp)
    overflow = _overflowed(shifted_acc, shifted_in, new_m)
    stats = AddStats(overwrite=jnp.zeros_like(overflow), overflow=overflow)
    return Planes(exp=new_e, man=new_m), stats


def fpisa_a_add(acc: Planes, inp: Planes, fmt: FpFormat = FP32):
    """FPISA-A addition: deployable on unmodified Tofino (paper Sec. 4.3).

    Only the incoming mantissa is ever shifted:
      * d <= 0            : right-shift incoming (identical to full FPISA);
      * 0 < d <= headroom : left-shift incoming into the headroom bits,
                            accumulator exponent unchanged (denormalized);
      * d > headroom      : overwrite the accumulator with the incoming value
                            ("overwrite" error, bounded; rare for gradients).
    """
    d = inp.exp - acc.exp
    h = fmt.headroom

    right = acc.man + nx.arshift(inp.man, -d)
    left = acc.man + nx.lshift(inp.man, d)

    use_right = d <= 0
    use_left = (d > 0) & (d <= h)
    use_over = d > h

    new_m = jnp.where(use_right, right, jnp.where(use_left, left, inp.man))
    new_e = jnp.where(use_over, inp.exp, acc.exp)

    shifted_in = jnp.where(use_right, nx.arshift(inp.man, -d), nx.lshift(inp.man, d))
    overflow = jnp.where(use_over, False, _overflowed(acc.man, shifted_in, new_m))
    # Overwriting a zero accumulator is the normal "first write", not an error.
    overwrite = use_over & (acc.man != 0)
    return Planes(exp=new_e, man=new_m), AddStats(overwrite=overwrite, overflow=overflow)


def fpisa_sum_sequential(
    values: jax.Array,
    fmt: FpFormat = FP32,
    variant: str = "fpisa_a",
    return_stats: bool = False,
):
    """Aggregate ``values`` along axis 0 with switch-arrival semantics.

    ``values``: (num_workers, ...) packed FP tensor. Worker 0 arrives first.
    This is the paper's software-library equivalent used for all accuracy /
    convergence experiments (Sec. 5.2.1-5.2.2). Returns the packed FP result
    (and summed event counts when ``return_stats``).
    """
    add = fpisa_a_add if variant == "fpisa_a" else fpisa_add_full
    planes = encode(values, fmt)

    def body(carry, x):
        acc, n_over, n_ovf = carry
        new_acc, st = add(acc, Planes(*x), fmt)
        return (
            new_acc,
            n_over + jnp.sum(st.overwrite),
            n_ovf + jnp.sum(st.overflow),
        ), None

    zero = Planes(
        exp=jnp.zeros(values.shape[1:], jnp.int32),
        man=jnp.zeros(values.shape[1:], jnp.int32),
    )
    (acc, n_over, n_ovf), _ = jax.lax.scan(
        body, (zero, jnp.int32(0), jnp.int32(0)), (planes.exp, planes.man)
    )
    out = renormalize(acc, fmt)
    if return_stats:
        return out, {"overwrite": n_over, "overflow": n_ovf}
    return out


# ---------------------------------------------------------------------------
# Block planes for the production integer-domain all-reduce
# ---------------------------------------------------------------------------


def block_max_exponent(exp: jax.Array, block: int) -> jax.Array:
    """Per-block max of the exponent plane. exp: (..., N) with N % block == 0."""
    shp = exp.shape
    e = exp.reshape(shp[:-1] + (shp[-1] // block, block))
    return jnp.max(e, axis=-1)


def block_encode(
    x: jax.Array,
    block_exp: jax.Array,
    block: int,
    preshift: int,
    fmt: FpFormat = FP32,
) -> jax.Array:
    """Align mantissas of ``x`` to the (globally-maxed) block exponent.

    ``block_exp``: (..., N // block) int32, already maxed across workers.
    Result: int32 mantissa plane at scale 2^(block_exp - bias - man_bits + preshift),
    i.e. each element's true value is man * 2^(block_exp - bias - man_bits + preshift).
    The right-shift truncation implements the same round-toward-neg-inf
    semantics as the switch registers.
    """
    planes = encode(x, fmt)
    be = jnp.repeat(block_exp, block, axis=-1)
    shift = (be - planes.exp) + preshift
    return nx.arshift(planes.man, shift)


def block_decode(
    man_sum: jax.Array,
    block_exp: jax.Array,
    block: int,
    preshift: int,
    fmt: FpFormat = FP32,
) -> jax.Array:
    """Renormalize summed block mantissas back to packed FP (delayed renorm)."""
    be = jnp.repeat(block_exp, block, axis=-1)
    return renormalize(Planes(exp=be + preshift, man=man_sum), fmt)
