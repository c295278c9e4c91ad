"""Pallas TPU kernels: FPISA extract + align (the pre-collective transform).

This is the compute hot-spot the paper moves off the end-host CPU (Sec. 4.1's
endianness/quantization overhead, Fig. 6/10): converting a gradient stream
into switch-register form at line rate. On TPU the equivalent requirement is
that the transform must run at HBM bandwidth so the collective — not the
transform — is the bottleneck. Both kernels are single-pass elementwise/
row-reduce VPU work tiled for VMEM:

  extract: f32 tile -> (exp, signed mantissa, per-row max-exp)   [1R + 2W + R/B]
  align:   (exp, man, global block exp) -> aligned mantissa      [2R + 1W]

Tiling: inputs are reshaped to (R, B) with B = the FPISA block size (a
multiple of 128 lanes); a grid step processes a (TILE_R, B) tile held in VMEM.
All integer ops are 32-bit VPU ops; there is no MXU involvement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fpisa
from repro.core import numerics as nx

# 256 rows x 256-wide blocks x 4 B = 256 KiB per operand tile; the extract
# kernel holds ~4 operands in VMEM (x, exp, man, bmax) ~= 1 MiB << 16 MiB VMEM.
TILE_R = 256


def _extract_kernel(x_ref, exp_ref, man_ref, bmax_ref, *, fmt: fpisa.FpFormat):
    planes = fpisa.encode_bits(x_ref[...], fmt)
    exp_ref[...] = planes.exp
    man_ref[...] = planes.man
    bmax_ref[...] = jnp.max(planes.exp, axis=-1, keepdims=True)


def _align_kernel(exp_ref, man_ref, bmax_ref, out_ref, *, preshift: int):
    shift = (bmax_ref[...] - exp_ref[...]) + preshift  # bmax broadcasts (TILE_R, 1)
    out_ref[...] = nx.arshift(man_ref[...], shift)


@functools.partial(jax.jit, static_argnames=("fmt_name", "interpret"))
def fpisa_extract(x: jax.Array, fmt_name: str = "fp32", interpret: bool = False):
    """x: (R, B) packed FP -> (exp i32 (R,B), man i32 (R,B), bmax i32 (R,))."""
    fmt = fpisa.FORMATS[fmt_name]
    r, b = x.shape
    tile_r = min(TILE_R, r)
    grid = (pl.cdiv(r, tile_r),)
    exp, man, bmax = pl.pallas_call(
        functools.partial(_extract_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_r, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, b), jnp.int32),
            jax.ShapeDtypeStruct((r, b), jnp.int32),
            jax.ShapeDtypeStruct((r, 1), jnp.int32),
        ],
        interpret=interpret,
    )(fpisa.to_bits(x, fmt))  # integer bits in: see fpisa_fused
    return exp, man, bmax[:, 0]


@functools.partial(jax.jit, static_argnames=("preshift", "interpret"))
def fpisa_align(
    exp: jax.Array,
    man: jax.Array,
    bmax: jax.Array,
    preshift: int = 0,
    interpret: bool = False,
):
    """Align mantissas to the (already cross-worker-maxed) block exponent."""
    r, b = man.shape
    tile_r = min(TILE_R, r)
    grid = (pl.cdiv(r, tile_r),)
    return pl.pallas_call(
        functools.partial(_align_kernel, preshift=preshift),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), jnp.int32),
        interpret=interpret,
    )(exp, man, bmax[:, None])
