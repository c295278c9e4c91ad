"""Pallas TPU kernel: FPISA delayed renormalization + assembly (post-collective).

The egress-pipeline stage of the paper (Sec. 3.2 "Renormalize and Assemble"):
count leading zeros (the TCAM-LPM analogue is a 5-step branchless binary
search on the VPU), shift the two's-complement mantissa (round-to--inf),
adjust the exponent, pack to IEEE bits. One VMEM pass, no MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fpisa
from repro.kernels.fpisa_encode import TILE_R


def _decode_kernel(man_ref, bmax_ref, out_ref, *, preshift: int, fmt: fpisa.FpFormat):
    man = man_ref[...]
    e = jnp.broadcast_to(bmax_ref[...] + preshift, man.shape)  # (TILE_R,1) -> tile
    out_ref[...] = fpisa.renormalize_bits(
        fpisa.Planes(exp=e, man=man), fmt).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("preshift", "fmt_name", "interpret"))
def fpisa_decode(
    man_sum: jax.Array,
    bmax: jax.Array,
    preshift: int = 0,
    fmt_name: str = "fp32",
    interpret: bool = False,
):
    """(R,B) i32 aggregated mantissas + (R,) block exps -> (R,B) packed FP."""
    fmt = fpisa.FORMATS[fmt_name]
    r, b = man_sum.shape
    tile_r = min(TILE_R, r)
    grid = (pl.cdiv(r, tile_r),)
    bits = pl.pallas_call(
        functools.partial(_decode_kernel, preshift=preshift, fmt=fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), fpisa.BITS_DTYPE[fmt_name]),
        interpret=interpret,
    )(man_sum, bmax[:, None])
    return fpisa.from_bits(bits, fmt)  # integer bits out: see fpisa_fused
