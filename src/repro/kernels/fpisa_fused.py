"""Pallas TPU kernels: fused single-pass FPISA encode->align and decode.

The two-kernel pipeline in ``fpisa_encode.py`` (extract, then align) round-trips
the intermediate (exp, man) planes through HBM between the passes: 1R + 3W for
extract plus 3R + 1W for align — 8 plane-sized HBM transfers to produce one
aligned mantissa plane. That is exactly the "expensive workaround" shape the
paper attributes to end-host conversion (Sec. 4.1): the transform, not the
collective, becomes the bottleneck. These kernels collapse the hot path:

  fused_encode_align : f32 tile -> (locally-aligned int32 mantissa plane,
                       per-block max exponent).  ONE read of x, ONE write of
                       man (+ R ints of bmax); the (exp, man) planes live only
                       in VMEM/registers inside the tile pass.
  fused_decode       : (summed mantissa plane [any wire width], block exps) ->
                       packed FP.  Folds ``block_decode``'s exponent repeat,
                       wire-dtype upcast and renormalize into one tile pass.

Alignment factorization
-----------------------
The collective needs mantissas aligned to the *cross-worker* block exponent,
which is only known after a ``pmax``. Instead of a second full pass over the
(exp, man) planes, ``fused_encode_align`` aligns to the *local* block max in
the same pass that extracts the planes. Because non-negative arithmetic right
shifts compose exactly ( (m >> a) >> b == m >> (a+b), both round toward -inf,
and the >=31 clamp saturates identically), the caller finishes alignment with
a cheap per-element shift by ``(global_bmax - local_bmax) + preshift`` — a
jnp op that XLA fuses with the wire-dtype cast — and the result is
bit-identical to the reference ``extract_ref`` + ``align_ref`` composition
against the global exponent.

Both kernels read and write the packed values as same-width integer bits
(``fpisa.to_bits``); the float bitcast happens in the jitted wrapper, where
XLA folds it away, because Mosaic cannot load or cast f16 vectors on v5e.

VMEM budget: a (TILE_R, B) f32/int32 tile is TILE_R*B*4 bytes; the fused
encode kernel holds ~3 live tiles (x, man, plus encode temporaries) — at the
default TILE_R=256, B=512 worst case that is ~1.5 MiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fpisa
from repro.core import numerics as nx
from repro.kernels.fpisa_encode import TILE_R


def _fused_encode_align_kernel(x_ref, man_ref, bmax_ref, *, fmt: fpisa.FpFormat):
    planes = fpisa.encode_bits(x_ref[...], fmt)
    bmax = jnp.max(planes.exp, axis=-1, keepdims=True)  # (TILE_R, 1)
    man_ref[...] = nx.arshift(planes.man, bmax - planes.exp)
    bmax_ref[...] = bmax


def _fused_decode_kernel(man_ref, bmax_ref, out_ref, *, preshift: int, fmt: fpisa.FpFormat):
    man = man_ref[...].astype(jnp.int32)  # upcast narrow wire dtypes in-VMEM
    e = jnp.broadcast_to(bmax_ref[...] + preshift, man.shape)
    out_ref[...] = fpisa.renormalize_bits(
        fpisa.Planes(exp=e, man=man), fmt).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt_name", "interpret"))
def fused_encode_align(x: jax.Array, fmt_name: str = "fp32", interpret: bool = False):
    """x: (R, B) packed FP -> (man (R,B) i32 aligned to the LOCAL block max,
    bmax (R,) i32 local per-block max exponent).

    One HBM read of x, one HBM write of man; no intermediate plane traffic.
    Finish cross-worker alignment with ``nx.arshift(man, (global_bmax -
    bmax)[:, None] + preshift)`` after the bmax pmax.
    """
    fmt = fpisa.FORMATS[fmt_name]
    r, b = x.shape
    tile_r = min(TILE_R, r)
    grid = (pl.cdiv(r, tile_r),)
    man, bmax = pl.pallas_call(
        functools.partial(_fused_encode_align_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_r, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, b), jnp.int32),
            jax.ShapeDtypeStruct((r, 1), jnp.int32),
        ],
        interpret=interpret,
    )(fpisa.to_bits(x, fmt))
    return man, bmax[:, 0]


@functools.partial(jax.jit, static_argnames=("preshift", "fmt_name", "interpret"))
def fused_decode(
    man_sum: jax.Array,
    bmax: jax.Array,
    preshift: int = 0,
    fmt_name: str = "fp32",
    interpret: bool = False,
):
    """(R,B) int aggregated mantissas (int32/int16/int8 wire) + (R,) block
    exps -> (R,B) packed FP. Single tile pass: upcast, repeat, renormalize."""
    fmt = fpisa.FORMATS[fmt_name]
    r, b = man_sum.shape
    tile_r = min(TILE_R, r)
    grid = (pl.cdiv(r, tile_r),)
    bits = pl.pallas_call(
        functools.partial(_fused_decode_kernel, preshift=preshift, fmt=fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), fpisa.BITS_DTYPE[fmt_name]),
        interpret=interpret,
    )(man_sum, bmax[:, None])
    return fpisa.from_bits(bits, fmt)
