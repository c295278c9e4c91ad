"""Pallas TPU kernel: sequential FPISA-A accumulation over a worker axis.

Bit-faithful to the switch's packet-arrival semantics (worker 0 first): this
is the in-VMEM equivalent of the MAU register pipeline of Fig. 2 — the
accumulator (exp, man) planes live in VMEM across the worker loop, exactly as
the switch registers persist across packets. Used by the accuracy/fidelity
benchmarks; the production all-reduce uses the associative block path instead.

Tiling: x is (W, R, B); a grid step owns a (TILE_R, B) slice of the register
file and loops over the W packets with `jax.lax.fori_loop`, so VMEM holds
W * TILE_R * B * 4 bytes of payload — the wrapper picks TILE_R to keep this
under ~4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fpisa


def _accum_kernel(x_ref, out_ref, *, num_workers: int, variant: str, fmt: fpisa.FpFormat):
    add = fpisa.fpisa_a_add if variant == "fpisa_a" else fpisa.fpisa_add_full
    shape = x_ref.shape[1:]

    def body(i, acc):
        inp = fpisa.encode_bits(x_ref[i], fmt)
        new, _ = add(fpisa.Planes(*acc), inp, fmt)
        return (new.exp, new.man)

    zero = (jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.int32))
    exp, man = jax.lax.fori_loop(0, num_workers, body, zero)
    out_ref[...] = fpisa.renormalize_bits(
        fpisa.Planes(exp=exp, man=man), fmt).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("variant", "fmt_name", "interpret"))
def fpisa_accum(
    x: jax.Array,
    variant: str = "fpisa_a",
    fmt_name: str = "fp32",
    interpret: bool = False,
):
    """x: (W, R, B) packed FP -> (R, B) f32 switch-order FPISA aggregate."""
    fmt = fpisa.FORMATS[fmt_name]
    w, r, b = x.shape
    # keep W * TILE_R * B * 4B <= ~4 MiB of VMEM for the payload tile
    budget_rows = max(8, (4 << 20) // max(1, w * b * 4))
    tile_r = min(r, budget_rows, 256)
    grid = (pl.cdiv(r, tile_r),)
    # integer bits in and out: see fpisa_fused
    bits = pl.pallas_call(
        functools.partial(_accum_kernel, num_workers=w, variant=variant, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((w, tile_r, b), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tile_r, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), fpisa.BITS_DTYPE[fmt_name]),
        interpret=interpret,
    )(fpisa.to_bits(x, fmt))
    return fpisa.from_bits(bits, fmt).astype(jnp.float32)
