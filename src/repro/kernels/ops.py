"""Public jit'd wrappers for the FPISA Pallas kernels.

On the CPU backend the kernels execute in Pallas interpret mode — the kernel
bodies run exactly as written, validating the TPU code path; on a TPU the
same calls compile to Mosaic (``interpret()`` is the one place that decides).
`use_pallas=False` routes to the pure-jnp oracles (ref.py), which XLA fuses
well — that is the default inside the big jitted train step so the dry-run
HLO stays portable, while the kernels are exercised by tests/benchmarks and
available for the TPU hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fpisa
from repro.kernels import ref
from repro.kernels.fpisa_accum import fpisa_accum
from repro.kernels.fpisa_decode import fpisa_decode
from repro.kernels.fpisa_encode import fpisa_align, fpisa_extract
from repro.kernels.fpisa_fused import fused_decode, fused_encode_align


def interpret() -> bool:
    """Whether the Pallas kernels (FPISA's, and the flash-attention kernel
    of ``models/attention``) run in interpret mode: on the CPU backend yes,
    on a TPU no (Mosaic). Any other platform raises instead of quietly
    running the TPU kernels through the interpreter."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels target TPU (Mosaic) or CPU (interpret mode); "
            f"platform {platform!r} is neither — for FPISA use backend='jnp'")
    return platform == "cpu"


def extract(x: jax.Array, fmt_name: str = "fp32", use_pallas: bool = True):
    if not use_pallas:
        return ref.extract_ref(x, fpisa.FORMATS[fmt_name])
    return fpisa_extract(x, fmt_name=fmt_name, interpret=interpret())


def align(exp, man, bmax, preshift: int = 0, use_pallas: bool = True):
    if not use_pallas:
        return ref.align_ref(exp, man, bmax, preshift)
    return fpisa_align(exp, man, bmax, preshift=preshift, interpret=interpret())


def decode(man_sum, bmax, preshift: int = 0, fmt_name: str = "fp32", use_pallas: bool = True):
    if not use_pallas:
        return ref.decode_ref(man_sum, bmax, preshift)
    return fpisa_decode(man_sum, bmax, preshift=preshift, fmt_name=fmt_name, interpret=interpret())


def accum(x, variant: str = "fpisa_a", fmt_name: str = "fp32", use_pallas: bool = True):
    if not use_pallas:
        return ref.accum_ref(x, variant=variant)
    return fpisa_accum(x, variant=variant, fmt_name=fmt_name, interpret=interpret())


def encode_align(x, fmt_name: str = "fp32", use_pallas: bool = True):
    """Fused single-pass extract+align to the LOCAL block max (hot path)."""
    if not use_pallas:
        return ref.fused_encode_align_ref(x, fpisa.FORMATS[fmt_name])
    return fused_encode_align(x, fmt_name=fmt_name, interpret=interpret())


def decode_fused(man_sum, bmax, preshift: int = 0, fmt_name: str = "fp32",
                 use_pallas: bool = True):
    """Fused decode accepting narrow wire dtypes (int8/int16/int32)."""
    if not use_pallas:
        return ref.fused_decode_ref(man_sum, bmax, preshift, fpisa.FORMATS[fmt_name])
    return fused_decode(man_sum, bmax, preshift=preshift, fmt_name=fmt_name,
                        interpret=interpret())
