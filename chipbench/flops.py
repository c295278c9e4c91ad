"""Operations and bytes of a train step, counted from the configuration's
shapes. Nothing here looks at the compiled program, so a change to the
program cannot change a count.
"""
from __future__ import annotations

from chipbench import models
from chipbench.weights import element_counts, leaf_specs

BLOCK = 256  # values per FPISA block; one int32 exponent each
GRAD_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul, as the configuration's model counts them."""
    return models.of(cfg).matmul_params(cfg)


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token, as the configuration's model counts them from
    the file's shapes (``chipbench.models``). Recomputation is not counted."""
    return models.of(cfg).model_flops_per_token(cfg, seq_len)


def fpisa_bytes_per_step(cfg: dict, wire_bits: int = 32) -> float:
    """HBM bytes that FPISA's encode and decode must move per step on one chip:
    the encode reads each gradient value, at its leaf's dtype, and writes its
    mantissa at the wire width plus one exponent per block; the decode reads
    the summed mantissas and the exponents and writes the gradient back."""
    counts = element_counts(cfg)
    n = sum(counts.values())
    grad = sum(counts[p] * GRAD_BYTES[dtype] for p, (_, _, dtype) in leaf_specs(cfg).items())
    wire = wire_bits // 8
    blocks = 4 * n / BLOCK
    return 2 * (grad + n * wire + blocks)
