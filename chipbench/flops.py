"""Operations and bytes of a train step, counted from the configuration's
shapes. Nothing here looks at the compiled program, so a change to the
program cannot change a count.
"""
from __future__ import annotations

from chipbench.weights import dims, element_counts

BLOCK = 256  # values per FPISA block; one int32 exponent each
GRAD_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul: every projection of every layer and the
    head (a tied head once); the embedding lookup, norms and biases are not
    matmuls."""
    s = dims(cfg)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["k"])
    mlp = 3 * s["d"] * s["ff"]
    return s["L"] * (attn + mlp) + s["d"] * s["v"]


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token: 6 per matmul parameter (forward and backward)
    plus causal attention, 6 * layers * seq_len * (heads * head_dim): its
    scores and weighted sum take 2 * 2 * (seq_len / 2) * heads * head_dim per
    layer forward, and the backward twice that. Recomputation is not counted."""
    s = dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * s["L"] * seq_len * s["h"] * s["hd"]


def fpisa_bytes_per_step(cfg: dict, wire_bits: int = 32) -> float:
    """HBM bytes that FPISA's encode and decode must move per step on one chip:
    the encode reads each gradient value and writes its mantissa at the wire
    width plus one exponent per block; the decode reads the summed mantissas
    and the exponents and writes the gradient back."""
    n = sum(element_counts(cfg).values())
    grad = GRAD_BYTES[cfg["torch_dtype"]]
    wire = wire_bits // 8
    blocks = 4 * n / BLOCK
    return 2 * (n * (grad + wire) + blocks)
