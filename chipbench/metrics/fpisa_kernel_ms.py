"""Device time per step and chip of FPISA's fused encode and decode kernels
(``repro.kernels.fpisa_fused``), found by their names in the trace: the
HLO instruction of a Pallas kernel is named after it (``fused_decode.21``)."""

KERNELS = ("fused_encode_align", "fused_decode")


def is_kernel(op):
    return op[0].split(".")[0] in KERNELS


def read(ctx):
    t = ctx.op_time_s(is_kernel)
    return t / ctx.steps * 1e3 if t else None
