"""Device time per step and chip of the s32 all-reduces, which carry FPISA's
integer sum of the mantissa planes (``repro.core.allreduce``)."""


def is_s32_all_reduce(op):
    return op[3].startswith("all-reduce") and "s32[" in op[4]


def read(ctx):
    t = ctx.op_time_s(is_s32_all_reduce)
    return t / ctx.steps * 1e3 if t else None
