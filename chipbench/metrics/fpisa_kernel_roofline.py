"""The least time FPISA's encode and decode need on the chip, their bytes
(``chipbench.flops.fpisa_bytes_per_step``) over its peak HBM rate, over the
time the fused kernels took (the ``fpisa_kernel_ms`` reader), in percent.
Bytes bound it: the kernels do a few integer operations a value."""
from chipbench import flops


def read(ctx):
    kernel_ms = ctx.metric("fpisa_kernel_ms")
    if not kernel_ms:
        return None
    wire = ctx.cell["agg"].get("wire_bits", 32)
    need = flops.fpisa_bytes_per_step(ctx.cfg, wire) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (kernel_ms / 1e3)
