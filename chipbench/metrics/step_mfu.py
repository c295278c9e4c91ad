"""Model FLOPs of the traced steps (``chipbench.flops``) over their span on the
device clock, times the chips and the chip's peak bf16 FLOP/s, in percent."""
from chipbench import flops


def read(ctx):
    span = ctx.device_span_s()
    if not span:
        return None
    cell = ctx.cell
    work = flops.model_flops_per_token(ctx.cfg, cell["seq_len"]) * cell["global_batch"] * cell["seq_len"]
    return 100.0 * work * ctx.steps / (span * ctx.chips * ctx.peaks["bf16_flops"])
