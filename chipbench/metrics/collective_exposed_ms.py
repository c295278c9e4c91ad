"""Per step and chip, the part of all collective ops' device time during which
no other op runs on that device: the exchange that nothing hides."""
from chipbench.trace import is_collective


def read(ctx):
    if not ctx.op_time_s(is_collective):
        return None
    return ctx.exposed_s(is_collective) / ctx.steps * 1e3
