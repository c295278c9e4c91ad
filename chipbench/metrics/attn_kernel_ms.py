"""Device time per step and chip of the flash-attention kernel
(``repro.models.attention.flash_attention``), forward, recomputed and
backward: the ops whose scope path carries the program's ``attn.kernel``
scope, and the kernel's own calls. They count under ``attention_ms`` as
well, so the reading says that the kernel ran and how much of the attention
blocks it holds. None where no op of the traced window is the kernel's.

A kernel call is found by its name as well as by its path. On a TPU the HLO
instruction of a Pallas kernel is named after it (``splash_mha_fwd_residuals.16``),
and splash attention's calls print their block sizes as metadata over several
lines, so their ``op_name`` sits on a line of its own that
``scopes.hlo_paths`` does not read: they take a neighbour's path, which
carries ``model.attn`` but not ``attn.kernel``."""
import re

from chipbench import scopes

_SCOPE = re.compile(r"(?:^|[/(])attn\.kernel(?:[/)]|$)")
KERNELS = "splash_mha_"


def read(ctx):
    paths = scopes.op_paths(ctx)
    if not paths:
        return None
    table = scopes.layer_table(ctx.cfg)

    def in_kernel(op):
        opcode, out_type, path = paths.get(op[0], (None, None, ""))
        if (opcode, out_type) != (op[3], op[4]):
            return False
        return bool(_SCOPE.search(path)) or (
            op[0].startswith(KERNELS) and scopes.layer_of(op, paths, table) == "attention_ms")

    t = ctx.per_chip_s(lambda ops: sum(op[2] for op in ops if in_kernel(op)))
    return t / ctx.steps * 1e3 if t else None
