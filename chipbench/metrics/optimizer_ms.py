"""Device time per step and chip of the optimizer update: the ops under the
program's ``optim`` scope (gradient clipping, AdamW's moments and the
parameter update) (``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "optimizer_ms")
