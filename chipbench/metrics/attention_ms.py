"""Device time per step and chip of the attention blocks: the ops under the
program's ``model.attn`` scope (ln1 norm, q/k/v/o projections, attention and
the residual add), forward, recomputed and backward (``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "attention_ms")
