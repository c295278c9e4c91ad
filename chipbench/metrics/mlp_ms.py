"""Device time per step and chip of the MLP blocks: the ops under the
program's ``model.mlp`` scope (ln2 norm, the gated MLP and the residual add),
forward, recomputed and backward (``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "mlp_ms")
