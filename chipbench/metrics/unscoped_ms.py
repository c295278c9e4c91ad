"""Device time per step and chip of the leaf ops under none of the scopes the
other layer readers take: what the attribution leaves uncovered (the layer
loop's slicing and stacking, the loss's mean over replicas, ops the compiled
step does not match) (``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, scopes.UNSCOPED)
