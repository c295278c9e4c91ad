"""Device time per step and chip of the gradient aggregation: the ops under
the program's ``agg`` scope and its ``agg.encode``, ``agg.psum`` and
``agg.decode`` (FPISA's kernels, the glue around them, the exponents' pmax and
the planes' psum run in line), barriers and layout pins included
(``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "agg_ms")
