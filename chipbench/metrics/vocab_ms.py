"""Device time per step and chip of the vocabulary-sized work: the ops under
the program's ``model.embed`` and ``model.head`` scopes (embedding lookup and
its gradient, final norm, head matmul, log-softmax and loss)
(``chipbench.scopes``)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, "vocab_ms")
