"""Named scopes: the layer names the program puts on its device work
(DESIGN.md §13).

A span (``repro.trace.span``) times host work. Device work inside a jitted
program cannot be timed from Python, whose body runs only while jax traces
it; it is named instead. ``scope(name)`` is a ``jax.named_scope``: the name
goes into the name stack of every op traced under it, and from there into
the compiled HLO's ``op_name`` metadata, which a profiler trace of the
device carries. The names survive ``jvp``, ``transpose`` and remat, so a
backward op carries the scope of its forward op. The cost falls at trace
time only; the compiled program differs in op metadata alone.

Every scope the program opens is in ``SCOPES``, so that tests and the
benchmark's readers can cite them:

- ``model.embed``: the token embedding (``models/transformer._input_embeds``)
- ``model.attn``: a block's ln1 norm, attention and its residual add
- ``model.mlp``: a block's ln2 norm, MLP (or MoE) and its residual add
- ``model.head``: the final norm, the head einsum, log-softmax and the loss
- ``agg``: the whole gradient aggregation (``Aggregator``), barriers and
  layout pins included; within it
- ``agg.encode``: flatten/pad or bucket packing, encode and align with the
  exponents' ``pmax``
- ``agg.psum``: the wire cast and the integer ``psum`` (in-pod
  ``psum_scatter`` and cross-pod ``psum`` on the hierarchical path)
- ``agg.decode``: decode, the hierarchical all-gather, and unflatten
- ``optim``: the optimizer update (``optim/optimizers.update``)
"""
from __future__ import annotations

import jax

SCOPES = ("model.embed", "model.attn", "model.mlp", "model.head",
          "agg", "agg.encode", "agg.psum", "agg.decode", "optim")


def scope(name: str):
    """``with trace.scope("agg.psum"): ...`` names the device work traced
    inside it; ``name`` must be one of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the program's scopes are {SCOPES}")
    return jax.named_scope(name)
