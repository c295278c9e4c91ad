"""The weights of a cell, made on the device from the seed.

The benchmark makes the weights itself, so the reference never takes them from
the program: both start from this tree. Its layout is that of the
configuration's model (``chipbench.models``), which the harness checks against
the program's own before it runs. Every leaf is drawn in one jitted call, in
its own dtype (the configuration's ``torch_dtype`` unless its spec names
another), straight into the shardings the caller gives; the seed enters as
data, so every seed runs the same compiled program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import models

ONES = "ones"
ZEROS = "zeros"


def leaf_specs(cfg: dict) -> dict:
    """{path: (shape, init, dtype)} of every parameter, as the configuration's
    model gives them (``chipbench.models``): init is a std, ONES, ZEROS or a
    function of (key, shape) that returns float32; a spec without a dtype
    takes the configuration's ``torch_dtype``."""
    default = cfg["torch_dtype"]
    return {path: (spec[0], spec[1], spec[2] if len(spec) > 2 else default)
            for path, spec in models.of(cfg).leaf_specs(cfg).items()}


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}; an untied model keeps an empty head."""
    out: dict = {"head": {}}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """The inverse of ``nest``: {path: leaf}, in sorted path order."""
    out = {}
    for key in sorted(tree):
        path = f"{prefix}{key}"
        if isinstance(tree[key], dict):
            out.update(flatten(tree[key], path + "/"))
        else:
            out[path] = tree[key]
    return out


def seed_key(seed: int) -> np.ndarray:
    """A raw uint32[2] PRNG key from any whole number, 64 bits and beyond."""
    return np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)


def maker(cfg: dict, shardings=None):
    """A function of the seed that draws the parameter tree of ``cfg`` on the
    device in one jitted call; ``shardings`` is a tree like the parameters, or
    one sharding for all of them. Leaf ``i`` of the sorted paths draws from
    the seed's key folded with ``i``."""
    specs = sorted(leaf_specs(cfg).items())

    def draw(key):
        flat = {}
        for i, (path, (shape, init, dtype)) in enumerate(specs):
            if init == ONES:
                flat[path] = jnp.ones(shape, dtype)
            elif init == ZEROS:
                flat[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                x = init(k, shape) if callable(init) else jax.random.normal(k, shape, jnp.float32) * init
                flat[path] = x.astype(dtype)
        return nest(flat)

    jitted = jax.jit(draw, out_shardings=shardings)
    return lambda seed: jitted(jnp.asarray(seed_key(seed)))


def element_counts(cfg: dict) -> dict:
    """{path: number of elements} of every parameter."""
    return {p: math.prod(shape) for p, (shape, _, _) in leaf_specs(cfg).items()}
