"""The weights of a cell, made on the device from the seed.

The benchmark makes the weights itself, so the reference never takes them from
the program: both start from this tree. Its layout is that of the
configuration's model (``chipbench.models``), which the harness checks against
the program's own before it runs. Every matrix is drawn in one jitted call, in
bfloat16, straight into the shardings the caller gives; the seed enters as
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
    """{path: (shape, init)} of every parameter, as the configuration's model
    gives them (``chipbench.models``); init is a std, ONES or ZEROS."""
    return models.of(cfg).leaf_specs(cfg)


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


def maker(cfg: dict, shardings=None, dtype=jnp.bfloat16):
    """A function of the seed that draws the parameter tree of ``cfg`` on the
    device in one jitted call; ``shardings`` is a tree like the parameters, or
    one sharding for all of them."""
    specs = sorted(leaf_specs(cfg).items())

    def draw(key):
        flat = {}
        for i, (path, (shape, init)) in enumerate(specs):
            if init == ONES:
                flat[path] = jnp.ones(shape, dtype)
            elif init == ZEROS:
                flat[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = (jax.random.normal(k, shape, jnp.float32) * init).astype(dtype)
        return nest(flat)

    jitted = jax.jit(draw, out_shardings=shardings)
    return lambda seed: jitted(jnp.asarray(seed_key(seed)))


def element_counts(cfg: dict) -> dict:
    """{path: number of elements} of every parameter."""
    return {p: math.prod(shape) for p, (shape, _) in leaf_specs(cfg).items()}
