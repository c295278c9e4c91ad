"""The weights of a cell, made on the device from the seed.

The benchmark makes the weights itself, so the reference never takes them from
the program: both start from this tree. Its layout is the repo's transformer
parameter tree (stacked layers, ``(d, heads, head_dim)`` projections), which
the harness checks against the program's own before it runs. Every matrix is
drawn in one jitted call, in bfloat16, straight into the shardings the caller
gives; the seed enters as data, so every seed runs the same compiled program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ONES = "ones"
ZEROS = "zeros"


def dims(cfg: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "k": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // h),
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def leaf_specs(cfg: dict) -> dict:
    """{path: (shape, init)} of every parameter; init is a std, ONES or ZEROS."""
    s = dims(cfg)
    d, h, k, hd, ff, v, L = (s[n] for n in ("d", "h", "k", "hd", "ff", "v", "L"))
    std = 0.02
    # output projections scale with the depth of the whole published model
    out_std = std / math.sqrt(2 * cfg["reduced"].get("num_hidden_layers", [L])[0])
    specs = {
        "embed/tok": ((v, d), std),
        "layers/ln1/w": ((L, d), ONES),
        "layers/attn/wq": ((L, d, h, hd), std),
        "layers/attn/wk": ((L, d, k, hd), std),
        "layers/attn/wv": ((L, d, k, hd), std),
        "layers/attn/wo": ((L, h, hd, d), out_std),
        "layers/ln2/w": ((L, d), ONES),
        "layers/mlp/wi": ((L, d, ff), std),
        "layers/mlp/wg": ((L, d, ff), std),
        "layers/mlp/wo": ((L, ff, d), out_std),
        "final_norm/w": ((d,), ONES),
    }
    if cfg.get("use_qkv_bias", cfg.get("qkv_bias", False)):
        specs.update({"layers/attn/bq": ((L, h, hd), ZEROS),
                      "layers/attn/bk": ((L, k, hd), ZEROS),
                      "layers/attn/bv": ((L, k, hd), ZEROS)})
    if not cfg["tie_word_embeddings"]:
        specs["head/w"] = ((d, v), std)
    return specs


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
