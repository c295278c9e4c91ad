"""Basic neural-net layers, functional style (init fns return pytrees).

Logical-axis annotations: every parameter is created through `param(...)`
with a tuple of logical axis names; sharding/rules.py maps those to mesh
axes. Weights are stored in ``param_dtype`` (bf16 by default); compute
upcasts where numerically required (norms, softmax, SSD state).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

# Registry of parameter path -> logical axes, filled during init by `param`.
# init functions thread an `Axes` recorder for sharding metadata.


class AxesRecorder:
    def __init__(self):
        self.axes: dict = {}

    def record(self, path: str, logical_axes: Sequence[str]):
        self.axes[path] = tuple(logical_axes)


def param(key, shape, logical_axes, dtype, rec: AxesRecorder, path: str, scale=None):
    rec.record(path, logical_axes)
    if scale is None:
        scale = 0.02
    if scale == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, weight, eps):
    """RMSNorm with f32 variance accumulation but NO f32 op applied directly
    to x: any convert(x)->f32 in the layer body makes XLA hoist a float32
    convert of the whole remat-saved activation stack out of the backward
    scan (+72 GB/device on the internlm dry-run, +107 GB on kimi; even an
    einsum with preferred_element_type lowers through convert(x)). Squaring
    first keeps the convert on the loop-LOCAL x*x value, which cannot be
    hoisted. The f32 reduction preserves accumulation accuracy; x*x in the
    compute dtype costs ~2^-9 relative on the variance — negligible."""
    t = x * x
    var = jnp.sum(t, axis=-1, keepdims=True, dtype=jnp.float32) / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * weight.astype(x.dtype)


def init_rms_norm(d, dtype, rec, path):
    rec.record(path + "/w", ("embed_norm",))
    return {"w": jnp.ones((d,), dtype)}


def silu(x):
    return x * jax.nn.sigmoid(x)


def init_mlp(key, cfg, rec, path, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {
        "wi": param(ks[0], (d, f), ("embed", "ff"), dt, rec, path + "/wi"),
        "wo": param(ks[1], (f, d), ("ff", "embed"), dt, rec, path + "/wo",
                    scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.mlp == "swiglu":
        p["wg"] = param(ks[2], (d, f), ("embed", "ff"), dt, rec, path + "/wg")
    return p


def apply_mlp(p, x, cfg):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if "wg" in p:
        h = silu(jnp.einsum("bsd,df->bsf", x, p["wg"])) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def rope_angles(positions, head_dim, theta):
    """positions: int32 (...,) -> (cos, sin) of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, heads_first: bool = False):
    """x: (B, S, H, hd), or (B, H, S, hd) with ``heads_first``; cos/sin:
    (B?, S, hd//2) or (S, hd//2)."""
    half = x.shape[-1] // 2
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos, sin = cos[None], sin[None]
    if heads_first:  # (B, S, half) -> (B, 1, S, half)
        cos, sin = cos[:, None], sin[:, None]
    else:  # (B, S, half) -> (B, S, 1, half)
        cos, sin = cos[:, :, None], sin[:, :, None]
    xf = x.astype(jnp.float32)
    x1f, x2f = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def init_embedding(key, cfg, rec, path):
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "tok": param(key, (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), dt, rec, path + "/tok")
    }


def embed(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p_embed, p_head, x, cfg):
    w = p_embed["tok"].T if cfg.tie_embeddings else p_head["w"]
    return jnp.einsum("bsd,dv->bsv", x, w)


def init_lm_head(key, cfg, rec, path):
    if cfg.tie_embeddings:
        return {}
    dt = jnp.dtype(cfg.param_dtype)
    return {"w": param(key, (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt, rec, path + "/w")}
