"""Optimizers from scratch (no optax in this environment).

AdamW keeps m/v in float32 (params may be bf16; the update math runs in
fp32 and casts back — no separate master copy, which halves optimizer memory
at a well-understood precision cost; see DESIGN.md). State layouts are plain
pytrees mirroring params so sharding rules (ZeRO-1 'data' sharding) apply
leaf-wise.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import trace


@dataclasses.dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"  # adamw | sgdm
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    momentum: float = 0.9
    grad_clip: float = 1.0
    warmup_steps: int = 100


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any  # None for sgdm


def init(params, cfg: OptConfig) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    m = jax.tree.map(zeros, params)
    v = jax.tree.map(zeros, params) if cfg.name == "adamw" else None
    return OptState(step=jnp.zeros((), jnp.int32), m=m, v=v)


def _schedule(cfg: OptConfig, step):
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    return cfg.lr * warm


def _clip(grads, max_norm):
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), gnorm


def update(params, grads, state: OptState, cfg: OptConfig):
    """Returns (new_params, new_state, metrics); the device work is named
    ``optim`` (``repro.trace.SCOPES``)."""
    with trace.scope("optim"):
        step = state.step + 1
        lr = _schedule(cfg, step)
        grads, gnorm = _clip(grads, cfg.grad_clip)

        if cfg.name == "adamw":
            b1, b2 = cfg.b1, cfg.b2
            m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, state.m, grads)
            v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, state.v, grads)
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)

            def upd(p, mm, vv):
                u = (mm / c1) / (jnp.sqrt(vv / c2) + cfg.eps)
                pf = p.astype(jnp.float32)
                pf = pf - lr * (u + cfg.weight_decay * pf)
                return pf.astype(p.dtype)

            new_params = jax.tree.map(upd, params, m, v)
            new_state = OptState(step=step, m=m, v=v)
        elif cfg.name == "sgdm":
            m = jax.tree.map(lambda mm, g: cfg.momentum * mm + g, state.m, grads)

            def upd(p, mm):
                pf = p.astype(jnp.float32) - lr * mm
                return pf.astype(p.dtype)

            new_params = jax.tree.map(upd, params, m)
            new_state = OptState(step=step, m=m, v=None)
        else:
            raise ValueError(cfg.name)
        return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
