"""Plain reference of a train cell: the model, its loss, its gradients and
AdamW, in float32 at the highest matmul precision, from the configuration file
alone. It imports nothing of the program.

The model's forward pass and loss are those of the module the configuration
names (``chipbench.models``), every matmul through ``_mm`` here; the loss is
the mean next-token cross-entropy. Each parameter is held in its leaf's dtype
(``chipbench.weights``: the configuration's ``torch_dtype`` unless the model
names another), and every update is computed in float32 and rounded back to
that dtype (the repo's AdamW keeps no float32 master copy).

It runs after the program's state is freed, in blocks so that it fits: the
batch in blocks of rows, and AdamW's moments on the host between steps, one
leaf at a time on the device; the model blocks its own forward pass.

``precision="fp8"`` is the control: every matmul operand rounded to the 3
mantissa bits of float8_e4m3 and every cotangent reaching a matmul to the 2 of
float8_e5m2 (scaled, so range is never the limit). ``variant`` plants a fault
in the reference put in the program's place: ``half`` steps on the first half
of each batch, ``one_replica`` on the rows of the first chip only (the
exchange between chips left out; the loss is still the mean of all rows).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import models
from chipbench.weights import flatten, maker, nest

HIGHEST = jax.lax.Precision.HIGHEST


def _round_mantissa(x, bits):
    """Round float32 ``x`` to ``bits`` mantissa bits (nearest, ties away)."""
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    drop = 23 - bits
    i = (i + (1 << (drop - 1))) & ~((1 << drop) - 1)
    return jax.lax.bitcast_convert_type(i, jnp.float32)


@jax.custom_vjp
def _fp8(x):
    return _round_mantissa(x, 3)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_mantissa(g, 2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def nll_sum(params, tokens, cfg, precision="f32", targets=None):
    """Sum over the rows of ``tokens`` of the next-token negative log-likelihood
    of the first ``targets`` positions (all by default), by the configuration's
    model; ``params`` is the float32 tree of ``chipbench.weights``."""
    return models.of(cfg).nll_sum(params, tokens, cfg, precision, targets)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json: str, precision: str, targets):
    cfg = json.loads(cfg_json)

    def f(params, tokens):
        up = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(nll_sum)(up, tokens, cfg, precision, targets)

    return jax.jit(f)


@functools.partial(jax.jit, static_argnames=("first", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0,))
def _adam_leaf(p, g, m, v, scale, lr, c1, c2, *, first, b1, b2, eps, wd):
    g = g * scale
    m = (1 - b1) * g if first else b1 * m + (1 - b1) * g
    v = (1 - b2) * g * g if first else b2 * v + (1 - b2) * g * g
    u = (m / c1) / (jnp.sqrt(v / c2) + eps)
    pf = p.astype(jnp.float32)
    return (pf - lr * (u + wd * pf)).astype(p.dtype), m, v


@jax.jit
def _sq(g):
    return jnp.sum(jnp.square(g.astype(jnp.float32)))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


class Trainer:
    """AdamW steps of the reference, one call at a time, with the program's
    ``chipbench.program.Trainer`` interface so that it can be put in the
    program's place (the control, and the faults that tests plant).

    The weights come from ``weights.maker(cfg, ...)(seed)``; a batch is a
    host array of token rows, split over ``devices`` in blocks of
    ``rows_per_block`` rows. ``variant`` names a planted fault (module doc;
    ``one_replica`` takes ``replicas`` chips, by default those it runs on),
    or ``frozen`` (a step that hands its state back unchanged) or
    ``loss_off`` (each loss reported 1% high)."""

    def __init__(self, cfg: dict, seed: int, devices, *, rows_per_block: int,
                 precision: str = "f32", variant: str = "", replicas: int = 0):
        if variant not in ("", "half", "one_replica", "frozen", "loss_off"):
            raise ValueError(f"unknown variant {variant!r}")
        self.cfg, self.seed, self.variant = cfg, seed, variant
        self.devices = list(devices)
        self.rows_per_block = rows_per_block
        self.replicas = replicas or len(self.devices)
        self.precision = precision
        mesh = jax.sharding.Mesh(np.array(self.devices), ("d",))
        self.repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        self.split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("d"))
        self.one = jax.sharding.SingleDeviceSharding(self.devices[0])
        self._make = maker(cfg, self.one)
        self.params = flatten(self._make(seed))
        self.moments: dict = {}
        self.t = 0
        self._first_norms = None

    def place(self, tokens):
        return np.asarray(tokens)

    def step(self, tokens) -> float:
        n_rows, seq = tokens.shape
        targets = None
        grad_rows = loss_rows = n_rows
        if self.variant == "one_replica":
            grad_rows = n_rows // self.replicas
        elif self.variant == "half" and n_rows > 1:
            grad_rows = loss_rows = n_rows // 2
        elif self.variant == "half":
            targets = (seq - 1) // 2
        block = math.gcd(self.rows_per_block, grad_rows)
        grad_fn = _grad_fn(json.dumps(self.cfg, sort_keys=True), self.precision, targets)
        placed = jax.device_put(nest(self.params), self.repl)
        loss_sum, grads = 0.0, None
        for lo in range(0, loss_rows, block):
            ls, g = grad_fn(placed, jax.device_put(tokens[lo:lo + block], self.split))
            loss_sum += float(ls)
            if lo < grad_rows:
                g = {p: jax.device_put(x, self.one) for p, x in flatten(g).items()}
                grads = g if grads is None else {p: grads[p] + g[p] for p in g}
            del g
        del placed
        count = targets or seq - 1
        loss = loss_sum / (loss_rows * count) * (1.01 if self.variant == "loss_off" else 1.0)
        grads = {p: x / (grad_rows * count) for p, x in grads.items()}
        self._update(grads)
        return loss

    def _update(self, grads):
        opt = self.cfg["optimizer"]
        gnorm = math.sqrt(sum(float(_sq(g)) for g in grads.values()))
        scale = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
        if self._first_norms is None:
            self._first_norms = {p: math.sqrt(float(_sq(g))) * scale for p, g in grads.items()}
        if self.variant == "frozen":
            return
        self.t += 1
        t = self.t
        lr = opt["lr"] * min(1.0, (t + 1) / max(opt["warmup_steps"], 1))
        c1, c2 = 1 - opt["b1"] ** t, 1 - opt["b2"] ** t
        for p in sorted(self.params):
            m, v = self.moments.pop(p, (None, None))
            args = (self.params[p], grads.pop(p),
                    jnp.float32(0) if m is None else jax.device_put(m, self.one),
                    jnp.float32(0) if v is None else jax.device_put(v, self.one),
                    jnp.float32(scale), jnp.float32(lr), jnp.float32(c1), jnp.float32(c2))
            self.params[p], m, v = _adam_leaf(*args, first=m is None, b1=opt["b1"], b2=opt["b2"],
                                              eps=opt["eps"], wd=opt["weight_decay"])
            self.moments[p] = (np.asarray(m), np.asarray(v))
            del m, v

    def first_grad_norms(self) -> dict:
        """Per leaf, the norm of the first (clipped) gradient; a frozen step's
        optimizer never took it, so it reads 0."""
        if self.variant == "frozen":
            return {p: 0.0 for p in self._first_norms}
        return dict(self._first_norms)

    def change_norms(self) -> dict:
        start = flatten(self._make(self.seed))
        return {p: float(_diff_norm(self.params[p], start[p])) for p in self.params}

    def free(self):
        self.params, self.moments = {}, {}
