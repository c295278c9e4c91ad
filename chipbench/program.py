"""The system under test, driven as ``repro.launch.train.train_loop`` drives it.

This is the one module of the benchmark that imports the program. It builds
the jitted step with ``repro.launch.train.build_step`` (the helper
``train_loop`` uses), starts it from the benchmark's own weights
(``chipbench.weights``) in the shardings the program keeps its state in, and
places each batch as ``train_loop`` does (``rules.batch_pspec``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import models, weights
from repro.core.agg import AggConfig
from repro.launch.train import build_step, state_shardings
from repro.optim import optimizers
from repro.runtime.elastic import make_mesh_for
from repro.sharding import rules

_OPT_KEYS = ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip", "warmup_steps")


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration, as its model maps it
    (``chipbench.models``)."""
    return models.of(cfg).program_config(cfg)


def _leaf_norms(tree, scale=1.0):
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) * scale
            for p, x in weights.flatten(tree).items()}


class Trainer:
    """The program's train step and state for one cell and seed."""

    def __init__(self, cfg: dict, cell: dict, seed: int, devices):
        self.cfg = cfg
        self.seed = seed
        self.mesh = make_mesh_for(devices=list(devices))
        mcfg = model_config(cfg)
        batch = cell["global_batch"]
        opt = {k: cfg["optimizer"][k] for k in _OPT_KEYS}
        self.model, self.opt_cfg, self.step_fn = build_step(
            mcfg, self.mesh, AggConfig(**cell["agg"]), batch, opt_overrides=opt)
        self.pshard, oshard = state_shardings(self.model, mcfg, self.mesh, self.opt_cfg)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            jax.eval_shape(self.model.init, jax.random.PRNGKey(0)))
        self._make = weights.maker(cfg, self.pshard)
        self.params = self._make(seed)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if got != want:
            raise ValueError("the benchmark's parameter tree no longer matches the program's")
        self.opt_state = jax.jit(lambda p: optimizers.init(p, self.opt_cfg),
                                 out_shardings=oshard)(self.params)
        self.batch_sharding = NamedSharding(self.mesh, P(*rules.batch_pspec(self.mesh, batch), None))
        self._grad_norms = jax.jit(lambda m: _leaf_norms(m, 1.0 / (1.0 - self.opt_cfg.b1)))
        self._change = jax.jit(lambda a, b: _leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

    def place(self, tokens):
        return {"tokens": jax.device_put(tokens, self.batch_sharding)}

    def step(self, batch):
        """One step of the window's call; returns the loss on the device."""
        self.params, self.opt_state, metrics = self.step_fn(self.params, self.opt_state, batch)
        return metrics["loss"]

    def first_grad_norms(self) -> dict:
        """Per leaf, the norm of the gradient AdamW took in the first step,
        worked out from its first moment (m = (1 - b1) g after one step)."""
        return {p: float(x) for p, x in self._grad_norms(self.opt_state.m).items()}

    def change_norms(self) -> dict:
        """Per leaf, the norm of the parameters' change since the start."""
        start = self._make(self.seed)
        out = {p: float(x) for p, x in self._change(self.params, start).items()}
        del start
        return out

    def free(self):
        del self.params, self.opt_state
