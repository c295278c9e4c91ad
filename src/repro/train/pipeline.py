"""Pipeline parallelism: GPipe-style microbatched stage loop via shard_map +
collective_permute.

Stages live on the ``pp`` mesh axis (mapped onto 'pod' for the production
mesh, or a dedicated axis on test meshes). The stacked layer parameters
(L, ...) are split into ``n_stages`` contiguous chunks along L and sharded so
each stage group holds only its chunk. The schedule runs m + n - 1 ticks for
m microbatches; activations flow stage→stage via ppermute. Because ppermute
is differentiable (its transpose is the reverse permute), ``jax.grad``
through this forward yields the reverse-schedule pipelined backward for free
— no hand-written bubble management for the backward pass.

Scope: dense/vlm-family blocks (the families that benefit from PP depth);
embedding and head are computed on every stage (replicated, cheap) with the
pipeline carrying the residual stream only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.models import transformer as T
from repro.models.layers import embed, rms_norm


def split_stages(params, n_stages: int):
    """Reshape stacked layer params (L, ...) -> (n_stages, L/n_stages, ...)."""
    def one(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    out = dict(params)
    out["layers"] = jax.tree.map(one, params["layers"])
    return out


def _stage_fn(stage_layers, x, cfg, positions):
    def body(carry, lp):
        y, _ = T._dense_block(lp, carry, cfg, positions)
        return y, None

    y, _ = jax.lax.scan(jax.checkpoint(body), x, stage_layers)
    return y


def pipeline_forward(params, batch, cfg, *, stage_axis: str, n_micro: int):
    """Runs inside shard_map with ``stage_axis`` manual. params['layers'] is
    the LOCAL stage chunk (L/n_stages, ...); other params replicated.
    Returns logits for the full batch (valid on the last stage, broadcast to
    all stages for loss uniformity)."""
    n = jax.lax.axis_size(stage_axis)
    sid = lax.axis_index(stage_axis)
    toks = batch["tokens"]
    b, s = toks.shape
    assert b % n_micro == 0
    mb = b // n_micro

    x_full = embed(params["embed"], toks).astype(jnp.dtype(cfg.activation_dtype))
    micro = x_full.reshape(n_micro, mb, s, -1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (mb, s))

    fwd = functools.partial(_stage_fn, params["layers"], cfg=cfg, positions=positions)

    def tick(carry, t):
        stream, outputs = carry  # stream: (mb, s, d) activation entering this stage
        # stage 0 injects microbatch t (when valid); others use the stream
        inject = jnp.where(t < n_micro, t, 0)
        x_in = jnp.where(sid == 0, micro[inject], stream)
        y = fwd(x=x_in)
        # forward the result to the next stage
        nxt = lax.ppermute(y, stage_axis, [(i, i + 1) for i in range(n - 1)])
        # last stage banks its result for microbatch t - (n - 1)
        out_idx = jnp.clip(t - (n - 1), 0, n_micro - 1)
        bank = (t >= n - 1) & (sid == n - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(bank, y, outputs[out_idx]), out_idx, axis=0
        )
        return (nxt, outputs), None

    stream0 = jnp.zeros_like(micro[0])
    outputs0 = jnp.zeros_like(micro)
    (_, outputs), _ = jax.lax.scan(
        tick, (stream0, outputs0), jnp.arange(n_micro + n - 1)
    )
    # broadcast last stage's outputs to all stages (psum over one-hot holder)
    mask = (sid == n - 1).astype(outputs.dtype)
    # exactly one stage is nonzero, so the sum has a single term and no
    # ordering sensitivity — not a gradient-path reduce
    # repro-lint: disable=bit-identity
    outputs = lax.psum(outputs * mask, stage_axis)

    x = outputs.reshape(b, s, -1)
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]["w"]
    return jnp.einsum("bsd,dv->bsv", x, w)


def make_pp_loss(cfg, mesh: Mesh, stage_axis: str = "pod", n_micro: int = 4):
    """Returns loss_fn(params_staged, batch) running the pipeline under
    shard_map (stage axis manual, everything else auto)."""

    def loss_inner(params, batch):
        # shard_map keeps the sharded stage axis with local size 1 — squeeze
        # to get this stage's (L/n_stages, ...) chunk
        params = dict(params) | {
            "layers": jax.tree.map(lambda a: a[0], params["layers"])
        }
        logits = pipeline_forward(params, batch, cfg, stage_axis=stage_axis,
                                  n_micro=n_micro)
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        tgt = batch["tokens"][:, 1:]
        nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
        return nll.mean()

    def loss(params_staged, batch):
        in_specs = (
            jax.tree.map(lambda _: P(), params_staged) | {
                "layers": jax.tree.map(lambda _: P(stage_axis), params_staged["layers"])
            },
            jax.tree.map(lambda _: P(), batch),
        )
        return compat.shard_map(
            loss_inner, mesh=mesh, in_specs=in_specs, out_specs=P(),
            axis_names={stage_axis}, check_vma=False,
        )(params_staged, batch)

    return loss
