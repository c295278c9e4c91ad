"""Train-step factory: FPISA gradient aggregation at the data-parallel boundary.

Two execution shapes, selected by ``cfg.dp_boundary`` and the mesh:

* ``replica`` (dense/ssm/hybrid/vlm/audio): params are replicated over the
  replica axes (pod, data) and TP-sharded over 'model'. The whole
  grad-computation runs inside ``shard_map`` with the replica axes *manual*
  and 'model' *auto*; per-replica gradients are aggregated explicitly by the
  configured strategy (native float psum / SwitchML / FPISA integer planes /
  sequential switch semantics). This is the paper's architecture: workers
  compute full gradients, the "switch" (= the FPISA collective) aggregates.

* ``pod`` (MoE giants): experts and FSDP shards live on the (data, model)
  grid, so only the cross-pod hop carries replica-redundant gradients —
  exactly where an in-network aggregator physically sits. shard_map is manual
  over 'pod' only; in-pod reductions stay in XLA-native float, the cross-pod
  reduction is FPISA-integer (hierarchical aggregation, DESIGN.md §2).

On a single-pod mesh with ``pod`` boundary there is no replica axis left and
the step degrades to plain auto-jit with native reductions.

The optimizer update runs *outside* the shard_map under automatic sharding so
ZeRO-1 ('data'-sharded m/v) resolves through XLA's partitioner.

Gradient aggregation is per-leaf by default; with ``agg.bucket_bytes`` set
(the ``--bucket-bytes`` launcher knob) the whole gradient pytree is streamed
through fixed-size block-aligned wire buckets with double-buffered dispatch
(core/bucketer.py) — bit-identical results, but the encode/decode overhead is
paid per bucket instead of per leaf and overlaps the in-flight collective.

Logical-worker mode (``logical_workers`` = W > 0) decouples the aggregation
group from the physical mesh for elastic fault tolerance: the global batch is
owned by W fixed logical workers (= switch ports); each mesh shard hosts
k = W / mesh_size of them, computes their gradients SEPARATELY (lax.map over
the local workers), and aggregates through the stacked integer-domain
collectives (core/allreduce.py stacked section). Because the wire shift is
derived from W and integer addition is associative, the aggregated gradient
— and the fixed-order loss reduction over the gathered (W,) per-worker loss
vector — are bit-identical on ANY mesh that divides W. That is what lets
runtime/controller.py resume training on a survivor mesh after a host death
with a trajectory equal, bit for bit, to the uninterrupted run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core.agg import AggConfig, Aggregator
from repro.optim import optimizers
from repro.sharding import rules


def _replica_axes(mesh: Mesh, cfg) -> tuple:
    if cfg.dp_boundary == "pod":
        return ("pod",) if "pod" in mesh.axis_names else ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_train_step(model, mesh: Mesh, agg: AggConfig, opt_cfg: optimizers.OptConfig,
                    global_batch: int, accum_steps: int = 1,
                    logical_workers: int = 0, diagnostics: bool = False):
    """Returns step_fn(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum_steps`` > 1 splits the per-device batch into microbatches and
    scans over them, accumulating gradients in f32 — divides the remat
    activation live-set by the microbatch count at the cost of re-running the
    (already overlapped) backward collectives per microbatch.

    ``logical_workers`` > 0 selects logical-worker mode (module doc): W fixed
    aggregation ports independent of the mesh size; requires a non-native
    aggregation strategy, ``accum_steps == 1``, and a mesh whose replica
    extent divides both W and the global batch.

    The aggregator returns the SUM of the replicas' (or logical workers')
    mean gradients; the step divides it by their count, so every strategy
    steps on the global-batch mean gradient, as ``native`` does.

    ``diagnostics`` (explicit-boundary, non-logical steps only) adds two
    entries to the metrics: ``local_grads``, each replica's gradients before
    aggregation stacked on a leading replica axis, and ``agg_grads``, the
    aggregator's output (the sum, before the division). They cost a copy of
    the gradients per replica; the program is otherwise the same step."""
    cfg = model.cfg
    boundary = _replica_axes(mesh, cfg)
    if diagnostics and (logical_workers or not boundary or agg.strategy == "native"):
        raise ValueError("diagnostics needs an explicit aggregation boundary with a "
                         "non-native strategy and no logical workers")
    if logical_workers:
        if agg.strategy == "native" or not boundary:
            raise ValueError(
                "logical_workers needs an explicit aggregation boundary with "
                f"a non-native strategy (got strategy={agg.strategy!r}, "
                f"boundary={boundary})")
        if accum_steps != 1:
            raise ValueError("logical_workers is incompatible with accum_steps")
        repl = math.prod(mesh.shape[a] for a in boundary)
        if logical_workers % repl or global_batch % logical_workers:
            raise ValueError(
                f"logical_workers={logical_workers} must be a multiple of the "
                f"replica extent {repl} and divide global_batch={global_batch}")

    def grads_and_loss(params, batch):
        if accum_steps <= 1:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            return loss, grads

        def reshape(leaf):
            b = leaf.shape[0]
            assert b % accum_steps == 0, (b, accum_steps)
            return leaf.reshape(accum_steps, b // accum_steps, *leaf.shape[1:])

        micro = jax.tree.map(reshape, batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, mb):
            acc, loss_acc = carry
            loss, grads = jax.value_and_grad(model.loss)(params, mb)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, loss_acc + loss), None

        (grads, loss), _ = jax.lax.scan(body, (zeros, jnp.float32(0)), micro)
        inv = 1.0 / accum_steps
        grads = jax.tree.map(lambda g: g * inv, grads)
        return loss * inv, grads

    if boundary and agg.strategy != "native":
        batch_axes = rules.batch_axes(mesh, global_batch)
        manual_batch_axes = tuple(a for a in batch_axes if a in boundary)
        # the ONE facade instance for this step: strategy/backend resolution
        # and capability validation happen here, before anything is traced
        aggregator = Aggregator(agg, boundary, stacked=bool(logical_workers))

        if logical_workers:
            def sharded_grads(params, batch):
                # this shard hosts k = W / replica_extent logical workers,
                # each owning a fixed global-batch slice (contiguous: shard d
                # hosts workers [d*k, (d+1)*k) — matches _gather_logical)
                repl = math.prod(jax.lax.axis_size(a) for a in boundary)
                k = logical_workers // repl

                def split(leaf):
                    b = leaf.shape[0]
                    assert b % k == 0, (b, k)
                    return leaf.reshape(k, b // k, *leaf.shape[1:])

                losses, grads = jax.lax.map(
                    lambda mb: jax.value_and_grad(model.loss)(params, mb),
                    jax.tree.map(split, batch))
                # stacked integer-domain aggregation over (worker, mesh) —
                # bit-identical on any mesh dividing W (core/allreduce.py)
                grads = aggregator.allreduce_tree(grads)
                grads = jax.tree.map(lambda g: g / logical_workers, grads)
                # fixed-order loss reduction: the gathered (W,) vector has the
                # same shape and order on every mesh. The sum MUST be a scan —
                # a jnp.sum here gets pattern-matched into a cross-device
                # all-reduce whose grouping follows the mesh size, and the
                # scalar stops being bit-reproducible across re-meshes.
                gathered = jax.lax.all_gather(losses, boundary).reshape(-1)
                loss, _ = jax.lax.scan(
                    lambda c, v: (c + v, None), jnp.float32(0), gathered)
                return loss / logical_workers, grads
        else:
            repl = math.prod(mesh.shape[a] for a in boundary)

            def sharded_grads(params, batch):
                loss, local = grads_and_loss(params, batch)
                # per-leaf or bucketed per agg.bucket_bytes (core/bucketer.py)
                summed = aggregator.allreduce_tree(local)
                grads = jax.tree.map(lambda g: g / repl, summed)
                loss = jax.lax.pmean(loss, boundary)
                if diagnostics:
                    stacked = jax.tree.map(lambda g: g[None], local)
                    return loss, grads, {"local_grads": stacked, "agg_grads": summed}
                return loss, grads

        def batch_spec(leaf):
            return P(*( [manual_batch_axes if manual_batch_axes else None]
                       + [None] * (leaf.ndim - 1)))

        # size-1 axes partition nothing, so they join the manual set: Mosaic
        # kernels (the pallas backend) cannot be partitioned automatically
        # and refuse to lower in a region that has any auto axis left
        manual = set(boundary) | {a for a in mesh.axis_names if mesh.shape[a] == 1}

        def apply_grads(params, batch):
            in_specs = (
                jax.tree.map(lambda _: P(), params),
                jax.tree.map(batch_spec, batch),
            )
            out_specs = (P(), jax.tree.map(lambda _: P(), params))
            if diagnostics:
                out_specs += ({"local_grads": jax.tree.map(lambda _: P(boundary), params),
                               "agg_grads": jax.tree.map(lambda _: P(), params)},)
            return compat.shard_map(
                sharded_grads,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                axis_names=manual,
                check_vma=False,
            )(params, batch)
    else:
        def apply_grads(params, batch):
            loss, grads = grads_and_loss(params, batch)
            return loss, grads

    def train_step(params, opt_state, batch):
        loss, grads, *diag = apply_grads(params, batch)
        params, opt_state, metrics = optimizers.update(params, grads, opt_state, opt_cfg)
        metrics["loss"] = loss
        for d in diag:
            metrics.update(d)
        return params, opt_state, metrics

    return train_step


def make_serve_steps(model, mesh: Mesh):
    """(prefill_fn, decode_fn) — plain auto-sharded jit functions."""

    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache)

    def decode(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    return prefill, decode
