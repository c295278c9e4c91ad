"""Block-aligned gradient bucketing with overlapped streaming aggregation.

The switch in the paper aggregates a *stream* of fixed-size packets cut from
the whole gradient; SwitchML (Sapio et al., NSDI'21) shows that the
end-to-end training win comes from exactly this bucketing + streaming — not
from hundreds of tiny per-leaf collectives, each paying full encode/decode
overhead. This module is the host-side analogue for the FPISA collectives in
``core/allreduce.py``:

* ``make_plan``   — a static :class:`BucketPlan`: the gradient pytree's leaves
                    are grouped by dtype, scheduled in reverse-autograd order
                    (the leaves whose grads become ready first during backprop
                    go on the wire first), and packed into fixed-size wire
                    buckets. Every leaf starts at an offset padded up to the
                    FPISA block boundary and large leaves are split only at
                    block multiples, so **a block never spans two leaves** and
                    every block's contents are identical to the per-leaf
                    path's blocks — which is what makes every strategy
                    bit-identical to per-leaf aggregation (DESIGN.md §3).
* ``bucketed_allreduce_tree`` — packs, dispatches, and reassembles. For the
                    production ``fpisa`` strategy the dispatch is
                    **double-buffered**: the encode of bucket *i* and the
                    decode of bucket *i-1* are issued between the collective
                    launches of buckets *i-1* and *i*, so XLA's latency-hiding
                    scheduler overlaps transform work with wire time. On
                    hierarchical (pod, data) meshes, consecutive buckets are
                    striped across the in-pod shard ranks (whole-shard roll,
                    DESIGN.md §5) so the cross-pod hop of consecutive buckets
                    leaves from rotating DCI uplinks.

Bit-identity contract: for every strategy / backend / wire width, the result
equals ``jax.tree_util.tree_map(lambda g: allreduce(g, ...), tree)`` bit for
bit (enforced by tests/test_bucketer.py). When both ``bucket_bytes`` and
``chunk_elems`` are set the identity additionally requires
``chunk_elems % block == 0`` (block groupings of the two paths coincide only
at block-aligned chunk cuts; same caveat as per-leaf chunking itself).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro import trace as _trace
from repro.core import agg as _agg
from repro.core.agg import AggConfig


def _ceil_to(n: int, q: int) -> int:
    return -(-n // q) * q


@dataclasses.dataclass(frozen=True)
class Segment:
    """A block-aligned slice of one leaf placed inside one bucket."""

    leaf: int    # index into the pytree's flattened leaf list
    start: int   # element offset within the flattened leaf
    size: int    # real leaf elements carried (0 = pure padding tail)
    span: int    # slots occupied in the bucket (block multiple, >= size)
    offset: int  # start offset within the bucket buffer


@dataclasses.dataclass(frozen=True)
class Bucket:
    index: int                     # dispatch order (reverse-autograd)
    group: str                     # dtype group key, e.g. "float32"
    elems: int                     # buffer length (sum of spans; block-aligned)
    segments: tuple[Segment, ...]


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    block: int
    bucket_elems: int              # target capacity per bucket, in elements
    buckets: tuple[Bucket, ...]    # in dispatch order
    passthrough: tuple[int, ...]   # leaf indices routed per-leaf (non-float /
                                   # zero-size): bucketing has nothing to gain


def make_plan(leaves: Sequence, *, block: int, bucket_bytes: int) -> BucketPlan:
    """Build the static packing plan from leaf shapes/dtypes.

    ``leaves`` may be arrays or ShapeDtypeStructs (the plan never touches
    values, so it works under ``jax.eval_shape``). Leaves are walked in
    REVERSE flatten order — gradients of the deepest layers become ready
    first during backprop, so their buckets go on the wire first — and packed
    greedily into per-dtype-group open buckets. Buckets are dispatched in the
    order they fill up.
    """
    if block <= 0:
        raise ValueError(f"block must be positive, got {block}")
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")

    buckets: list[Bucket] = []
    passthrough: list[int] = []
    open_buckets: dict[str, list[Segment]] = {}
    open_fill: dict[str, int] = {}
    capacity: dict[str, int] = {}

    def seal(group: str) -> None:
        segs = open_buckets.pop(group, [])
        if segs:
            buckets.append(Bucket(
                index=len(buckets), group=group,
                elems=sum(s.span for s in segs), segments=tuple(segs)))
        open_fill.pop(group, None)

    for i in reversed(range(len(leaves))):
        leaf = leaves[i]
        dtype = jnp.dtype(leaf.dtype)
        size = int(math.prod(leaf.shape)) if leaf.shape else 1
        if size == 0 or not jnp.issubdtype(dtype, jnp.floating):
            passthrough.append(i)
            continue
        group = dtype.name
        if group not in capacity:
            capacity[group] = max(block, _ceil_to(bucket_bytes // dtype.itemsize, block))
        cap = capacity[group]
        padded = _ceil_to(size, block)
        start = 0
        while start < padded:
            fill = open_fill.get(group, 0)
            take = min(padded - start, cap - fill)
            open_buckets.setdefault(group, []).append(Segment(
                leaf=i, start=start, size=max(0, min(size, start + take) - start),
                span=take, offset=fill))
            open_fill[group] = fill + take
            start += take
            if open_fill[group] >= cap:
                seal(group)
    for group in list(open_buckets):
        seal(group)

    cap_any = max(capacity.values()) if capacity else block
    return BucketPlan(block=block, bucket_elems=cap_any,
                      buckets=tuple(buckets), passthrough=tuple(passthrough))


def plan_for_config(leaves: Sequence, cfg: AggConfig) -> BucketPlan:
    return make_plan(leaves, block=cfg.block, bucket_bytes=cfg.bucket_bytes)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------


def _stage_dtype(cfg: AggConfig, group: str):
    """Wire staging dtype of a bucket buffer — the same cast the per-leaf
    path applies to each leaf before aggregating (cast is elementwise, so
    cast-then-concat == concat-then-cast). Declared per strategy on its
    registry spec (``StrategySpec.stage_dtype``); float32 by default."""
    spec = _agg.get_strategy(cfg.strategy)
    if spec.stage_dtype is not None:
        return spec.stage_dtype(cfg, group)
    return jnp.float32  # switchml / fpisa_seq / switch_emu


def pack_bucket(bucket: Bucket, flat_leaves, stage_dtype) -> jax.Array:
    """Assemble one bucket buffer from (already flattened) leaves."""
    parts = []
    for s in bucket.segments:
        piece = lax.slice(flat_leaves[s.leaf], (s.start,), (s.start + s.size,)) \
            if s.size else None
        if piece is not None:
            piece = piece.astype(stage_dtype)
            if s.span > s.size:
                piece = jnp.pad(piece, (0, s.span - s.size))
        else:
            piece = jnp.zeros((s.span,), stage_dtype)
        parts.append(piece)
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unpack_bucket(bucket: Bucket, out: jax.Array, pieces: dict) -> None:
    """Scatter an aggregated bucket buffer back into per-leaf piece lists."""
    for s in bucket.segments:
        if s.size:
            pieces[s.leaf].append(
                (s.start, lax.slice(out, (s.offset,), (s.offset + s.size,))))


# ---------------------------------------------------------------------------
# per-bucket dispatch: split-phase pipeline (registry hooks) / generic call
# ---------------------------------------------------------------------------


def _stream_buckets(plan: BucketPlan, flat_leaves: dict, cfg: AggConfig,
                    pack_fn, phases_for, generic_fn) -> dict:
    """Double-buffered dispatch shared by the per-leaf and stacked tree
    entries: for each bucket the trace issues
        encode(i) -> [finish(i-1)] -> collective(i)
    so the decode of the in-flight bucket and the encode of the next one sit
    between consecutive collective launches — the transform work of bucket i
    overlaps the wire time of bucket i-1 under any latency-hiding scheduler.

    ``pack_fn(bucket, stage_dtype)`` assembles the wire buffer;
    ``phases_for(bucket)`` returns (encode, collect, finish) for split-phase
    pipelined strategies or None to dispatch through the one-shot
    ``generic_fn(buffer)`` with the same interleaving. Returns the
    {leaf index: [(start, aggregated piece), ...]} map."""
    pieces: dict[int, list] = {i: [] for i in flat_leaves}
    inflight = None  # (bucket, state, finish_fn or None)

    def land(entry):
        bucket, state, finish = entry
        with _trace.scope("agg.decode"):
            out = finish(state) if finish is not None else state
            unpack_bucket(bucket, out, pieces)

    for bucket in plan.buckets:
        phases = phases_for(bucket)
        if phases is not None:
            encode, collect, finish = phases
            with _trace.scope("agg.encode"):
                state = encode(pack_fn(bucket, _stage_dtype(cfg, bucket.group)))
            if inflight is not None:
                land(inflight)
            inflight = (bucket, collect(state), finish)
        else:
            with _trace.scope("agg.encode"):
                buf = pack_fn(bucket, _stage_dtype(cfg, bucket.group))
            out = generic_fn(buf)
            if inflight is not None:
                land(inflight)
            inflight = (bucket, out, None)
    if inflight is not None:
        land(inflight)
    return pieces


def _reassemble(leaves, treedef, results: dict, pieces: dict, shape_of):
    for i, leaf in enumerate(leaves):
        if i in results:
            continue
        ps = sorted(pieces[i], key=lambda t: t[0])
        with _trace.scope("agg.decode"):
            flat = jnp.concatenate([p for _, p in ps]) if len(ps) > 1 else ps[0][1]
            results[i] = flat.reshape(shape_of(leaf)).astype(leaf.dtype)
    return jax.tree_util.tree_unflatten(
        treedef, [results[i] for i in range(len(leaves))])


def bucketed_allreduce_tree(tree, axis_names: Sequence[str], cfg: AggConfig):
    """Aggregate a gradient pytree through fixed-size streamed wire buckets
    with double-buffered dispatch (``_stream_buckets``). Strategies exposing
    split-phase hooks on their registry spec (``flat_phases``/``hier_phases``)
    pipeline encode/collective/decode; everything else (and chunked dispatch)
    goes through the one-shot facade path with the same interleaving."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    axes = tuple(axis_names)
    inner = dataclasses.replace(cfg, bucket_bytes=0)
    plan = plan_for_config(leaves, cfg)

    results: dict[int, jax.Array] = {}
    for i in plan.passthrough:
        results[i] = _agg._dispatch(leaves[i], axes, inner)

    planned = {s.leaf for b in plan.buckets for s in b.segments}
    flat_leaves = {i: jnp.ravel(leaves[i]) for i in planned}

    spec = _agg.get_strategy(cfg.strategy)
    hier = len(axes) == 2 and spec.hier_phases is not None
    pipelined = not cfg.chunk_elems and (
        spec.hier_phases is not None if hier else spec.flat_phases is not None)
    backend = _agg.resolve_backend(cfg.backend)
    flat_phases = None

    def phases_for(bucket):
        nonlocal flat_phases
        if not pipelined:
            return None
        if hier:
            return spec.hier_phases(axes[1], axes[0], cfg, backend,
                                    stripe=bucket.index)
        if flat_phases is None:
            flat_phases = spec.flat_phases(axes, cfg, backend)
        return flat_phases

    pieces = _stream_buckets(
        plan, flat_leaves, cfg,
        lambda bucket, dt: pack_bucket(bucket, flat_leaves, dt),
        phases_for,
        lambda buf: _agg._dispatch(buf, axes, inner))
    return _reassemble(leaves, treedef, results, pieces, lambda l: l.shape)


# ---------------------------------------------------------------------------
# stacked (logical-worker) bucketed dispatch — elastic recovery (DESIGN.md §8)
# ---------------------------------------------------------------------------


def bucketed_stacked_allreduce_tree(tree, axis_names: Sequence[str],
                                    cfg: AggConfig):
    """``bucketed_allreduce_tree`` for per-logical-worker gradient stacks:
    every leaf carries a leading worker axis of size k and the reduction runs
    over that axis plus the mesh axes (core/allreduce.py stacked section).

    The plan is built from the PER-WORKER leaf shapes (leading axis dropped),
    so the wire layout — block alignment, bucket cuts, dispatch order — is
    identical to the unstacked plan of the same pytree, and identical across
    meshes: re-tracing on a survivor mesh after a failure re-plans for the
    new k without changing a single block boundary. Packing vmaps the same
    ``pack_bucket`` over the worker axis; aggregated buckets come back
    reduced (1-D) and unpack through the unchanged ``unpack_bucket``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    axes = tuple(axis_names)
    k = leaves[0].shape[0]
    inner = dataclasses.replace(cfg, bucket_bytes=0)
    per_worker = [jax.ShapeDtypeStruct(l.shape[1:], l.dtype) for l in leaves]
    plan = plan_for_config(per_worker, cfg)

    results: dict[int, jax.Array] = {}
    for i in plan.passthrough:
        results[i] = _agg._dispatch_stacked(leaves[i], axes, inner)

    planned = {s.leaf for b in plan.buckets for s in b.segments}
    flat_leaves = {i: leaves[i].reshape(k, -1) for i in planned}

    spec = _agg.get_strategy(cfg.strategy)
    backend = _agg.resolve_backend(cfg.backend)
    phases = None

    def phases_for(bucket):
        nonlocal phases
        if spec.stacked_phases is None:
            return None
        if phases is None:
            phases = spec.stacked_phases(axes, cfg, backend, k)
        return phases

    pieces = _stream_buckets(
        plan, flat_leaves, cfg,
        lambda bucket, dt: jax.vmap(
            lambda fl: pack_bucket(bucket, fl, dt))(flat_leaves),
        phases_for,
        lambda buf: _agg._dispatch_stacked(buf, axes, inner))
    return _reassemble(leaves, treedef, results, pieces, lambda l: l.shape[1:])
