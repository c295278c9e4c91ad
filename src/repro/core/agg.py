"""Unified ``Aggregator`` facade with a pluggable strategy registry.

FPISA's value proposition is that in-switch floating-point aggregation is a
drop-in substitute for host collectives — so the repo's aggregation surface
must itself be drop-in. This module is the ONE public entry point:

* :class:`AggConfig`    — every aggregation knob (strategy, backend, wire
                          widths, chunking, bucketing) in one frozen config.
* :class:`Aggregator`   — the facade. Constructed once from an ``AggConfig``
                          plus the mesh axis names it reduces over, it owns
                          strategy lookup, backend resolution, chunked
                          streaming, hierarchical routing, logical-worker
                          stacking, and tree-level bucketing behind two calls:
                          ``agg.allreduce(x)`` and ``agg.allreduce_tree(tree)``.
                          All capability validation happens at construction —
                          a bad combination fails with a named, actionable
                          error before anything is traced.
* :func:`register_strategy` — the registry. Strategies declare themselves
                          (flat fn, optional stacked/hierarchical variants,
                          optional split-phase pipeline hooks for the
                          bucketer) with capability flags instead of being
                          hand-threaded through dispatch dicts and
                          ``if``/``elif`` special cases. A new strategy — a
                          NetFC-style table lookup, a different emulator —
                          plugs in with one call and is immediately reachable
                          from every consumer (train step, elastic controller,
                          launchers, examples, benchmarks, serving).
* :func:`add_agg_args` / :meth:`AggConfig.from_args` — the one place CLI flag
                          threading lives. Every entry point calls the pair
                          instead of re-declaring ``--agg-*`` flags by hand.

The strategy *implementations* live in ``repro.core.allreduce`` (the math),
which registers them here at import time. The legacy module-level functions
(``allreduce``, ``allreduce_tree``, ``stacked_allreduce[_tree]``) remain as
thin deprecation shims delegating to this facade.

Capability matrix of the built-in strategies (DESIGN.md §9):

========== ======== ======== ============ ============= ==============
strategy   chunking stacking hierarchical host callback split-phase
========== ======== ======== ============ ============= ==============
native     no-op    yes      —            no            —
switchml   yes      yes      —            no            —
fpisa      yes      yes      yes          no            flat/hier/stacked
fpisa_seq  yes      yes      —            no            —
switch_emu yes      yes      —            yes           —
========== ======== ======== ============ ============= ==============
"""
from __future__ import annotations

import argparse
import dataclasses
import difflib
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import layout

from repro import trace as _trace

DEFAULT_BLOCK = 256

BACKENDS = ("auto", "jnp", "pallas")


def _did_you_mean(name: str, options: Sequence[str]) -> str:
    close = difflib.get_close_matches(name, options, n=1)
    return f"; did you mean {close[0]!r}?" if close else ""


# Measured "auto" choice per jax platform. Pallas wins only where the fused
# kernels actually beat jnp: on TPU the mosaic kernels fuse encode+align into
# one VMEM pass; on CPU the Triton/interpreter path is ~2.2x SLOWER than jnp
# (BENCH_roofline: fused 4.1 ms vs jnp 1.9 ms for the 16M-elem transform), so
# auto must resolve to jnp there — regression-pinned by tests/test_agg.py.
_AUTO_BACKEND = {
    "tpu": "pallas",
    "gpu": "jnp",  # pallas-on-gpu unmeasured here; jnp is the safe default
    "cpu": "jnp",
}


def resolve_backend(backend: str) -> str:
    """Map "auto" to the measured-fastest backend for the current jax
    platform (``_AUTO_BACKEND``; unlisted platforms fall back to jnp).

    Unknown names fail here with the valid options and the nearest match,
    not as a KeyError deep inside a traced function."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown aggregation backend {backend!r}; valid backends: "
            f"{', '.join(BACKENDS)}{_did_you_mean(backend, BACKENDS)}")
    if backend == "auto":
        return _AUTO_BACKEND.get(jax.default_backend(), "jnp")
    return backend


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AggConfig:
    """Every aggregation knob in one frozen config (strategy docs in
    ``repro.core.allreduce``; facade + registry docs in this module)."""

    strategy: str = "fpisa"  # any name in available_strategies()
    block: int = DEFAULT_BLOCK
    wire_bits: int = 32
    fmt_name: str = "fp32"
    # wire bits for the cross-pod hop when hierarchical (defaults to wire_bits)
    pod_wire_bits: int | None = None
    # process the flattened gradient in chunks of this many elements (scan):
    # bounds the transient f32/int32 plane memory to O(chunk) instead of
    # O(total params) — a 20B-param model otherwise materializes ~160 GB of
    # planes. 0 disables chunking. Chunking also matches the switch reality:
    # aggregation is streamed per-packet, never whole-tensor.
    chunk_elems: int = 0
    # encode/decode transform backend: "jnp" | "pallas" | "auto"
    backend: str = "auto"
    # tree-level bucketing (core/bucketer.py): flatten the gradient pytree
    # into fixed-size wire buckets (leaf offsets padded to block boundaries so
    # every strategy stays bit-identical to the per-leaf path) and dispatch
    # them double-buffered. 0 = legacy per-leaf tree_map. See DESIGN.md §3.
    bucket_bytes: int = 0
    # multi-tenant switch emulation (switch_emu only, DESIGN.md §10): name a
    # process-shared emulated dataplane and this aggregator's tenant on it,
    # so several jobs (plus query streams) contend for one switch. None =
    # a private single-tenant dataplane per call (the default behavior).
    switch_shared: str | None = None
    switch_jobs: int = 1
    switch_job: int = 0

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}"
                f"{_did_you_mean(self.backend, BACKENDS)}")
        if not 0 <= self.switch_job < self.switch_jobs:
            raise ValueError(
                f"switch_job must be in [0, switch_jobs={self.switch_jobs}), "
                f"got {self.switch_job}")

    @property
    def fmt(self):
        from repro.core import fpisa

        return fpisa.FORMATS[self.fmt_name]

    @classmethod
    def from_args(cls, ns: argparse.Namespace) -> "AggConfig":
        """Build the config from a namespace produced by a parser that went
        through :func:`add_agg_args` — the single CLI threading point.

        Validates strategy and backend immediately (named options + nearest
        match) so a typo'd flag fails at the command line, not mid-trace.

        ``--bucket-bytes auto`` resolves HERE, once, to a concrete byte
        count via the cost-model autotuner (``repro.autotune``): the trace
        named by ``--autotune-trace`` (or $REPRO_AUTOTUNE_TRACE) is fitted
        and the candidate sweep picks the plan; with no trace available it
        falls back loudly to the measured-good default. The config itself
        always carries an int, so everything downstream (hashing, jit
        caching, the bucketer) is unchanged."""
        bucket_bytes = getattr(ns, "bucket_bytes", 0)
        if isinstance(bucket_bytes, str):
            from repro.autotune import search as _search

            bucket_bytes = _search.auto_bucket_bytes(
                trace_path=getattr(ns, "autotune_trace", None),
                block=getattr(ns, "agg_block", None) or DEFAULT_BLOCK)
        cfg = cls(
            strategy=getattr(ns, "agg_strategy", "fpisa"),
            backend=getattr(ns, "agg_backend", "auto"),
            wire_bits=getattr(ns, "agg_wire_bits", None) or 32,
            pod_wire_bits=getattr(ns, "agg_pod_wire_bits", None),
            fmt_name=getattr(ns, "agg_fmt", None) or "fp32",
            chunk_elems=getattr(ns, "agg_chunk", 0),
            bucket_bytes=bucket_bytes,
            block=getattr(ns, "agg_block", None) or DEFAULT_BLOCK,
        )
        get_strategy(cfg.strategy)   # raises with options + nearest match
        resolve_backend(cfg.backend)
        return cfg


def _bucket_bytes_flag(value: str):
    """argparse type for ``--bucket-bytes``: an int, or the literal "auto"
    (resolved by the cost-model autotuner in ``AggConfig.from_args``)."""
    if value.strip().lower() == "auto":
        return "auto"
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--bucket-bytes expects an integer byte count or 'auto', "
            f"got {value!r}") from None


def add_agg_args(parser: argparse.ArgumentParser, *,
                 default_strategy: str = "fpisa"):
    """Register the shared ``--agg-*`` flags on ``parser``.

    Every entry point (launchers, examples, serving, benchmarks) calls this
    instead of declaring its own copies; ``AggConfig.from_args`` turns the
    parsed namespace back into a config. Legacy spellings (``--agg``,
    ``--wire-bits``, ``--pod-wire-bits``) are kept as aliases."""
    g = parser.add_argument_group(
        "aggregation", "FPISA aggregation facade (repro.core.agg)")
    g.add_argument(
        "--agg-strategy", "--agg", dest="agg_strategy",
        default=default_strategy, metavar="NAME",
        help="aggregation strategy (registry: "
             f"{', '.join(available_strategies()) or 'populated at runtime'})")
    g.add_argument(
        "--agg-backend", default="auto", metavar="NAME",
        help="pre/post-collective transform backend: auto | jnp | pallas "
             "(fused Pallas kernels on TPU; pure jnp elsewhere)")
    g.add_argument(
        "--agg-chunk", type=int, default=0, metavar="N",
        help="stream the aggregation through chunks of this many elements "
             "(bounds transient plane memory; 0 = whole-tensor)")
    g.add_argument(
        "--bucket-bytes", type=_bucket_bytes_flag, default=0, metavar="N",
        help="flatten the gradient pytree into fixed-size block-aligned wire "
             "buckets dispatched double-buffered (core/bucketer.py; "
             "bit-identical to per-leaf; 0 = per-leaf tree_map; 'auto' = "
             "pick via the cost-model autotuner, see --autotune-trace)")
    g.add_argument(
        "--autotune-trace", default=None, metavar="PATH",
        help="span trace (JSONL from --trace-out or repro.autotune.profile) "
             "the '--bucket-bytes auto' cost model is fitted from; default "
             "$REPRO_AUTOTUNE_TRACE")
    g.add_argument(
        "--agg-wire-bits", "--wire-bits", dest="agg_wire_bits", type=int,
        default=32, choices=[8, 16, 32],
        help="wire element width for the integer collective")
    g.add_argument(
        "--agg-pod-wire-bits", "--pod-wire-bits", dest="agg_pod_wire_bits",
        type=int, default=None, choices=[8, 16, 32],
        help="narrower wire for the cross-pod hop on hierarchical meshes "
             "(default: --agg-wire-bits)")
    g.add_argument(
        "--agg-fmt", default="fp32", choices=["fp32", "fp16", "bf16"],
        help="packed floating-point format of the aggregated values")
    g.add_argument(
        "--agg-block", type=int, default=DEFAULT_BLOCK, metavar="N",
        help="FPISA block size (elements sharing one exponent)")
    return g


# ---------------------------------------------------------------------------
# strategy registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StrategySpec:
    """One registered aggregation strategy with its capability flags.

    ``fn`` / ``stacked_fn`` take ``(x, axes, cfg)``; ``hierarchical_fn`` takes
    ``(x, data_axis, pod_axis, cfg)``. The ``*_phases`` hooks are optional
    split-phase pipeline factories consumed by ``core/bucketer.py`` for
    double-buffered dispatch — a strategy without them streams through the
    one-shot path with the same interleaving."""

    name: str
    fn: Callable
    stacked_fn: Callable | None = None
    hierarchical_fn: Callable | None = None
    # capability flags (validated once, at Aggregator construction)
    supports_chunking: bool = True
    # chunking is an identity for elementwise strategies (native float psum):
    # the chunked scan is skipped rather than paid
    chunk_noop: bool = False
    requires_host_callback: bool = False
    # optional config validator: raises on combinations the strategy cannot
    # honor (e.g. switch_emu's numpy dataplane is fp32-only)
    validate: Callable | None = None
    # bucketer staging dtype: (cfg, dtype_group_name) -> jnp dtype the bucket
    # buffer is assembled in (defaults to float32)
    stage_dtype: Callable | None = None
    # split-phase pipeline factories for the bucketer's double-buffering:
    #   flat_phases(axes, cfg, backend)                      -> (enc, coll, fin)
    #   hier_phases(data_axis, pod_axis, cfg, backend, stripe) -> (enc, coll, fin)
    #   stacked_phases(axes, cfg, backend, k)                -> (enc, coll, fin)
    flat_phases: Callable | None = None
    hier_phases: Callable | None = None
    stacked_phases: Callable | None = None
    description: str = ""

    @property
    def supports_stacking(self) -> bool:
        return self.stacked_fn is not None

    @property
    def supports_hierarchical(self) -> bool:
        return self.hierarchical_fn is not None


_REGISTRY: dict[str, StrategySpec] = {}


def register_strategy(name: str, *, stacked: Callable | None = None,
                      hierarchical: Callable | None = None,
                      supports_chunking: bool = True, chunk_noop: bool = False,
                      requires_host_callback: bool = False,
                      validate: Callable | None = None,
                      stage_dtype: Callable | None = None,
                      flat_phases: Callable | None = None,
                      hier_phases: Callable | None = None,
                      stacked_phases: Callable | None = None,
                      description: str = "", overwrite: bool = False):
    """Decorator registering ``fn(x, axes, cfg)`` as strategy ``name``.

        @register_strategy("netfc", stacked=netfc_stacked,
                           supports_chunking=False,
                           description="table-lookup FP add")
        def netfc_allreduce(x, axes, cfg): ...

    Also usable as a plain call: ``register_strategy("native", ...)(fn)``.
    Re-registering an existing name requires ``overwrite=True`` (guards
    against two plugins silently colliding)."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY and not overwrite:
            raise ValueError(
                f"aggregation strategy {name!r} is already registered "
                f"(pass overwrite=True to replace it)")
        _REGISTRY[name] = StrategySpec(
            name=name, fn=fn, stacked_fn=stacked, hierarchical_fn=hierarchical,
            supports_chunking=supports_chunking, chunk_noop=chunk_noop,
            requires_host_callback=requires_host_callback, validate=validate,
            stage_dtype=stage_dtype, flat_phases=flat_phases,
            hier_phases=hier_phases, stacked_phases=stacked_phases,
            description=description or (fn.__doc__ or "").split("\n")[0])
        return fn

    return deco


def unregister_strategy(name: str) -> None:
    """Remove a registered strategy (test/plugin teardown)."""
    _REGISTRY.pop(name, None)


def _ensure_builtin() -> None:
    # the built-in strategies live in repro.core.allreduce, which registers
    # them at import time; importing lazily here breaks the module cycle
    # (allreduce imports this module for AggConfig + the registry)
    if "fpisa" not in _REGISTRY:
        from repro.core import allreduce  # noqa: F401


def available_strategies() -> tuple[str, ...]:
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


def get_strategy(name: str) -> StrategySpec:
    """Look up a strategy; unknown names fail with the registered options and
    the nearest match instead of a bare KeyError."""
    _ensure_builtin()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregation strategy {name!r}; registered strategies: "
            f"{', '.join(sorted(_REGISTRY))}"
            f"{_did_you_mean(name, sorted(_REGISTRY))}") from None


# ---------------------------------------------------------------------------
# dispatch (internal — consumers go through Aggregator; the deprecation shims
# in repro.core.allreduce also land here)
# ---------------------------------------------------------------------------


def _dispatch(x: jax.Array, axes: tuple, cfg: AggConfig) -> jax.Array:
    """Single-array dispatch: chunked scan -> hierarchical -> flat."""
    spec = get_strategy(cfg.strategy)
    if cfg.chunk_elems and not spec.chunk_noop and x.size > cfg.chunk_elems:
        if not spec.supports_chunking:
            raise ValueError(
                f"strategy {cfg.strategy!r} does not support chunk_elems")
        return _chunked(x, axes, cfg)
    if len(axes) == 2 and spec.hierarchical_fn is not None:
        pod_axis, data_axis = axes[0], axes[1]
        return spec.hierarchical_fn(x, data_axis, pod_axis, cfg)
    return spec.fn(x, axes, cfg)


def _dispatch_stacked(x: jax.Array, axes: tuple, cfg: AggConfig) -> jax.Array:
    """Stacked (leading logical-worker axis) dispatch."""
    spec = get_strategy(cfg.strategy)
    if cfg.chunk_elems:
        raise NotImplementedError(
            "chunk_elems is not supported with stacked (logical-worker) "
            "aggregation; use bucket_bytes to bound transient memory instead")
    if spec.stacked_fn is None:
        raise ValueError(
            f"strategy {cfg.strategy!r} does not support stacked "
            f"(logical-worker) aggregation")
    return spec.stacked_fn(x, axes, cfg)


def _chunked(x: jax.Array, axes: tuple, cfg: AggConfig) -> jax.Array:
    """Stream the aggregation through fixed-size chunks (lax.scan) so the
    integer planes of only ONE chunk are live at a time."""
    inner = dataclasses.replace(cfg, chunk_elems=0)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % cfg.chunk_elems
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(-1, cfg.chunk_elems)

    def body(_, c):
        return None, _dispatch(c, axes, inner).astype(orig_dtype)

    _, out = lax.scan(body, None, chunks)
    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(orig_dtype)


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------


class Aggregator:
    """The one aggregation entry point (module doc).

    Constructed OUTSIDE the traced function (validation is Python-level), its
    two methods run INSIDE ``shard_map`` over ``axis_names``:

        agg = Aggregator(AggConfig(strategy="fpisa"), ("pod", "data"))
        ...
        y    = agg.allreduce(x)        # one array
        tree = agg.allreduce_tree(g)   # a gradient pytree (bucketed when
                                       # cfg.bucket_bytes is set)

    ``stacked=True`` selects logical-worker mode: every input carries a
    leading worker axis and the reduction runs over that axis plus the mesh
    axes through the strategy's stacked variant (elastic fault tolerance,
    DESIGN.md §8).

    All capability checks happen here, once: unknown strategy/backend names
    (with the valid options and nearest match), chunking with stacking or
    with a strategy that cannot chunk, stacking without a stacked variant,
    and per-strategy config validation (e.g. ``switch_emu`` is fp32-only).
    """

    def __init__(self, cfg: AggConfig, axis_names: Sequence[str] | str, *,
                 stacked: bool = False):
        if isinstance(axis_names, str):
            axis_names = (axis_names,)
        self.cfg = cfg
        self.axes = tuple(axis_names)
        self.stacked = bool(stacked)
        self.spec = get_strategy(cfg.strategy)
        self.backend = resolve_backend(cfg.backend)

        if self.stacked and not self.spec.supports_stacking:
            raise ValueError(
                f"strategy {cfg.strategy!r} does not support stacked "
                f"(logical-worker) aggregation; stacked-capable strategies: "
                f"{', '.join(s for s in available_strategies() if get_strategy(s).supports_stacking)}")
        if self.stacked and cfg.chunk_elems:
            raise ValueError(
                "chunk_elems is not supported with stacked (logical-worker) "
                "aggregation; use bucket_bytes to bound transient memory "
                "instead")
        if cfg.chunk_elems and not (self.spec.supports_chunking
                                    or self.spec.chunk_noop):
            raise ValueError(
                f"strategy {cfg.strategy!r} does not support chunk_elems "
                f"(set chunk_elems=0)")
        if cfg.bucket_bytes and cfg.chunk_elems \
                and cfg.chunk_elems % cfg.block:
            raise ValueError(
                f"bucket_bytes with chunk_elems requires chunk_elems to be a "
                f"multiple of block={cfg.block} for bit-identity "
                f"(got chunk_elems={cfg.chunk_elems}; see core/bucketer.py)")
        if self.spec.validate is not None:
            self.spec.validate(cfg)

    # -- introspection ----------------------------------------------------

    @property
    def strategy(self) -> str:
        return self.spec.name

    @property
    def requires_host_callback(self) -> bool:
        """True when the strategy round-trips through a host callback
        (``jax.pure_callback``) — such strategies need a fully-manual
        (data-only) mesh. Exposed for consumers picking a mesh; the elastic
        controller's data-only re-mesh and the serving engine's 1-D data
        mesh satisfy the constraint by construction."""
        return self.spec.requires_host_callback

    def __repr__(self) -> str:
        return (f"Aggregator(strategy={self.spec.name!r}, "
                f"backend={self.backend!r}, axes={self.axes}, "
                f"stacked={self.stacked}, "
                f"chunk_elems={self.cfg.chunk_elems}, "
                f"bucket_bytes={self.cfg.bucket_bytes})")

    # -- the two calls ----------------------------------------------------

    def allreduce(self, x: jax.Array) -> jax.Array:
        """Aggregate one array over the configured axes (leading
        logical-worker axis first when ``stacked``)."""
        with _trace.scope("agg"):
            if self.stacked:
                return _dispatch_stacked(x, self.axes, self.cfg)
            return _dispatch(x, self.axes, self.cfg)

    def allreduce_tree(self, tree):
        """Aggregate every leaf of a gradient pytree.

        With ``cfg.bucket_bytes`` set, the whole pytree is flattened into
        fixed-size block-aligned wire buckets and streamed double-buffered
        (core/bucketer.py) — bit-identical to the per-leaf path but with the
        per-collective encode/decode overhead amortized over whole buckets.
        Otherwise: per-leaf tree_map (XLA's latency-hiding scheduler still
        overlaps the independent per-leaf collectives with other work).

        The tree goes in and comes out through an optimization barrier, in
        row-major layout (``_row_major``), so the code around the aggregation
        compiles the same whichever backend runs inside it."""
        with _trace.scope("agg"):
            tree = lax.optimization_barrier(_row_major(tree))
            if self.cfg.bucket_bytes:
                from repro.core import bucketer

                if self.stacked:
                    out = bucketer.bucketed_stacked_allreduce_tree(
                        tree, self.axes, self.cfg)
                else:
                    out = bucketer.bucketed_allreduce_tree(
                        tree, self.axes, self.cfg)
            else:
                out = jax.tree_util.tree_map(self.allreduce, tree)
            return _row_major(lax.optimization_barrier(out))


def _row_major(tree):
    """Pin every leaf of ``tree`` to the row-major layout.

    Together with the optimization barriers in ``Aggregator.allreduce_tree``
    this makes the aggregation a fixed boundary. Without it, XLA on TPU fuses
    the jnp backend's elementwise encode/decode into the code around it and
    lets the backward matmuls' layouts flow through to the optimizer, while
    a Pallas kernel's operands and results are row-major: the optimizer's
    gradient-norm reduction then sums the same values in another order, and
    the two backends' trajectories part by an ulp."""
    return jax.tree_util.tree_map(
        lambda x: layout.with_layout_constraint(
            x, layout.Layout(major_to_minor=tuple(range(x.ndim)))), tree)
