"""Gradient-aggregation strategies (the paper's technique as a collective).

The switch in the paper sits at the aggregation point between workers. On a
TPU fleet the analogous boundary is the data-parallel replica axis (and, on a
multi-pod mesh, the cross-pod hop — the expensive link where an in-network
aggregator would physically sit). All strategies here operate *inside*
``shard_map`` over the replica axes (manual collectives), with the model/TP
axes left automatic.

Strategies
----------
native     : plain float psum — the no-switch baseline.
switchml   : SwitchML (Sapio et al., NSDI'21) reimplementation: per-chunk
             max-exponent round trip (collective #1), int32 fixed-point
             quantize -> int psum (collective #2) -> dequantize. This is the
             baseline the paper improves on.
fpisa      : the paper's technique adapted to TPU: block-exponent planes,
             mantissas aligned with worker-count pre-shift, ONE int32 psum +
             one tiny int32 pmax, delayed renormalization after the collective.
             Bit-reproducible for any reduction order/topology (int add is
             associative + commutative).
fpisa_seq  : bit-faithful switch-arrival semantics (sequential FPISA-A over
             the worker axis via all_gather + scan). Used by accuracy
             experiments; not a production path (W x bytes on the wire).
switch_emu : validation strategy — routes the gathered per-worker gradients
             through the batched switch-dataplane emulator
             (``repro/switchsim``) via a host callback: real slot pool,
             worker bitmaps, streaming window and packetization, lossless
             fabric. Bit-identical to ``fpisa_seq`` (zero-drop arrival order
             is worker-major per chunk). Strictly for validating the
             emulator against the production collectives — never a hot path.

Options
-------
wire_bits  : 32 (default), 16 or 8 — beyond-paper compression: mantissas are
             truncated to the requested element width before the reduction
             (error bound widens by the extra shift; see DESIGN.md §2).
hierarchical: on a multi-pod mesh, reduce-scatter in-pod over `data`, psum
             across `pod`, all-gather in-pod — lets the cross-pod hop use a
             narrower wire than the in-pod hop.
bucket_bytes: tree-level bucketing for ``allreduce_tree`` — the whole
             gradient pytree is flattened into fixed-size block-aligned wire
             buckets, scheduled in reverse-autograd order and dispatched
             double-buffered (core/bucketer.py, DESIGN.md §3/§5). Bit-identical
             to the per-leaf path; 0 = legacy per-leaf tree_map.

Backends
--------
The pre/post-collective transform (encode->align before the psum, decode
after) is pluggable via ``AggConfig.backend``:

``"jnp"``    : pure jnp ``fpisa.encode`` / ``block_decode`` — portable, XLA
               decides the fusion. Reference semantics.
``"pallas"`` : the fused single-pass kernels in ``kernels/fpisa_fused.py`` —
               one HBM read of the gradient and one write of the mantissa
               plane per direction; the (exp, man) planes never round-trip
               through HBM. Mantissas leave the kernel aligned to the LOCAL
               block max; the residual shift to the cross-worker max composes
               exactly on top (arithmetic right shifts compose), so the two
               backends are bit-identical for every strategy, wire width,
               chunking and format. On CPU hosts the kernels run in Pallas
               interpret mode (same semantics, for tests).
``"auto"``   : default — "pallas" on TPU backends, "jnp" elsewhere.

The chunked streaming path (``chunk_elems``) threads the backend through
unchanged: each scanned chunk runs the fused kernel on its own (chunk/block,
block) tile grid, so only one chunk's mantissa plane is ever live — the
whole-tensor planes are never materialized on either backend.

Public API
----------
This module holds the strategy *implementations*; the public aggregation
surface is the :class:`repro.core.agg.Aggregator` facade, where every
strategy below registers itself (``register_strategy``) with its capability
flags. The module-level ``allreduce`` / ``allreduce_tree`` /
``stacked_allreduce[_tree]`` functions are retained as thin deprecation
shims delegating to the facade; ``AggConfig``, ``resolve_backend``,
``BACKENDS`` and ``DEFAULT_BLOCK`` are re-exported from ``repro.core.agg``
for backwards compatibility.
"""
from __future__ import annotations

import math
import warnings
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import trace as _trace
from repro.core import agg as _agg
from repro.core.agg import (  # noqa: F401  (re-exported legacy surface)
    AggConfig, BACKENDS, DEFAULT_BLOCK, register_strategy, resolve_backend,
)
from repro.core import fpisa
from repro.core import numerics as nx
from repro.kernels import fpisa_fused
from repro.kernels.ops import interpret as _interpret


# ---------------------------------------------------------------------------
# backend layer: encode->align (pre-collective) / decode (post-collective)
# ---------------------------------------------------------------------------


def _encode_align(flat: jax.Array, axes, shift: int, cfg: AggConfig, backend: str):
    """flat (N,) packed FP -> (man (N,) int32 aligned to the cross-worker
    block exponent and pre-shifted by ``shift``, bmax (N/block,) int32).

    Runs the tiny per-block max-exponent pmax internally (it must sit between
    the local extract and the final alignment). The pallas backend does the
    extract+local-align in ONE fused HBM pass and finishes with the residual
    per-element shift, which XLA fuses into the wire cast; the jnp backend is
    the reference formulation. Both are bit-identical (shift composition)."""
    if backend == "pallas":
        x2 = flat.reshape(-1, cfg.block)
        man_local, local_bmax = fpisa_fused.fused_encode_align(
            x2, fmt_name=cfg.fmt_name, interpret=_interpret())
        bmax = lax.pmax(local_bmax, axes)
        man = nx.arshift(man_local, (bmax - local_bmax)[:, None] + shift)
        return man.reshape(-1), bmax
    planes = fpisa.encode(flat, cfg.fmt)
    local_bmax = fpisa.block_max_exponent(planes.exp, cfg.block)
    bmax = lax.pmax(local_bmax, axes)
    be = jnp.repeat(bmax, cfg.block, axis=-1)
    man = nx.arshift(planes.man, (be - planes.exp) + shift)
    return man, bmax


def _decode(man_sum: jax.Array, bmax: jax.Array, shift: int, cfg: AggConfig,
            backend: str):
    """(N,) aggregated mantissas (any wire dtype) + (N/block,) block exps ->
    (N,) packed FP via delayed renormalization."""
    if backend == "pallas":
        out2 = fpisa_fused.fused_decode(
            man_sum.reshape(-1, cfg.block), bmax, preshift=shift,
            fmt_name=cfg.fmt_name, interpret=_interpret())
        return out2.reshape(-1)
    return fpisa.block_decode(man_sum.astype(jnp.int32), bmax, cfg.block, shift, cfg.fmt)


def _axis_size(axis_names: Sequence[str]) -> int:
    return math.prod(lax.axis_size(a) for a in axis_names)


def _flatten_pad(x: jax.Array, block: int):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def _unflatten(flat: jax.Array, pad: int, shape, dtype):
    if pad:
        flat = flat[: flat.shape[0] - pad]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# native
# ---------------------------------------------------------------------------


def native_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    return lax.psum(x, tuple(axis_names))


# ---------------------------------------------------------------------------
# SwitchML baseline
# ---------------------------------------------------------------------------


def _pow2(e) -> jax.Array:
    """Exact float32 2^e for integer e in [-126, 127], by bit assembly.

    ``jnp.exp2`` is off by ulps for |e| >~ 64 on some XLA CPU backends, which
    silently breaks exact power-of-two rescaling; building the exponent field
    directly is exact by construction."""
    return nx.bitcast_i32_to_f32((jnp.asarray(e, jnp.int32) + 127) << 23)


def switchml_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    """Fixed-point aggregation with a per-chunk scale-factor round trip.

    Mirrors SwitchML's host logic: chunk c uses scale 2^(man_bits) / 2^e_max(c)
    where e_max is agreed via a *separate collective round* (the overhead FPISA
    eliminates). Values are quantized to ints, int-psum'd, dequantized.

    The scale exponent k = man_bits - s - (e_max - bias) reaches +-~150 at the
    exponent extremes, past float32's 2^+-126 — a single ``exp2(k)`` factor
    goes inf for blocks whose max is a small normal (flushing them to zero
    through inf/NaN laundering), and ``exp2`` itself is not even exact for
    |k| >~ 64 on some XLA backends. The scale is therefore applied as two
    bit-assembled power-of-two half-factors (exact by construction), so every
    multiply is an exact scaling and in-range blocks quantize identically to
    the ideal single-factor formulation. All-zero / all-denormal blocks
    (e_max == 0) have no finite scale and quantize to exactly 0 by definition
    (see tests/test_wire_edges.py).
    """
    axes = tuple(axis_names)
    w = _axis_size(axes)
    fmt = cfg.fmt
    orig_shape, orig_dtype = x.shape, x.dtype
    flat, pad = _flatten_pad(x.astype(jnp.float32), cfg.block)

    planes = fpisa.encode(flat, fmt)
    local_bmax = fpisa.block_max_exponent(planes.exp, cfg.block)
    # ---- round 1: max-exponent agreement (extra RTT in SwitchML) ----
    bmax = lax.pmax(local_bmax, axes)

    # quantize: x / 2^(bmax - bias) * 2^(man_bits - s); s guards the int32 sum
    s = nx.required_preshift(w, fmt)
    be = jnp.repeat(bmax, cfg.block, axis=-1)
    k = (fmt.man_bits - s) - (be - fmt.bias)
    k1 = k // 2
    k2 = k - k1
    live = be > 0
    q = jnp.where(
        live, jnp.round((flat * _pow2(k1)) * _pow2(k2)), 0.0,
    ).astype(jnp.int32)
    # ---- round 2: integer aggregation (the in-switch op) ----
    qsum = lax.psum(q, axes)
    out = jnp.where(
        live, (qsum.astype(jnp.float32) * _pow2(-k1)) * _pow2(-k2), 0.0)
    return _unflatten(out, pad, orig_shape, orig_dtype)


# ---------------------------------------------------------------------------
# FPISA production path
# ---------------------------------------------------------------------------


def _check_wire_capacity(w: int, wire_bits: int) -> None:
    """No shift can make a narrow wire safe beyond w = 2^(wire_bits - 1)
    summands: the arithmetic right shift floors every negative mantissa at -1
    (round toward -inf), so a same-signed reduction can always reach -w —
    past the wire dtype's negative rail once w exceeds it. Refused loudly
    rather than silently wrapping (see tests/test_wire_edges.py)."""
    if wire_bits < 32 and w > 1 << (wire_bits - 1):
        raise ValueError(
            f"wire_bits={wire_bits} cannot carry a {w}-way sum: negative "
            f"mantissas floor at -1 under the arithmetic pre-shift, so the "
            f"reduction can reach -{w} < -2^{wire_bits - 1}")


def _wire_shift(fmt: fpisa.FpFormat, w: int, wire_bits: int) -> int:
    """Extra right-shift so each aligned mantissa fits in `wire_bits` signed
    ints AND the integer sum over w workers cannot overflow the wire dtype
    during an associative reduction (DESIGN.md §2)."""
    s = nx.required_preshift(w, fmt)
    if wire_bits >= 32:
        return s
    _check_wire_capacity(w, wire_bits)
    # element magnitude < 2^(man_bits + 1 - total_shift); need the *sum* to fit:
    # w * 2^(man_bits + 1 - t) <= 2^(wire_bits - 1)
    t = fmt.man_bits + 1 + math.ceil(math.log2(max(w, 1))) - (wire_bits - 1)
    return max(s, t)


_PACKED = {"fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16}


def _wire_cast(man: jax.Array, wire_bits: int) -> jax.Array:
    """Cast a mantissa plane to the wire element dtype (lossless: the wire
    shift guarantees every value — and every partial sum — fits)."""
    if wire_bits == 16:
        return man.astype(jnp.int16)
    if wire_bits == 8:
        return man.astype(jnp.int8)
    return man


def fpisa_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    """The paper's aggregation mapped to TPU collectives (see module doc).

    The input is handled in the *format's* packed dtype — aggregating bf16
    gradients with ``fmt_name='bf16'`` never materializes an f32 copy and
    its mantissa planes fit int16 natively (9-bit magnitude + headroom)."""
    axes = tuple(axis_names)
    w = _axis_size(axes)
    fmt = cfg.fmt
    backend = resolve_backend(cfg.backend)
    orig_shape, orig_dtype = x.shape, x.dtype
    shift = _wire_shift(fmt, w, cfg.wire_bits)
    with _trace.scope("agg.encode"):
        flat, pad = _flatten_pad(x.astype(_PACKED[cfg.fmt_name]), cfg.block)
        # The per-block max-exponent pmax inside _encode_align is a tiny
        # collective: one int per block (1/block of the data, and it can ride
        # in int8 on real hardware). Unlike SwitchML this is NOT a host round
        # trip; it pipelines with the mantissa pass chunk-by-chunk.
        man, bmax = _encode_align(flat, axes, shift, cfg, backend)
    with _trace.scope("agg.psum"):
        man_sum = lax.psum(_wire_cast(man, cfg.wire_bits), axes)
    with _trace.scope("agg.decode"):
        out = _decode(man_sum, bmax, shift, cfg, backend)
        return _unflatten(out, pad, orig_shape, orig_dtype)


def _hier_collect(man: jax.Array, data_axis: str, pod_axis: str,
                  cfg: AggConfig, shift: int):
    """Two-level integer collective: in-pod reduce-scatter + cross-pod psum.

    Returns (man_shard, pod_shift). Split out of the monolithic hierarchical
    path so the bucketer's double-buffered dispatch (core/bucketer.py) can
    overlap this phase with the encode of the next bucket.
    """
    fmt = cfg.fmt
    w_data = lax.axis_size(data_axis)
    w_pod = lax.axis_size(pod_axis)
    # level 1: in-pod reduce-scatter (int32 wire on ICI)
    man_shard = lax.psum_scatter(man, data_axis, scatter_dimension=0, tiled=True)
    # level 2: cross-pod integer psum, optionally narrow wire. The in-pod
    # partial sums carry up to man_bits+1+log2(w_data) magnitude bits; a
    # narrower cross-pod wire requires one extra truncating shift, applied
    # ONCE, after the full-precision in-pod reduction (optimal ordering:
    # precision is only given up on the expensive hop).
    pod_bits = cfg.pod_wire_bits or cfg.wire_bits
    pod_shift = 0
    if pod_bits < 32:
        # same floor-at--1 rail as _wire_shift, for the cross-pod summand count
        _check_wire_capacity(w_pod, pod_bits)
        partial_mag_bits = (fmt.man_bits + 1 - shift) + math.ceil(math.log2(max(w_data, 1)))
        pod_shift = max(0, partial_mag_bits + math.ceil(math.log2(max(w_pod, 1))) - (pod_bits - 1))
        man_shard = nx.arshift(man_shard, pod_shift)
        if pod_bits == 16:
            man_shard = man_shard.astype(jnp.int16)
        elif pod_bits == 8:
            man_shard = man_shard.astype(jnp.int8)
    man_shard = lax.psum(man_shard, pod_axis)
    return man_shard, pod_shift


def _hier_finish(man_shard: jax.Array, bmax: jax.Array, shift: int,
                 pod_shift: int, data_axis: str, cfg: AggConfig, backend: str):
    """Delayed renorm on the owned shard only, then gather packed FP."""
    w_data = lax.axis_size(data_axis)
    idx = lax.axis_index(data_axis)
    blocks_per_shard = bmax.shape[0] // w_data
    bmax_shard = lax.dynamic_slice_in_dim(bmax, idx * blocks_per_shard, blocks_per_shard)
    out_shard = _decode(man_shard, bmax_shard, shift + pod_shift, cfg, backend)
    return lax.all_gather(out_shard, data_axis, axis=0, tiled=True)


def fpisa_allreduce_hierarchical(
    x: jax.Array,
    data_axis: str,
    pod_axis: str,
    cfg: AggConfig,
):
    """Two-level FPISA aggregation for the multi-pod mesh.

    In-pod (ICI, cheap): reduce_scatter int32 mantissas over `data`.
    Cross-pod (DCI, expensive): psum over `pod`, optionally narrower wire.
    In-pod: all_gather the renormalized result.
    Exponent agreement is global (pmax over both axes) so mantissa scales are
    compatible across levels; the sum stays in integer domain end-to-end and
    renormalization happens ONCE (delayed, as in the paper).
    """
    w_data = lax.axis_size(data_axis)
    w_pod = lax.axis_size(pod_axis)
    w = w_data * w_pod
    fmt = cfg.fmt
    backend = resolve_backend(cfg.backend)
    orig_shape, orig_dtype = x.shape, x.dtype
    shift = _wire_shift(fmt, w, cfg.wire_bits)
    with _trace.scope("agg.encode"):
        # pad to block * w_data so reduce_scatter tiles evenly
        quantum = cfg.block * w_data
        flat = x.reshape(-1).astype(_PACKED[cfg.fmt_name])
        pad = (-flat.shape[0]) % quantum
        if pad:
            flat = jnp.pad(flat, (0, pad))
        # exponent agreement is global (pmax over both axes) so mantissa
        # scales are compatible across both reduction levels
        man, bmax = _encode_align(flat, (data_axis, pod_axis), shift, cfg, backend)
    with _trace.scope("agg.psum"):
        man_shard, pod_shift = _hier_collect(man, data_axis, pod_axis, cfg, shift)
    with _trace.scope("agg.decode"):
        out = _hier_finish(man_shard, bmax, shift, pod_shift, data_axis, cfg, backend)
        return _unflatten(out, pad, orig_shape, orig_dtype)


# ---------------------------------------------------------------------------
# bit-faithful sequential variant (accuracy experiments)
# ---------------------------------------------------------------------------


def fpisa_seq_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    axes = tuple(axis_names)
    stacked = lax.all_gather(x.astype(jnp.float32).reshape(-1), axes)
    stacked = stacked.reshape(-1, x.size)
    out = fpisa.fpisa_sum_sequential(stacked, cfg.fmt, variant="fpisa_a")
    return out.reshape(x.shape).astype(x.dtype)


def switch_emu_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    """Validation strategy: all_gather the per-worker shards, then run the
    real gradient through the batched switch-dataplane emulator on the host
    (``jax.pure_callback``). Exercises the full protocol machinery — slot
    claim/recycle, bitmaps, packetized streaming window — on a lossless
    fabric, so the result is bit-identical to ``fpisa_seq`` (worker-major
    arrival order per chunk). See repro/switchsim/dataplane.py.

    With ``cfg.switch_shared`` set, the traffic instead rides the named
    process-shared multi-tenant dataplane as tenant ``cfg.switch_job`` of
    ``cfg.switch_jobs`` — several jobs' aggregators (plus query streams)
    then contend for one emulated switch with QoS-aware slot admission
    (repro/switchsim/tenancy.py, DESIGN.md §10). The aggregated bits are
    unchanged: a lossless fabric delivers every result regardless of how
    admission interleaves the claims."""
    if cfg.fmt_name != "fp32":
        raise ValueError(
            "switch_emu runs on the jax-free numpy dataplane, which is "
            f"fp32-only; got fmt_name={cfg.fmt_name!r}")
    axes = tuple(axis_names)
    w = _axis_size(axes)
    stacked = lax.all_gather(x.astype(jnp.float32).reshape(-1), axes)
    stacked = stacked.reshape(-1, x.size)

    def host(vals):
        from repro import switchsim

        # NumpyDataplane, NOT the jitted one: concurrent host callbacks that
        # re-enter jax deadlock the CPU client (see switchsim/npfpisa.py).
        if cfg.switch_shared is not None:
            return switchsim.shared_emulated_allreduce(
                cfg.switch_shared, np.asarray(vals),
                num_jobs=cfg.switch_jobs, job=cfg.switch_job)
        dp = switchsim.NumpyDataplane(switchsim.DataplaneConfig(
            num_workers=w, fmt_name="fp32", variant="fpisa_a"))
        return switchsim.run_aggregation(dp, np.asarray(vals)).astype(np.float32)

    out = jax.pure_callback(
        host, jax.ShapeDtypeStruct((x.size,), jnp.float32), stacked)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# stacked (logical-worker) aggregation — elastic fault tolerance (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# ``stacked_*`` variants reduce over a LEADING logical-worker axis as well as
# the mesh axes: x has shape (k, ...) where this shard hosts k of the job's
# W = k * mesh_size logical workers. The reduction over logical workers runs
# entirely in the integer domain (mantissa planes for fpisa, fixed-point for
# switchml, arrival-ordered planes for fpisa_seq/switch_emu), and the wire
# shift is derived from W — NOT the mesh size — so the aggregated bits are
# IDENTICAL for any distribution of the W workers over any mesh. That is the
# property elastic recovery rests on: after a host death the survivors re-mesh
# with k' > k workers per shard and the training trajectory continues bit-for-
# bit (runtime/controller.py, tests/test_recovery.py). ``native`` is provided
# for completeness but sums in float, which is grouping-sensitive — it does
# not carry the bit-identity guarantee.


def _stacked_rows(x: jax.Array, dtype) -> jax.Array:
    if x.ndim < 1:
        raise ValueError("stacked aggregation needs a leading worker axis")
    return x.reshape(x.shape[0], -1).astype(dtype)


def _encode_align_stacked(rows: jax.Array, axes, shift: int, cfg: AggConfig,
                          backend: str):
    """rows (k, Nb) packed FP -> (man (k, Nb) int32 aligned to the block
    exponent maxed across ALL W logical workers, bmax (Nb/block,) int32).

    The block max folds the local worker axis with ``jnp.max`` before the
    cross-shard ``pmax`` — max is associative, so the agreed exponent (and
    with it every aligned mantissa) is independent of the worker placement."""
    k, nb_elems = rows.shape
    nblocks = nb_elems // cfg.block
    if backend == "pallas":
        man_local, local_bmax = fpisa_fused.fused_encode_align(
            rows.reshape(-1, cfg.block), fmt_name=cfg.fmt_name,
            interpret=_interpret())
        local_bmax = local_bmax.reshape(k, nblocks)
        bmax = lax.pmax(jnp.max(local_bmax, axis=0), axes)
        man = nx.arshift(man_local.reshape(k, nblocks, cfg.block),
                         (bmax[None, :] - local_bmax)[:, :, None] + shift)
        return man.reshape(k, nb_elems), bmax
    planes = fpisa.encode(rows, cfg.fmt)
    local_bmax = fpisa.block_max_exponent(planes.exp, cfg.block)  # (k, nblocks)
    bmax = lax.pmax(jnp.max(local_bmax, axis=0), axes)
    be = jnp.repeat(bmax, cfg.block)[None, :]
    man = nx.arshift(planes.man, (be - planes.exp) + shift)
    return man, bmax


def _stacked_pad(rows: jax.Array, quantum: int):
    pad = (-rows.shape[1]) % quantum
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return rows, pad


def stacked_native_allreduce(x, axis_names: Sequence[str], cfg: AggConfig):
    return lax.psum(jnp.sum(x, axis=0), tuple(axis_names))


def stacked_fpisa_allreduce(x, axis_names: Sequence[str], cfg: AggConfig):
    """FPISA aggregation over (leading logical-worker axis) + mesh axes.

    Each logical worker's mantissas are individually wire-cast (its packet
    payload), summed over the local workers in int32 — exact, every partial
    fits the wire dtype by the W-derived shift — then psum'd across shards.
    Integer addition is associative + commutative, so the result is bit-
    identical for every placement of the W workers. A 2-axis (pod, data) mesh
    is reduced jointly (flat): hierarchical striping is a routing choice and
    the flat integer sum is bit-identical to it at equal W."""
    axes = tuple(axis_names)
    k = x.shape[0]
    w = k * _axis_size(axes)
    backend = resolve_backend(cfg.backend)
    orig_shape, orig_dtype = x.shape[1:], x.dtype
    shift = _wire_shift(cfg.fmt, w, cfg.wire_bits)
    with _trace.scope("agg.encode"):
        rows, pad = _stacked_pad(_stacked_rows(x, _PACKED[cfg.fmt_name]), cfg.block)
        man, bmax = _encode_align_stacked(rows, axes, shift, cfg, backend)
    with _trace.scope("agg.psum"):
        man = _wire_cast(man, cfg.wire_bits)  # per-worker wire payloads
        local = _wire_cast(jnp.sum(man.astype(jnp.int32), axis=0), cfg.wire_bits)
        man_sum = lax.psum(local, axes)
    with _trace.scope("agg.decode"):
        out = _decode(man_sum, bmax, shift, cfg, backend)
        return _unflatten(out, pad, orig_shape, orig_dtype)


def stacked_switchml_allreduce(x, axis_names: Sequence[str], cfg: AggConfig):
    """SwitchML fixed-point aggregation with W logical workers (see
    ``switchml_allreduce`` for the scale-factor mechanics): per-worker
    quantization, exact int32 local fold, int psum — same invariance
    argument as ``stacked_fpisa_allreduce``."""
    axes = tuple(axis_names)
    k = x.shape[0]
    w = k * _axis_size(axes)
    fmt = cfg.fmt
    orig_shape, orig_dtype = x.shape[1:], x.dtype
    rows, pad = _stacked_pad(_stacked_rows(x, jnp.float32), cfg.block)

    planes = fpisa.encode(rows, fmt)
    local_bmax = fpisa.block_max_exponent(planes.exp, cfg.block)
    bmax = lax.pmax(jnp.max(local_bmax, axis=0), axes)

    s = nx.required_preshift(w, fmt)
    be = jnp.repeat(bmax, cfg.block)  # (Nb,)
    kexp = (fmt.man_bits - s) - (be - fmt.bias)
    k1 = kexp // 2
    k2 = kexp - k1
    live = be > 0
    q = jnp.where(live[None, :],
                  jnp.round((rows * _pow2(k1)[None, :]) * _pow2(k2)[None, :]),
                  0.0).astype(jnp.int32)
    qsum = lax.psum(jnp.sum(q, axis=0), axes)
    out = jnp.where(
        live, (qsum.astype(jnp.float32) * _pow2(-k1)) * _pow2(-k2), 0.0)
    return _unflatten(out, pad, orig_shape, orig_dtype)


def _gather_logical(x, axes):
    """(k, ...) per-shard stacks -> (W, N) rows in logical-worker order.

    Logical workers are assigned to shards contiguously (shard d hosts
    workers [d*k, (d+1)*k)), so the device-major all_gather concatenation IS
    the logical order — on every mesh size."""
    k = x.shape[0]
    rows = x.astype(jnp.float32).reshape(k, -1)
    return lax.all_gather(rows, axes).reshape(-1, rows.shape[-1])


def stacked_fpisa_seq_allreduce(x, axis_names: Sequence[str], cfg: AggConfig):
    stacked = _gather_logical(x, tuple(axis_names))
    out = fpisa.fpisa_sum_sequential(stacked, cfg.fmt, variant="fpisa_a")
    return out.reshape(x.shape[1:]).astype(x.dtype)


def stacked_switch_emu_allreduce(x, axis_names: Sequence[str], cfg: AggConfig):
    """Validation strategy with W logical switch ports: the gathered per-
    worker gradients stream through the numpy dataplane exactly as in
    ``switch_emu_allreduce`` — arrival order is logical-worker-major, i.e.
    identical on every mesh, so kill-and-resume trajectories stay bit-exact
    even under the full protocol emulation."""
    if cfg.fmt_name != "fp32":
        raise ValueError(
            "switch_emu runs on the jax-free numpy dataplane, which is "
            f"fp32-only; got fmt_name={cfg.fmt_name!r}")
    if cfg.switch_shared is not None:
        raise ValueError(
            "switch_shared tenancy is wired for the flat switch_emu path; "
            "the stacked (elastic logical-worker) variant does not support "
            "a shared dataplane")
    axes = tuple(axis_names)
    w = x.shape[0] * _axis_size(axes)
    n = math.prod(x.shape[1:]) if x.ndim > 1 else 1
    stacked = _gather_logical(x, axes)

    def host(vals):
        from repro import switchsim

        dp = switchsim.NumpyDataplane(switchsim.DataplaneConfig(
            num_workers=w, fmt_name="fp32", variant="fpisa_a"))
        return switchsim.run_aggregation(dp, np.asarray(vals)).astype(np.float32)

    out = jax.pure_callback(
        host, jax.ShapeDtypeStruct((n,), jnp.float32), stacked)
    return out.reshape(x.shape[1:]).astype(x.dtype)


# ---------------------------------------------------------------------------
# split-phase pipeline factories (bucketer hooks, DESIGN.md §3/§5)
# ---------------------------------------------------------------------------


def _fpisa_flat_phases(axes, cfg: AggConfig, backend: str):
    """(encode, collect, finish) for the flat single-level fpisa path —
    mirrors ``fpisa_allreduce`` exactly (bucket buffers are already block
    multiples, so its pad step is a no-op here)."""
    w = _axis_size(axes)
    shift = _wire_shift(cfg.fmt, w, cfg.wire_bits)

    def encode(flat):
        with _trace.scope("agg.encode"):
            man, bmax = _encode_align(flat, axes, shift, cfg, backend)
        with _trace.scope("agg.psum"):
            return _wire_cast(man, cfg.wire_bits), bmax

    def collect(state):
        man, bmax = state
        with _trace.scope("agg.psum"):
            return lax.psum(man, axes), bmax

    def finish(state):
        man_sum, bmax = state
        with _trace.scope("agg.decode"):
            return _decode(man_sum, bmax, shift, cfg, backend)

    return encode, collect, finish


def _fpisa_hier_phases(data_axis, pod_axis, cfg: AggConfig, backend: str,
                       stripe: int):
    """(encode, collect, finish) for the hierarchical fpisa path.

    ``stripe`` rotates the in-pod reduce-scatter shard assignment of this
    bucket by whole shards (a block-multiple roll): bucket i's cross-pod hop
    and delayed renorm for any given gradient range land on data-rank
    (rank + i) % w_data, striping consecutive buckets' DCI traffic across the
    pod axis's uplinks. Rolling by whole shards keeps every block's contents
    intact, so the result is bit-identical to the unstriped path.
    """
    w_data = lax.axis_size(data_axis)
    w_pod = lax.axis_size(pod_axis)
    shift = _wire_shift(cfg.fmt, w_data * w_pod, cfg.wire_bits)
    quantum = cfg.block * w_data

    def encode(flat):
        with _trace.scope("agg.encode"):
            pad = (-flat.shape[0]) % quantum
            if pad:
                flat = jnp.pad(flat, (0, pad))
            roll = (stripe % w_data) * (flat.shape[0] // w_data)
            if roll:
                flat = jnp.roll(flat, -roll)
            man, bmax = _encode_align(
                flat, (data_axis, pod_axis), shift, cfg, backend)
            return man, bmax, pad, roll

    def collect(state):
        man, bmax, pad, roll = state
        with _trace.scope("agg.psum"):
            man_shard, pod_shift = _hier_collect(man, data_axis, pod_axis, cfg, shift)
        return man_shard, bmax, pod_shift, pad, roll

    def finish(state):
        man_shard, bmax, pod_shift, pad, roll = state
        with _trace.scope("agg.decode"):
            out = _hier_finish(man_shard, bmax, shift, pod_shift, data_axis,
                               cfg, backend)
            if roll:
                out = jnp.roll(out, roll)
            if pad:
                out = out[:out.shape[0] - pad]
            return out

    return encode, collect, finish


def _fpisa_stacked_phases(axes, cfg: AggConfig, backend: str, k: int):
    """(encode, collect, finish) for the stacked flat fpisa path — mirrors
    ``stacked_fpisa_allreduce``: per-worker encode + exact local int fold
    before the wire, W-derived shift, one delayed renorm after the psum."""
    w = k * _axis_size(axes)
    shift = _wire_shift(cfg.fmt, w, cfg.wire_bits)

    def encode(buf):  # (k, elems) packed FP
        with _trace.scope("agg.encode"):
            man, bmax = _encode_align_stacked(buf, axes, shift, cfg, backend)
        with _trace.scope("agg.psum"):
            man = _wire_cast(man, cfg.wire_bits)
            local = _wire_cast(jnp.sum(man.astype(jnp.int32), axis=0),
                               cfg.wire_bits)
            return local, bmax

    def collect(state):
        man, bmax = state
        with _trace.scope("agg.psum"):
            return lax.psum(man, axes), bmax

    def finish(state):
        man_sum, bmax = state
        with _trace.scope("agg.decode"):
            return _decode(man_sum, bmax, shift, cfg, backend)

    return encode, collect, finish


# ---------------------------------------------------------------------------
# registry (repro.core.agg) — the declarative strategy table. Capability
# flags are validated once at Aggregator construction; the bucketer pulls the
# split-phase pipeline hooks and staging dtypes from the same specs.
# ---------------------------------------------------------------------------


def _validate_switch_emu(cfg: AggConfig) -> None:
    if cfg.fmt_name != "fp32":
        raise ValueError(
            "switch_emu runs on the jax-free numpy dataplane, which is "
            f"fp32-only; got fmt_name={cfg.fmt_name!r}")


def _stage_native(cfg: AggConfig, group: str):
    return jnp.dtype(group)  # native psums in the leaf dtype


def _stage_packed(cfg: AggConfig, group: str):
    return _PACKED[cfg.fmt_name]


register_strategy(
    "native", stacked=stacked_native_allreduce, chunk_noop=True,
    stage_dtype=_stage_native,
    description="plain float psum — the no-switch baseline",
)(native_allreduce)

register_strategy(
    "switchml", stacked=stacked_switchml_allreduce,
    description="SwitchML int32 fixed-point with a scale-factor round trip",
)(switchml_allreduce)

register_strategy(
    "fpisa", stacked=stacked_fpisa_allreduce,
    hierarchical=fpisa_allreduce_hierarchical,
    stage_dtype=_stage_packed,
    flat_phases=_fpisa_flat_phases, hier_phases=_fpisa_hier_phases,
    stacked_phases=_fpisa_stacked_phases,
    description="the paper's block-exponent integer planes (production path)",
)(fpisa_allreduce)

register_strategy(
    "fpisa_seq", stacked=stacked_fpisa_seq_allreduce,
    description="bit-faithful sequential switch-arrival FPISA-A",
)(fpisa_seq_allreduce)

register_strategy(
    "switch_emu", stacked=stacked_switch_emu_allreduce,
    requires_host_callback=True, validate=_validate_switch_emu,
    description="validation via the batched switch-dataplane emulator",
)(switch_emu_allreduce)


# ---------------------------------------------------------------------------
# deprecation shims — the legacy module-level surface. They delegate to the
# Aggregator facade unchanged (same dispatch, bit for bit) and warn with the
# CALLER attributed (stacklevel), so the suite can refuse in-tree use while
# out-of-tree users keep working. New code: repro.core.agg.Aggregator.
# ---------------------------------------------------------------------------


def _facade_shim_warn(name: str) -> None:
    warnings.warn(
        f"repro.core.allreduce.{name}() is deprecated; construct a "
        f"repro.core.agg.Aggregator once and call its methods instead",
        DeprecationWarning, stacklevel=3)


def allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    """Deprecated shim: ``Aggregator(cfg, axis_names).allreduce(x)``."""
    _facade_shim_warn("allreduce")
    return _agg.Aggregator(cfg, axis_names).allreduce(x)


def allreduce_tree(tree, axis_names: Sequence[str], cfg: AggConfig):
    """Deprecated shim: ``Aggregator(cfg, axis_names).allreduce_tree(tree)``."""
    _facade_shim_warn("allreduce_tree")
    return _agg.Aggregator(cfg, axis_names).allreduce_tree(tree)


def stacked_allreduce(x: jax.Array, axis_names: Sequence[str], cfg: AggConfig):
    """Deprecated shim: ``Aggregator(cfg, axis_names, stacked=True)
    .allreduce(x)`` (leading logical-worker axis, see section doc)."""
    _facade_shim_warn("stacked_allreduce")
    if cfg.chunk_elems:
        # preserved shim behavior: the facade refuses this at construction
        # with ValueError; the legacy function raised NotImplementedError
        raise NotImplementedError(
            "chunk_elems is not supported with stacked (logical-worker) "
            "aggregation; use bucket_bytes to bound transient memory instead")
    return _agg.Aggregator(cfg, axis_names, stacked=True).allreduce(x)


def stacked_allreduce_tree(tree, axis_names: Sequence[str], cfg: AggConfig):
    """Deprecated shim: ``Aggregator(cfg, axis_names, stacked=True)
    .allreduce_tree(tree)``."""
    _facade_shim_warn("stacked_allreduce_tree")
    return _agg.Aggregator(cfg, axis_names, stacked=True).allreduce_tree(tree)
