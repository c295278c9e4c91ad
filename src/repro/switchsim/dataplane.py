"""Vectorized multi-pipeline FPISA switch dataplane.

State model
-----------
A dataplane is ``num_pipelines`` independent ingress pipelines, each with
``2 * num_slots`` physical aggregation slots (SwitchML's double pool: a
completed slot keeps re-serving its cached result for a full window before
being recycled). All per-slot state is stacked into arrays over the global
slot axis ``G = num_pipelines * 2 * num_slots``:

* ``exp`` / ``man``  — (G, E) int32 FPISA accumulator planes,
* ``seen``           — (G, W) bool worker bitmap (idempotence),
* ``slot_chunk``     — (G,) owning chunk id (-1 = never claimed),
* ``result`` / ``result_valid`` — cached broadcast payload per completed slot.

Chunk ``c`` is striped across pipelines (``pipeline = c % P``) and lands in
physical slot ``(c // P) % (2 * num_slots)`` of that pipeline — with ``P = 1``
this is exactly the legacy ``core/switch.py`` mapping, which is what the
parity tests pin.

Batched ingest
--------------
``ingest_batch`` applies a batch of B packets with *per-slot sequential
semantics*: packets hitting the same slot are applied in batch order (FPISA
addition is order-dependent), while different slots proceed fully in
parallel. The trick is a rank/round decomposition computed inside the jit:

1. stable-sort packets by global slot id; the within-slot *rank* of each
   packet falls out of the sorted segment offsets;
2. scatter packet indices into a (G, rounds) table — round ``r`` holds at
   most one packet per slot;
3. ``lax.scan`` over rounds: each round is one fully vectorized pass of the
   slot state machine (stale drop / claim+reset / bitmap-gated FPISA add /
   completion + delayed renormalization / cached-result re-serve) over all
   G slots at once.

Packets whose rank exceeds ``rounds`` are reported as *deferred* (untouched);
the ``BatchedDataplane`` wrapper resubmits them in order, so any occupancy is
handled while the common case stays a single dispatch.

Pipeline/throughput model
-------------------------
Per-pipeline recirculation counters model the paper's Tofino limitation: the
``full`` (RSAW shift-any-operand) add variant costs one recirculation per
accepted packet — halving per-pipeline packet rate — while ``fpisa_a``
completes in a single pass (Sec. 4.3, 6.1). ``benchmarks/fig10_goodput.py``
turns these counters plus wall-clock packets/sec into the goodput figure.

Stats: ``packets`` (accepted adds), ``duplicates`` (bitmap hits),
``stale`` (retransmissions for an already-recycled slot — counted separately
from duplicates, unlike the pre-refactor emulator which conflated them),
``overwrite`` / ``overflow`` (element counts from the FPISA adds),
``reclaimed`` (in-flight slots freed by dead-worker reclamation, below), and
``recirculations`` per pipeline.

Worker-failure reclamation
--------------------------
A worker that dies mid-aggregation parks every slot still waiting on its
bitmap bit: completion requires all worker bits, so those slots would never
complete and the pool would leak. ``reclaim_worker`` is the control-plane
recovery op, invoked once a heartbeat timeout declares the worker dead (the
training runtime's ``HealthMonitor``; ``run_aggregation`` models the same
timeout with ``detect_rounds``):

* the worker is removed from the *live set* — completion henceforth requires
  only the live workers' bits, and late packets from the dead worker are
  dropped (counted under ``stale``);
* every **in-flight** slot (claimed, result not yet cached) is reset —
  accumulator planes zeroed, bitmap cleared — and counted in ``reclaimed``.
  Survivors still hold the shadow copies of their un-acked chunks (SwitchML's
  retransmission buffer), so their normal timeout retransmissions *resubmit*
  the reset slots from scratch and the chunk completes as a live-worker-only
  sum. Completed slots keep re-serving their cached full-worker results
  unchanged (those chunks finished before the death was declared).

All three dataplanes (batched jit, legacy per-packet shim, numpy) implement
the identical reclamation semantics; tests/test_recovery.py pins the parity.

Multi-tenancy (DESIGN.md §10)
-----------------------------
The switch is a *shared* in-network accelerator: ``num_jobs`` concurrent
tenants (training jobs, query streams, telemetry) ride one dataplane. Each
tenant j gets

* a **quota** ``job_slots[j]`` of logical slots per pipeline — its chunks
  stripe over a contiguous region of the double pool starting at
  ``2 * job_base(j)``; quotas that tile ``num_slots`` give disjoint
  (contention-free) partitions, while the default (every quota =
  ``num_slots``) fully overlaps the pool;
* a **weight** — when a claim attempt hits a *stale* slot owned by another
  tenant, a deterministic per-(slot, round) weighted lottery names the one
  tenant admitted to take it over this round (weighted admission);
* a **priority** — a higher-priority tenant may *preempt* a stale
  lower-priority **in-flight** window (accumulator discarded, victim's
  ``preempted`` counter bumped; the victim's workers simply resubmit once
  they win the slot back). Completed slots are never preempted: their cached
  results keep re-serving until the slot is recycled via the lottery, so
  preemption can never destroy a result a worker is still owed.

A slot is *stale* once no owner-job packet has touched it (claim, add, or
re-serve) for ``stale_after`` driver rounds — the round clock ``now`` is
supplied by the driver with each ingest, so all three dataplanes age slots
identically. Fresh foreign slots always deny the claim (``admission_denied``).
Counters, the live set, and reclamation are all per-job: ``reclaim_worker
(w, job=j)`` resets only in-flight slots *owned by job j*.

Single-tenant equivalence: with ``num_jobs=1`` every tenancy rule is
vacuous (there is no foreign owner), and with quotas that tile the pool and
no cross-tenant traffic every job sees exactly the single-tenant state
machine on its own slot region — both pinned bit-for-bit by
tests/test_multitenant.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import trace as _trace
from repro.core import fpisa
# the shared mirror contract — defined once in the package root (see the
# repro.switchsim module doc); re-exported here for legacy callers that
# spell switchsim.dataplane.COUNTERS
from repro.switchsim import COUNTERS, SLOT_STATE_FIELDS

_I_PACKETS, _I_DUP, _I_STALE, _I_OVERWRITE, _I_OVERFLOW, _I_RECLAIMED, \
    _I_DENIED, _I_PREEMPTED = range(len(COUNTERS))

# modulus/multipliers of the takeover lottery hash: a prime < 2**16 keeps
# every intermediate below 2**25, so the jnp (int32) and numpy planes compute
# the identical value with no overflow divergence
_LOTTERY_MOD = 65521
_LOTTERY_A, _LOTTERY_B, _LOTTERY_C = 257, 193, 11


@dataclasses.dataclass(frozen=True)
class DataplaneConfig:
    """Static shape/semantics of a batched dataplane (hashable: jit-static)."""

    num_workers: int
    num_slots: int = 8  # logical slots per pipeline (physical = 2x: double pool)
    elems_per_packet: int = 256
    fmt_name: str = "fp32"
    variant: str = "fpisa_a"  # fpisa_a | full
    num_pipelines: int = 1
    # max per-slot packets applied per ingest dispatch; 0 -> 2 * num_workers
    # (the worst case one driver round can produce under the window
    # discipline: W retransmissions of the completed chunk + W first packets
    # of the chunk recycling the slot). Overflow packets are deferred.
    rounds_per_call: int = 0
    # --- multi-tenancy (module doc / DESIGN.md §10) ---
    num_jobs: int = 1
    # per-job quota of logical slots per pipeline; None -> num_slots each
    # (fully shared pool). Quotas summing to num_slots tile the pool into
    # disjoint per-job partitions.
    job_slots: tuple[int, ...] | None = None
    # per-job QoS: priority orders in-flight preemption; weight biases the
    # stale-slot takeover lottery. None -> all equal.
    job_priorities: tuple[int, ...] | None = None
    job_weights: tuple[int, ...] | None = None
    # per-job port count (workers); None -> num_workers each. Job j's worker
    # ids live in [0, job_workers[j]); the rest are born non-live for it.
    job_workers: tuple[int, ...] | None = None
    # driver rounds without an owner-job touch before a slot counts as stale
    # (abandoned) and becomes claimable cross-job
    stale_after: int = 4

    @property
    def fmt(self):
        return fpisa.FORMATS[self.fmt_name]

    def _job_tuple(self, field, default) -> tuple[int, ...]:
        val = field if field is not None else (default,) * self.num_jobs
        assert len(val) == self.num_jobs, (val, self.num_jobs)
        return tuple(int(v) for v in val)

    @property
    def quotas(self) -> tuple[int, ...]:
        q = self._job_tuple(self.job_slots, self.num_slots)
        assert all(1 <= v <= self.num_slots for v in q), q
        return q

    @property
    def priorities(self) -> tuple[int, ...]:
        return self._job_tuple(self.job_priorities, 0)

    @property
    def weights(self) -> tuple[int, ...]:
        w = self._job_tuple(self.job_weights, 1)
        assert all(v >= 1 for v in w), w
        return w

    @property
    def ports(self) -> tuple[int, ...]:
        p = self._job_tuple(self.job_workers, self.num_workers)
        assert all(1 <= v <= self.num_workers for v in p), p
        return p

    @property
    def job_bases(self) -> tuple[int, ...]:
        """Logical-slot origin of each job's quota region (quotas tiling
        num_slots -> disjoint regions; full quotas -> everyone at 0)."""
        q, out, acc = self.quotas, [], 0
        for j in range(self.num_jobs):
            out.append(acc % self.num_slots)
            acc += q[j]
        return tuple(out)

    def job_window(self, job: int = 0) -> int:
        """Per-job streaming-window depth: its quota across all pipelines."""
        return self.quotas[job] * self.num_pipelines

    @property
    def physical_slots_per_pipeline(self) -> int:
        return 2 * self.num_slots

    @property
    def total_slots(self) -> int:
        return self.num_pipelines * self.physical_slots_per_pipeline

    @property
    def window(self) -> int:
        """Streaming-window depth in chunks (self-clocking: a worker may send
        chunk c only once it holds the result of c - window)."""
        return self.num_slots * self.num_pipelines

    @property
    def rounds(self) -> int:
        return self.rounds_per_call or 2 * self.num_workers


class DataplaneState(NamedTuple):
    exp: jax.Array  # (G, E) int32 accumulator exponent plane
    man: jax.Array  # (G, E) int32 accumulator mantissa plane
    seen: jax.Array  # (G, W) bool worker bitmap
    slot_chunk: jax.Array  # (G,) int32 chunk owning the slot; -1 = unclaimed
    result: jax.Array  # (G, E) packed-FP cached broadcast payload
    result_valid: jax.Array  # (G,) bool
    counters: jax.Array  # (J, len(COUNTERS)) int32 per-job counters
    recirc: jax.Array  # (P,) int32 per-pipeline recirculation counter
    live: jax.Array  # (J, W) bool — per-job live worker (port) set
    slot_job: jax.Array  # (G,) int32 owning job; -1 = never claimed
    last_touch: jax.Array  # (G,) int32 round of the last owner-job touch


# import-time mirror check: the jitted state layout IS the shared contract
# (the numpy mirror's attributes are checked the same way in its __init__,
# and tools/repro_lint's mirror-parity rule checks both statically)
assert DataplaneState._fields == SLOT_STATE_FIELDS, (
    DataplaneState._fields, SLOT_STATE_FIELDS)


def init_state(cfg: DataplaneConfig) -> DataplaneState:
    g, e = cfg.total_slots, cfg.elems_per_packet
    ports = np.asarray(cfg.ports)
    return DataplaneState(
        exp=jnp.zeros((g, e), jnp.int32),
        man=jnp.zeros((g, e), jnp.int32),
        seen=jnp.zeros((g, cfg.num_workers), bool),
        slot_chunk=jnp.full((g,), -1, jnp.int32),
        result=jnp.zeros((g, e), fpisa.PACKED_DTYPE[cfg.fmt_name]),
        result_valid=jnp.zeros((g,), bool),
        counters=jnp.zeros((cfg.num_jobs, len(COUNTERS)), jnp.int32),
        recirc=jnp.zeros((cfg.num_pipelines,), jnp.int32),
        live=jnp.asarray(np.arange(cfg.num_workers)[None, :] < ports[:, None]),
        slot_job=jnp.full((g,), -1, jnp.int32),
        last_touch=jnp.zeros((g,), jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def reclaim_dead_worker(state: DataplaneState, worker, job=0, *,
                        cfg: DataplaneConfig) -> DataplaneState:
    """Remove ``worker`` from ``job``'s live set and reset every in-flight
    slot **owned by that job** (module doc: Worker-failure reclamation).
    Other tenants' slots, live sets, and counters are untouched. Idempotent:
    reclaiming an already-dead worker is a no-op."""
    was_live = state.live[job, worker]
    inflight = (was_live & (state.slot_chunk >= 0) & ~state.result_valid
                & (state.slot_job == job))
    return state._replace(
        exp=jnp.where(inflight[:, None], 0, state.exp),
        man=jnp.where(inflight[:, None], 0, state.man),
        seen=jnp.where(inflight[:, None], False, state.seen),
        live=state.live.at[job, worker].set(False),
        counters=state.counters.at[job, _I_RECLAIMED].add(
            jnp.sum(inflight).astype(jnp.int32)),
    )


def slot_of(cfg: DataplaneConfig, chunks):
    """Global slot id for each chunk id (pipeline striping + double pool) —
    the single-tenant mapping, identical to ``slot_of_tenant`` with job 0 and
    a full quota."""
    pipe = chunks % cfg.num_pipelines
    slot = (chunks // cfg.num_pipelines) % cfg.physical_slots_per_pipeline
    return pipe * cfg.physical_slots_per_pipeline + slot


def slot_of_tenant(cfg: DataplaneConfig, jobs, chunks, xp=np):
    """Global slot id under per-job quota striping: job j's chunk stream
    wraps over the ``2 * quotas[j]`` physical slots starting at
    ``2 * job_bases[j]`` of its pipeline. With a full quota (base 0) this is
    exactly ``slot_of`` — the single-tenant parity anchor."""
    phys = cfg.physical_slots_per_pipeline
    q = xp.asarray(cfg.quotas)[jobs]
    base = xp.asarray(cfg.job_bases)[jobs]
    pipe = chunks % cfg.num_pipelines
    idx = (chunks // cfg.num_pipelines) % (2 * q)
    return pipe * phys + (2 * base + idx) % phys


def lottery_pref(cfg: DataplaneConfig, now, xp=np):
    """(G,) preferred tenant per slot for round ``now`` — the weighted
    admission lottery for stale-slot takeovers. A pure function of
    (slot, round, weights): order-free within a round and bit-identical
    across the jnp and numpy dataplanes (int32-safe modular hash)."""
    weights = cfg.weights
    g = xp.arange(cfg.total_slots, dtype=xp.int32)
    h = ((g % _LOTTERY_MOD) * _LOTTERY_A + (now % _LOTTERY_MOD) * _LOTTERY_B
         + _LOTTERY_C) % _LOTTERY_MOD
    cumw = xp.asarray(np.cumsum(weights, dtype=np.int32))
    return xp.searchsorted(cumw, h % sum(weights), side="right").astype(xp.int32)


def _rank_table(key, valid, num_keys: int, rounds: int):
    """Scatter packet indices into a (num_keys, rounds) table such that column
    r holds (at most) the r-th packet, in batch order, of every key.

    Returns (table int32 with -1 for empty cells, deferred bool mask over the
    batch marking packets whose within-key rank >= rounds)."""
    b = key.shape[0]
    key = jnp.where(valid, key, num_keys)  # invalid -> sentinel, dropped below
    order = jnp.argsort(key)  # stable: preserves batch order within a key
    ks = key[order]
    first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    seg_start = jnp.where(first, jnp.arange(b), 0)
    seg_start = lax.associative_scan(jnp.maximum, seg_start)
    rank = jnp.arange(b) - seg_start

    fits = (ks < num_keys) & (rank < rounds)
    table = jnp.full((num_keys, rounds), -1, jnp.int32)
    table = table.at[
        jnp.where(fits, ks, num_keys), jnp.where(fits, rank, 0)
    ].set(order.astype(jnp.int32), mode="drop")
    deferred = jnp.zeros((b,), bool).at[order].set((ks < num_keys) & (rank >= rounds))
    return table, deferred


@functools.partial(jax.jit, static_argnames=("cfg", "rounds"))
def ingest_batch(state: DataplaneState, workers, chunks, payloads, valid,
                 jobs=None, now=0, *,
                 cfg: DataplaneConfig, rounds: int | None = None):
    """Apply a batch of packets to the dataplane (see module doc).

    Args:
      state:    DataplaneState.
      workers:  (B,) int32 worker ids in [0, num_workers).
      chunks:   (B,) int32 chunk ids.
      payloads: (B, E) float payloads.
      valid:    (B,) bool lane mask (padding lanes are ignored).
      jobs:     (B,) int32 tenant ids in [0, num_jobs); None -> all job 0.
      now:      scalar driver round (the staleness clock; traced, so driving
                it every round never recompiles).

    Returns ``(state, ready, results, accepted, deferred)`` where ``ready``
    marks packets answered with a broadcast payload (slot completion or
    idempotent re-serve of a completed chunk), ``results`` holds those
    payloads, ``accepted`` marks packets whose contribution was added (first
    arrival of a (worker, chunk)), and ``deferred`` marks packets not
    processed this call (per-slot rank overflow; resubmit in order).
    """
    g, w_n, b = cfg.total_slots, cfg.num_workers, workers.shape[0]
    rounds = rounds or cfg.rounds
    fmt = cfg.fmt
    add = fpisa.fpisa_a_add if cfg.variant == "fpisa_a" else fpisa.fpisa_add_full
    planes = fpisa.encode(payloads, fmt)
    if jobs is None:
        jobs = jnp.zeros((b,), jnp.int32)
    jobs = jnp.clip(jobs, 0, cfg.num_jobs - 1).astype(jnp.int32)

    table, deferred = _rank_table(
        slot_of_tenant(cfg, jobs, chunks, jnp), valid, g, rounds)
    lane_pipe = jnp.arange(g) // cfg.physical_slots_per_pipeline
    prio = jnp.asarray(cfg.priorities)
    pref = lottery_pref(cfg, now, jnp)  # constant across this call's rounds

    ready0 = jnp.zeros((b,), bool)
    results0 = jnp.zeros((b, cfg.elems_per_packet), fpisa.PACKED_DTYPE[cfg.fmt_name])
    accepted0 = jnp.zeros((b,), bool)

    def round_body(carry, pidx):
        st, ready, results, accepted = carry
        active = pidx >= 0
        pi = jnp.where(active, pidx, 0)
        wk, ck, jb = workers[pi], chunks[pi], jobs[pi]
        inp = fpisa.Planes(planes.exp[pi], planes.man[pi])

        cur = st.slot_chunk
        owner = st.slot_job
        owner_c = jnp.clip(owner, 0, cfg.num_jobs - 1)
        # packets from reclaimed (dead) workers are dropped like stale ones
        act = active & st.live[jb, wk]
        is_dead = active & ~st.live[jb, wk]
        free = cur < 0
        same = act & (free | (owner == jb))
        cross = act & ~free & (owner != jb)

        # same-tenant path: the classic single-tenant slot machine
        s_stale = same & (cur > ck)
        is_new = same & (cur < ck)  # includes free slots (cur = -1)
        s_dup = same & (cur == ck)

        # cross-tenant path: fresh slots deny; stale slots are claimable by
        # takeover (completed: weighted lottery, or higher priority) or
        # preemption (in-flight: higher priority, or equal priority winning
        # the lottery — keeps abandoned windows from deadlocking the slot)
        slot_stale = (now - st.last_touch) >= cfg.stale_after
        higher = prio[jb] > prio[owner_c]
        equal = prio[jb] == prio[owner_c]
        takeover = cross & st.result_valid & slot_stale & (higher | (pref == jb))
        preempt = (cross & ~st.result_valid & slot_stale
                   & (higher | (equal & (pref == jb))))
        denied = cross & ~(takeover | preempt)

        claim = is_new | takeover | preempt
        is_stale = is_dead | s_stale
        proceed = claim | s_dup

        # claim: reset the slot for the new (job, chunk) ownership
        seen = jnp.where(claim[:, None], False, st.seen)
        exp = jnp.where(claim[:, None], 0, st.exp)
        man = jnp.where(claim[:, None], 0, st.man)
        rvalid = jnp.where(claim, False, st.result_valid)
        slot_chunk = jnp.where(claim, ck, cur)
        slot_job = jnp.where(claim, jb, owner)
        # owner-job activity refreshes the staleness clock (claims, adds, and
        # re-serve dups); denied/stale/dead packets do not
        last_touch = jnp.where(proceed, now, st.last_touch)

        already = seen[jnp.arange(g), jnp.where(proceed, wk, 0)]
        is_dup = proceed & already
        do_add = proceed & ~already

        newp, addst = add(fpisa.Planes(exp, man), inp, fmt)
        exp = jnp.where(do_add[:, None], newp.exp, exp)
        man = jnp.where(do_add[:, None], newp.man, man)
        seen = seen | (do_add[:, None] & (jnp.arange(w_n)[None, :] == wk[:, None]))
        # completion requires every LIVE worker's bit of the packet's own
        # tenant (dead/unported bits are waived)
        complete = do_add & jnp.all(seen | ~st.live[jb], axis=1)

        # delayed renormalization only on rounds that complete a slot
        result, rvalid = lax.cond(
            jnp.any(complete),
            lambda r, rv: (
                jnp.where(complete[:, None],
                          fpisa.renormalize(fpisa.Planes(exp, man), fmt), r),
                rv | complete,
            ),
            lambda r, rv: (r, rv),
            st.result, rvalid,
        )

        serve = complete | (is_dup & rvalid)
        # most rounds serve nothing (completion needs rank == W-1): skip the
        # (G -> B, E) result scatter unless some lane actually answers
        ready, results = lax.cond(
            jnp.any(serve),
            lambda rd, rs: (
                # b = out-of-bounds sentinel: non-serving lanes are dropped
                rd.at[jnp.where(serve, pi, b)].set(True, mode="drop"),
                rs.at[jnp.where(serve, pi, b)].set(result, mode="drop"),
            ),
            lambda rd, rs: (rd, rs),
            ready, results,
        )
        accepted = accepted.at[jnp.where(do_add, pi, b)].set(True, mode="drop")

        # per-job counters: commutative scatter-adds keyed by the packet's
        # tenant (preempted is charged to the VICTIM), so batched/numpy/
        # per-packet stay order-independent and bit-identical
        i32 = lambda m: m.astype(jnp.int32)  # noqa: E731
        counters = st.counters
        counters = counters.at[jb, _I_PACKETS].add(i32(do_add))
        counters = counters.at[jb, _I_DUP].add(i32(is_dup))
        counters = counters.at[jb, _I_STALE].add(i32(is_stale))
        counters = counters.at[jb, _I_OVERWRITE].add(
            jnp.sum(jnp.where(do_add[:, None], addst.overwrite, False),
                    axis=1).astype(jnp.int32))
        counters = counters.at[jb, _I_OVERFLOW].add(
            jnp.sum(jnp.where(do_add[:, None], addst.overflow, False),
                    axis=1).astype(jnp.int32))
        counters = counters.at[jb, _I_DENIED].add(i32(denied))
        counters = counters.at[owner_c, _I_PREEMPTED].add(i32(preempt))
        # RSAW full-add costs one recirculation pass per accepted packet
        recirc = st.recirc
        if cfg.variant == "full":
            recirc = recirc + jax.ops.segment_sum(
                do_add.astype(jnp.int32), lane_pipe, num_segments=cfg.num_pipelines)

        st = DataplaneState(exp, man, seen, slot_chunk, result, rvalid,
                            counters, recirc, st.live, slot_job, last_touch)
        return (st, ready, results, accepted), None

    (state, ready, results, accepted), _ = lax.scan(
        round_body, (state, ready0, results0, accepted0), table.T)
    return state, ready, results, accepted, deferred


def _pow2ceil(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class BatchedDataplane:
    """Host-side handle: owns the device state, pads/submits numpy batches,
    resubmits deferred packets, and exposes legacy-style ``stats``.

    Jit specialization discipline: batches are padded to one of (at most) two
    fixed sizes and the per-slot round count is the power-of-two cover of the
    batch's actual max slot occupancy, capped at ``cfg.rounds`` — so the
    compile cache stays small and steady-state driving never recompiles."""

    def __init__(self, cfg: DataplaneConfig, max_batch: int | None = None):
        self.cfg = cfg
        self.state = init_state(cfg)
        # largest batch one driver round can produce under the window
        # discipline (every worker's full in-flight window)
        self.max_batch = max_batch or min(
            _pow2ceil(cfg.num_workers * cfg.window), 8192)
        self._sizes = sorted({min(256, self.max_batch), self.max_batch})

    def _pad_size(self, n: int) -> int:
        for s in self._sizes:
            if n <= s:
                return s
        return self.max_batch

    def ingest_batch(self, workers, chunks, payloads, jobs=None, now=0):
        """Process packets (numpy in/out). Returns (ready, results, accepted)
        aligned with the input batch; within-slot application order is the
        batch order, matching a sequential per-packet switch. ``jobs`` tags
        each packet with its tenant (None -> job 0); ``now`` is the driver's
        round clock for staleness aging."""
        workers = np.asarray(workers, np.int32)
        chunks = np.asarray(chunks, np.int32)
        payloads = np.asarray(payloads, np.float32).reshape(
            len(workers), self.cfg.elems_per_packet)
        b = len(workers)
        jobs_np = (np.zeros(b, np.int32) if jobs is None
                   else np.asarray(jobs, np.int32))
        ready = np.zeros(b, bool)
        results = np.zeros((b, self.cfg.elems_per_packet), np.float32)
        accepted = np.zeros(b, bool)
        gids = np.asarray(slot_of_tenant(
            self.cfg, jobs_np.astype(np.int64), chunks.astype(np.int64)))
        queue = np.arange(b)
        while queue.size:
            cur, queue = queue[: self.max_batch], queue[self.max_batch :]
            bp = self._pad_size(cur.size)
            occ = int(np.bincount(gids[cur]).max())
            rounds = min(_pow2ceil(occ), self.cfg.rounds)
            pad = bp - cur.size
            wk = np.pad(workers[cur], (0, pad))
            ck = np.pad(chunks[cur], (0, pad))
            jb = np.pad(jobs_np[cur], (0, pad))
            pl = np.pad(payloads[cur], ((0, pad), (0, 0)))
            vmask = np.arange(bp) < cur.size
            self.state, rdy, res, acc, dfr = ingest_batch(
                self.state, jnp.asarray(wk), jnp.asarray(ck), jnp.asarray(pl),
                jnp.asarray(vmask), jnp.asarray(jb), jnp.int32(now),
                cfg=self.cfg, rounds=rounds)
            rdy = np.asarray(rdy)[: cur.size]
            res = np.asarray(res, np.float32)[: cur.size]
            acc = np.asarray(acc)[: cur.size]
            dfr = np.asarray(dfr)[: cur.size]
            ready[cur[rdy]] = True
            results[cur[rdy]] = res[rdy]
            accepted[cur[acc]] = True
            # deferred packets (rank overflow) go back FIRST: they precede
            # everything not yet submitted in the original batch order
            if dfr.any():
                queue = np.concatenate([cur[dfr], queue])
        return ready, results, accepted

    def reclaim_worker(self, worker: int, job: int = 0):
        """Control-plane recovery: drop ``worker`` from ``job``'s live set and
        reset its parked in-flight slots (module doc). Survivor
        retransmissions resubmit the reset chunks from their shadow copies."""
        self.state = reclaim_dead_worker(
            self.state, jnp.int32(worker), jnp.int32(job), cfg=self.cfg)

    @property
    def stats(self) -> dict:
        """Legacy switch-wide stats: per-job counters summed over tenants."""
        c = np.asarray(self.state.counters).sum(axis=0)
        out = {name: int(c[i]) for i, name in enumerate(COUNTERS)}
        out["recirculations"] = np.asarray(self.state.recirc).tolist()
        return out

    @property
    def job_stats(self) -> list[dict]:
        """Per-tenant counters, one dict per job id."""
        c = np.asarray(self.state.counters)
        return [{name: int(c[j, i]) for i, name in enumerate(COUNTERS)}
                for j in range(self.cfg.num_jobs)]


class NumpyDataplane:
    """Jax-free dataplane with the exact same slot semantics and
    ``ingest_batch`` interface as ``BatchedDataplane`` (per-packet numpy loop
    over ``npfpisa`` primitives — bit-identical, tests pin it).

    Exists for contexts that must not re-enter jax — above all the
    ``switch_emu`` all-reduce strategy, whose host callback would deadlock
    the CPU PJRT client if it dispatched jitted computations (see
    npfpisa module doc). Also a handy pdb-able reference."""

    def __init__(self, cfg: DataplaneConfig):
        from repro.switchsim import npfpisa

        assert cfg.fmt_name == "fp32", "numpy dataplane is fp32-only"
        self.cfg = cfg
        self._np = npfpisa
        g, e = cfg.total_slots, cfg.elems_per_packet
        self._exp = np.zeros((g, e), np.int32)
        self._man = np.zeros((g, e), np.int32)
        self._seen = np.zeros((g, cfg.num_workers), bool)
        self._slot_chunk = np.full((g,), -1, np.int64)
        self._result = np.zeros((g, e), np.float32)
        self._result_valid = np.zeros((g,), bool)
        self._live = (np.arange(cfg.num_workers)[None, :]
                      < np.asarray(cfg.ports)[:, None])
        self._slot_job = np.full((g,), -1, np.int64)
        self._last_touch = np.zeros((g,), np.int64)
        self._counters = np.zeros((cfg.num_jobs, len(COUNTERS)), np.int64)
        self._recirc = [0] * cfg.num_pipelines
        # runtime half of the mirror contract (static half: repro-lint's
        # mirror-parity rule): one `_`-prefixed attribute per shared
        # slot-state field, so the two dataplanes cannot drift silently
        missing = [f for f in SLOT_STATE_FIELDS
                   if not hasattr(self, f"_{f}")]
        assert not missing, f"NumpyDataplane missing mirror fields {missing}"

    @property
    def stats(self) -> dict:
        """Legacy switch-wide stats: per-job counters summed over tenants."""
        c = self._counters.sum(axis=0)
        out = {name: int(c[i]) for i, name in enumerate(COUNTERS)}
        out["recirculations"] = list(self._recirc)
        return out

    @property
    def job_stats(self) -> list[dict]:
        """Per-tenant counters, one dict per job id."""
        return [{name: int(self._counters[j, i])
                 for i, name in enumerate(COUNTERS)}
                for j in range(self.cfg.num_jobs)]

    def reclaim_worker(self, worker: int, job: int = 0):
        """Same reclamation semantics as ``BatchedDataplane.reclaim_worker``:
        only slots owned by ``job`` are reset."""
        if not self._live[job, worker]:
            return
        self._live[job, worker] = False
        inflight = ((self._slot_chunk >= 0) & ~self._result_valid
                    & (self._slot_job == job))
        self._exp[inflight] = 0
        self._man[inflight] = 0
        self._seen[inflight] = False
        self._counters[job, _I_RECLAIMED] += int(inflight.sum())

    def ingest_batch(self, workers, chunks, payloads, jobs=None, now=0):
        cfg, F = self.cfg, self._np
        workers = np.asarray(workers, np.int64)
        chunks = np.asarray(chunks, np.int64)
        payloads = np.asarray(payloads, np.float32).reshape(
            len(workers), cfg.elems_per_packet)
        b = len(workers)
        jobs = (np.zeros(b, np.int64) if jobs is None
                else np.asarray(jobs, np.int64))
        add = F.fpisa_a_add if cfg.variant == "fpisa_a" else F.fpisa_add_full
        gids = np.asarray(slot_of_tenant(cfg, jobs, chunks))
        pref = lottery_pref(cfg, int(now), np)
        prio = cfg.priorities
        in_exp, in_man = F.encode(payloads)
        ready = np.zeros(b, bool)
        results = np.zeros((b, cfg.elems_per_packet), np.float32)
        accepted = np.zeros(b, bool)
        ct = self._counters
        for i in range(b):
            g, w, c, j = int(gids[i]), int(workers[i]), int(chunks[i]), int(jobs[i])
            if not self._live[j, w]:
                ct[j, _I_STALE] += 1
                continue
            cur, owner = self._slot_chunk[g], int(self._slot_job[g])
            if cur >= 0 and owner != j:
                # cross-tenant: deny fresh slots; stale ones fall to the
                # takeover lottery / priority preemption (jit round_body
                # mirrors these rules lane-wise)
                slot_stale = (int(now) - self._last_touch[g]) >= cfg.stale_after
                higher = prio[j] > prio[owner]
                equal = prio[j] == prio[owner]
                if self._result_valid[g]:
                    allowed = slot_stale and (higher or pref[g] == j)
                else:
                    allowed = slot_stale and (higher or (equal and pref[g] == j))
                    if allowed:
                        ct[owner, _I_PREEMPTED] += 1
                if not allowed:
                    ct[j, _I_DENIED] += 1
                    continue
                claim = True
            elif cur > c:
                ct[j, _I_STALE] += 1
                continue
            else:
                claim = cur < c
            if claim:  # reset the slot for the new (job, chunk) ownership
                self._slot_chunk[g] = c
                self._slot_job[g] = j
                self._seen[g] = False
                self._exp[g] = 0
                self._man[g] = 0
                self._result_valid[g] = False
            self._last_touch[g] = int(now)  # owner-job activity: not stale
            if self._seen[g, w]:
                ct[j, _I_DUP] += 1  # idempotent: do NOT re-add
                if self._result_valid[g]:
                    ready[i] = True
                    results[i] = self._result[g]
                continue
            self._seen[g, w] = True
            ct[j, _I_PACKETS] += 1
            e2, m2, over, ovf = add(self._exp[g], self._man[g], in_exp[i], in_man[i])
            self._exp[g], self._man[g] = e2, m2
            ct[j, _I_OVERWRITE] += int(over.sum())
            ct[j, _I_OVERFLOW] += int(ovf.sum())
            accepted[i] = True
            if cfg.variant == "full":
                self._recirc[g // cfg.physical_slots_per_pipeline] += 1
            if (self._seen[g] | ~self._live[j]).all():
                self._result[g] = F.renormalize(self._exp[g], self._man[g])
                self._result_valid[g] = True
                ready[i] = True
                results[i] = self._result[g]
        return ready, results, accepted


def run_aggregation(
    switch,
    worker_vectors: np.ndarray,
    drop_prob: float = 0.0,
    seed: int = 0,
    max_rounds: int = 10_000,
    record_arrivals: bool = False,
    fail_worker: int | None = None,
    fail_round: int | None = None,
    detect_rounds: int = 2,
    chunk_base: int = 0,
    job: int = 0,
    now_base: int = 0,
):
    """Batch-per-round all-reduce driver over an unreliable fabric.

    ``switch`` is a ``BatchedDataplane`` (one jitted dispatch per round: every
    eligible (worker, chunk) packet that survives the i.i.d. request drop) or
    any object with a legacy per-packet ``.ingest`` (``core.switch.FpisaSwitch``
    — same round-synchronous schedule, one packet at a time). Both paths
    consume the seeded RNG identically (request drops drawn as one vector per
    round, per-worker result-delivery drops drawn per completion in packet
    order), so for identical seeds the two are **bit-identical** end to end —
    the parity the fig10 benchmark and tests/test_switchsim.py pin.

    Eligibility is snapshotted at round start: worker w may send chunk c iff
    it lacks c's result and holds the result of c - window (SwitchML's
    self-clocked streaming window, which makes slot recycling safe).

    Returns the aggregated (N,) vector; with ``record_arrivals`` (batched
    path only) also a {chunk: [workers in acceptance order]} dict for
    replaying the exact switch-arrival order through the jnp reference.

    Fault injection: with ``fail_worker``/``fail_round`` set, that worker
    crashes at the start of that round — it stops sending, and no result
    delivery is owed to it. ``detect_rounds`` rounds later the control plane's
    heartbeat timeout fires and ``switch.reclaim_worker`` frees its parked
    slots; the survivors' normal retransmissions (their shadow copies) then
    resubmit the reset chunks and the aggregation completes as a live-worker
    sum. Chunks whose slots completed before the death keep the dead worker's
    contribution (their cached results are re-served unchanged). The fault
    path consumes the shared RNG stream identically for every switch type, so
    per-packet/batched/numpy runs stay bit-identical under injected failures.

    ``chunk_base`` offsets the on-wire chunk ids so one switch can carry many
    consecutive calls (e.g. one per training step) without its slot state
    going stale: chunk ids stay monotonic across calls, which is exactly the
    SwitchML recycling discipline. State carried over from the previous call
    is recycled naturally as the new chunks claim slots.

    ``job`` tags every packet with that tenant id on a multi-tenant switch
    (this driver streams ONE job's traffic; ``tenancy.run_multitenant``
    interleaves several). ``now_base`` offsets the staleness clock the same
    way ``chunk_base`` offsets chunk ids, so consecutive calls against a
    shared switch keep aging the other tenants' slots; the clock reached is
    left on ``switch.last_now``.
    """
    cfg = switch.cfg
    w, n = worker_vectors.shape
    ports = getattr(cfg, "ports", None)
    assert w == (ports[job] if ports is not None else cfg.num_workers)
    e = cfg.elems_per_packet
    if hasattr(cfg, "job_window"):
        window = cfg.job_window(job)
    else:
        window = cfg.num_slots * getattr(cfg, "num_pipelines", 1)
    pad = (-n) % e
    vecs = np.pad(worker_vectors, ((0, 0), (0, pad))).astype(np.float32)
    nchunks = vecs.shape[1] // e
    vecs3 = vecs.reshape(w, nchunks, e)
    rng = np.random.default_rng(seed)
    batched = hasattr(switch, "ingest_batch")

    out = np.zeros((nchunks, e), np.float32)
    have_result = np.zeros((w, nchunks), bool)
    arrivals: dict[int, list[int]] = {}

    sp = _trace.span("switchsim.run_aggregation", phase="switch",
                     workers=w, nchunks=nchunks, job=job,
                     batched=batched, drop_prob=drop_prob)
    with sp:
        rnd = _drive_rounds(
            switch, vecs3, out, have_result, arrivals, rng,
            drop_prob=drop_prob, max_rounds=max_rounds, window=window,
            record_arrivals=record_arrivals, fail_worker=fail_worker,
            fail_round=fail_round, detect_rounds=detect_rounds,
            chunk_base=chunk_base, job=job, now_base=now_base,
            batched=batched)
        if sp:
            sp.tag(rounds=rnd + 1)
    switch.last_now = now_base + rnd  # staleness clock for the next caller
    flat = out.reshape(-1)[:n]
    if record_arrivals:
        return flat, arrivals
    return flat


def _drive_rounds(switch, vecs3, out, have_result, arrivals, rng, *,
                  drop_prob, max_rounds, window, record_arrivals,
                  fail_worker, fail_round, detect_rounds, chunk_base, job,
                  now_base, batched):
    """The round-synchronous loop of ``run_aggregation`` (same RNG stream,
    split out so the driver's trace span wraps exactly the wire time)."""
    w, nchunks, e = vecs3.shape
    reclaim_at: int | None = None
    for rnd in range(max_rounds):
        if fail_round is not None and rnd == fail_round and fail_worker is not None:
            # the worker crashes: it stops sending and is owed no delivery
            have_result[fail_worker, :] = True
            reclaim_at = rnd + detect_rounds  # heartbeat timeout fires then
        if reclaim_at is not None and rnd >= reclaim_at:
            switch.reclaim_worker(fail_worker, job)
            reclaim_at = None
        if have_result.all():
            break
        elig = ~have_result
        if nchunks > window:
            elig[:, window:] &= have_result[:, :-window]
        ws, cs = np.nonzero(elig)  # row-major: worker-major packet order
        keep = rng.random(ws.size) >= drop_prob
        ws, cs = ws[keep], cs[keep]
        if ws.size == 0:
            continue
        payloads = vecs3[ws, cs]
        if batched:
            ready, results, accepted = switch.ingest_batch(
                ws, cs + chunk_base, payloads,
                jobs=np.full(ws.size, job, np.int32), now=now_base + rnd)
            if record_arrivals:
                for i in np.nonzero(accepted)[0]:
                    arrivals.setdefault(int(cs[i]), []).append(int(ws[i]))
        else:
            from repro.core import switch as legacy

            ready = np.zeros(ws.size, bool)
            results = np.zeros((ws.size, e), np.float32)
            for i in range(ws.size):
                res = switch.ingest(
                    legacy.Packet(int(ws[i]), int(cs[i]) + chunk_base, payloads[i]),
                    job=job, now=now_base + rnd)
                if res is not None:
                    ready[i] = True
                    results[i] = res.payload
        for i in np.nonzero(ready)[0]:
            c = int(cs[i])
            out[c] = results[i]
            # vectorized but stream-identical to per-worker rng.random()
            # calls guarded by `not have_result` (Generator.random(n) draws
            # the same sequence as n scalar draws)
            miss = np.nonzero(~have_result[:, c])[0]
            if miss.size:
                ok = rng.random(miss.size) >= drop_prob
                have_result[miss[ok], c] = True
    if not have_result.all():
        raise RuntimeError("aggregation did not complete within max_rounds")
    return rnd
