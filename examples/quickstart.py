"""Quickstart: FPISA in 60 seconds.

1. Encode a gradient tensor into switch-register integer planes.
2. Aggregate 8 workers three ways: exact float, bit-faithful FPISA-A (switch
   arrival semantics), and the production block-integer path (order-invariant).
3. Show the paper's headline numerics: tiny error, bounded overwrite events,
   bit-exact reproducibility for the production path.

The production path honors the same shared knobs as every launch CLI
(repro.core.agg.add_agg_args — launch/train.py, launch/dryrun.py, serve_lm):
  --agg-backend {auto,jnp,pallas}   encode/decode transform backend
  --agg-chunk N                     stream the gradient in N-element chunks
  --bucket-bytes N                  bucketed whole-pytree aggregation (step 4)

Run:  PYTHONPATH=src python examples/quickstart.py [--agg-backend jnp]
"""
import argparse

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import fpisa as F
from repro.core import numerics as nx
from repro.core.agg import add_agg_args, resolve_backend
from repro.kernels import ops
from repro.launch.compile_cache import use_compile_cache
from repro.trace import add_trace_args
from repro.trace import from_args as trace_from_args

ap = argparse.ArgumentParser()
add_agg_args(ap)  # the same shared --agg-* flags every entry point uses
add_trace_args(ap)  # the shared --trace-* flags (repro.trace)
ap.set_defaults(bucket_bytes=1 << 16)  # step 4's whole-pytree demo
args = ap.parse_args()
use_compile_cache()
backend = resolve_backend(args.agg_backend)
session = trace_from_args(args)  # spans from step 4's Aggregator calls

rng = np.random.default_rng(0)
W, N, BLOCK = 8, 1 << 16, 256
grads = (rng.standard_normal((W, N)) * 0.01).astype(np.float32)

# --- 1. the representation (paper Fig. 3) ---
planes = F.encode(jnp.asarray(grads[0]))
print(f"FP32 value {grads[0,0]:+.6f} -> exp={int(planes.exp[0])} "
      f"man={int(planes.man[0])} (two's-complement, 7 headroom bits)")
roundtrip = F.renormalize(planes)
assert np.array_equal(np.asarray(roundtrip), grads[0])
print("encode -> delayed-renormalize roundtrip: bit-exact")

# --- 2. aggregation three ways ---
exact = grads.astype(np.float64).sum(0)

seq, stats = F.fpisa_sum_sequential(jnp.asarray(grads), return_stats=True)
err = np.abs(np.asarray(seq, np.float64) - exact)
print(f"\nFPISA-A (switch arrival order): p50 err {np.quantile(err,0.5):.2e}, "
      f"p99 {np.quantile(err,0.99):.2e}, overwrites {int(stats['overwrite'])} "
      f"of {W*N} adds (paper: rare, <0.9%)")


# production block-integer path (what the training framework uses), on the
# selected transform backend, optionally streamed chunk by chunk
def block_aggregate(chunk: np.ndarray) -> jnp.ndarray:
    """chunk: (W, M) with M % BLOCK == 0 -> aggregated (M,) float32."""
    s = nx.required_preshift(W)
    if backend == "pallas":
        # fused single-pass kernels (interpret mode off-TPU), local block max
        # + exact residual shift to the cross-worker max — bit-identical to
        # the jnp formulation (shift composition, see kernels/README.md)
        mans, bmaxs = zip(*(ops.encode_align(
            jnp.asarray(chunk[w]).reshape(-1, BLOCK)) for w in range(W)))
        bmax = jnp.max(jnp.stack(bmaxs), axis=0)
        man = jnp.stack([
            nx.arshift(m, (bmax - bm)[:, None] + s) for m, bm in zip(mans, bmaxs)])
        man_sum = man.sum(0)
        return ops.decode_fused(man_sum, bmax, preshift=s).reshape(-1)
    p = F.encode(jnp.asarray(chunk).reshape(-1))
    pe = p.exp.reshape(W, chunk.shape[1])
    bmax = jnp.max(F.block_max_exponent(pe, BLOCK), axis=0)  # "pmax across workers"
    man = jnp.stack([F.block_encode(jnp.asarray(chunk[w]), bmax, BLOCK, s)
                     for w in range(W)])
    man_sum = man.sum(0)  # "integer psum" — associative, reproducible
    return F.block_decode(man_sum, bmax, BLOCK, s)


chunk = args.agg_chunk or N
assert chunk % BLOCK == 0, "--agg-chunk must be a multiple of 256"
out = jnp.concatenate([block_aggregate(grads[:, lo:lo + chunk])
                       for lo in range(0, N, chunk)])
err2 = np.abs(np.asarray(out, np.float64) - exact)
print(f"FPISA block-integer psum [{backend}"
      f"{', chunked' if args.agg_chunk else ''}]: "
      f"p99 err {np.quantile(err2,0.99):.2e}")

perm = rng.permutation(W)
out2 = jnp.concatenate([block_aggregate(grads[perm][:, lo:lo + chunk])
                        for lo in range(0, N, chunk)])
print("permutation-invariant bit-exact:", bool(jnp.all(out == out2)),
      "(float sums are NOT — this is the production win)")

# --- 4. bucketed whole-pytree aggregation (what --bucket-bytes turns on) ---
# The trainer never aggregates one tensor: it aggregates a pytree of ragged
# leaves. Per-leaf dispatch pays the encode/decode overhead per LEAF;
# bucketing flattens the tree into fixed-size block-aligned wire buckets
# (a block never spans two leaves), streams them double-buffered, and stays
# bit-identical. See core/bucketer.py and DESIGN.md §3.
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.agg import AggConfig, Aggregator

mesh = compat.make_mesh((jax.device_count(),), ("data",))
tree = {f"layer{i}": jnp.asarray(
    (rng.standard_normal(n) * 0.01).astype(np.float32))
    for i, n in enumerate((4096, 700, 13 * 37, 2048, 5))}


def agg_tree(bucket_bytes: int):
    agg = Aggregator(AggConfig(strategy="fpisa", backend=args.agg_backend,
                               bucket_bytes=bucket_bytes), ("data",))
    fn = compat.shard_map(
        agg.allreduce_tree, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), tree),),
        out_specs=jax.tree.map(lambda _: P(), tree), check_vma=False)
    return jax.jit(fn)(tree)


per_leaf, bucketed = agg_tree(0), agg_tree(args.bucket_bytes)
same = all(bool(jnp.all(per_leaf[k].view(jnp.int32) == bucketed[k].view(jnp.int32)))
           for k in tree)
print(f"\nbucketed tree aggregation ({args.bucket_bytes} B buckets) "
      f"bit-identical to per-leaf: {same}")
session.finish()
