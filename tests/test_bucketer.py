"""Property-based differential harness for block-aligned gradient bucketing.

Two layers (DESIGN.md §3):

1. Plan invariants — pure-python properties of ``bucketer.make_plan``:
   exact coverage, block-aligned offsets, capacity, reverse-autograd order.
2. Parity — bucketed ``allreduce_tree`` is BIT-identical to the per-leaf path
   across strategy x backend x wire_bits x ragged leaf shapes. Single-worker
   (w=1) runs in-process; the multi-worker flat and hierarchical meshes run
   on 8 host devices in a subprocess (this process keeps 1 device per the
   project brief).

``hypothesis`` is optional (same pattern as tests/test_fpisa.py): without it
the property tests are skipped and a deterministic sweep over hand-picked
ragged trees — non-block-multiple leaves, scalars, a leaf spanning several
buckets, mixed dtypes — covers the same invariants.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import allreduce as AR
from repro.core import bucketer as B
from repro.core.agg import Aggregator

try:  # property tests are a bonus; the deterministic sweep always runs
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# 1. plan invariants
# ---------------------------------------------------------------------------

PLAN_CASES = [
    # (leaf sizes, block, bucket_bytes)
    ([5, 300, 1024, 7, 2600], 256, 4096),
    ([1, 1, 1], 256, 1024),          # scalars only: one block each
    ([100000], 256, 8192),           # single leaf spanning many buckets
    ([0, 64, 0, 65], 64, 512),       # zero-size leaves are passthrough
    ([513], 256, 1024),              # bucket_bytes not hit exactly
    ([17, 33, 65, 129, 255], 32, 256),
]


def _check_plan(sizes, block, bucket_bytes):
    leaves = [jax.ShapeDtypeStruct((n,), jnp.float32) for n in sizes]
    plan = B.make_plan(leaves, block=block, bucket_bytes=bucket_bytes)
    cap = max(block, -(-max(bucket_bytes // 4, 1) // block) * block)

    covered = {i: [] for i in range(len(sizes))}
    for b in plan.buckets:
        assert b.elems <= cap
        assert b.elems % block == 0
        off = 0
        for s in b.segments:
            assert s.offset == off, "segments must tile the bucket contiguously"
            assert s.offset % block == 0, "leaf offsets sit on block boundaries"
            assert s.start % block == 0, "leaves split only at block multiples"
            assert s.span % block == 0
            assert 0 <= s.size <= s.span
            off += s.span
            covered[s.leaf].append((s.start, s.size, s.span))
        assert off == b.elems

    for i, n in enumerate(sizes):
        if n == 0:
            assert i in plan.passthrough
            continue
        padded = -(-n // block) * block
        segs = sorted(covered[i])
        # segments tile [0, padded) exactly: each starts where the previous
        # span ended, and carries every real element in that span
        pos = 0
        for start, size, span in segs:
            assert start == pos, (i, segs)
            assert size == max(0, min(n, start + span) - start), (i, segs)
            pos = start + span
        assert pos == padded, (i, segs)
        assert sum(sz for _, sz, _ in segs) == n, (i, segs)

    # reverse-autograd dispatch: the first bucket starts with the LAST leaf
    nonzero = [i for i, n in enumerate(sizes) if n]
    if nonzero:
        assert plan.buckets[0].segments[0].leaf == nonzero[-1]


@pytest.mark.parametrize("sizes,block,bucket_bytes", PLAN_CASES)
def test_plan_invariants_sweep(sizes, block, bucket_bytes):
    _check_plan(sizes, block, bucket_bytes)


def test_plan_mixed_dtypes_grouped():
    leaves = [
        jax.ShapeDtypeStruct((300,), jnp.float32),
        jax.ShapeDtypeStruct((300,), jnp.bfloat16),
        jax.ShapeDtypeStruct((300,), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.int32),  # non-float: passthrough
    ]
    plan = B.make_plan(leaves, block=256, bucket_bytes=1 << 20)
    assert plan.passthrough == (3,)
    for b in plan.buckets:
        dtypes = {jnp.dtype(leaves[s.leaf].dtype).name for s in b.segments}
        assert dtypes == {b.group}, "buckets never mix dtypes"


def test_plan_rejects_bad_args():
    leaves = [jax.ShapeDtypeStruct((8,), jnp.float32)]
    with pytest.raises(ValueError):
        B.make_plan(leaves, block=0, bucket_bytes=1024)
    with pytest.raises(ValueError):
        B.make_plan(leaves, block=256, bucket_bytes=0)


if HAVE_HYPOTHESIS:

    @settings(max_examples=200, deadline=None)
    @given(
        sizes=st.lists(st.integers(0, 5000), min_size=1, max_size=24),
        block=st.sampled_from([32, 64, 256]),
        bucket_kb=st.integers(1, 64),
    )
    def test_plan_invariants_property(sizes, block, bucket_kb):
        _check_plan(sizes, block, bucket_kb * 1024)


# ---------------------------------------------------------------------------
# 2. parity: single worker (w=1), in-process
# ---------------------------------------------------------------------------

RAGGED_TREES = [
    ((37, 13), (5000,), (), (700,), (1300,)),
    ((777,), (1,), (256,), (255,), (257,)),
    ((12000,),),  # one leaf over many buckets
]

COMBOS = [  # (strategy, backend, wire_bits)
    ("native", "jnp", 32),
    ("switchml", "jnp", 32),
    ("fpisa_seq", "jnp", 32),
    ("fpisa", "jnp", 32),
    ("fpisa", "jnp", 16),
    ("fpisa", "jnp", 8),
    ("fpisa", "pallas", 32),
    ("fpisa", "pallas", 16),
    ("fpisa", "pallas", 8),
]


def _tree_from_shapes(shapes, seed=0, scale=0.01):
    rng = np.random.default_rng(seed)
    return {
        f"leaf{i}": jnp.asarray(
            (rng.standard_normal(shape) * scale).astype(np.float32))
        for i, shape in enumerate(shapes)
    }


def _parity_w1(tree, strategy, backend, wire_bits, bucket_bytes, chunk=0):
    mesh = compat.make_mesh((1,), ("data",))

    def make(bb):
        cfg = AR.AggConfig(strategy=strategy, backend=backend,
                           wire_bits=wire_bits, chunk_elems=chunk,
                           bucket_bytes=bb)
        agg = Aggregator(cfg, ("data",))
        return jax.jit(compat.shard_map(
            agg.allreduce_tree, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), tree),),
            out_specs=jax.tree.map(lambda _: P(), tree), check_vma=False))

    a, b = make(0)(tree), make(bucket_bytes)(tree)
    for k in tree:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        assert av.shape == bv.shape
        assert np.array_equal(av.view(np.int32), bv.view(np.int32)), \
            (strategy, backend, wire_bits, bucket_bytes, k)


@pytest.mark.parametrize("strategy,backend,wire_bits", COMBOS)
def test_parity_single_worker_sweep(strategy, backend, wire_bits):
    for shapes in RAGGED_TREES:
        _parity_w1(_tree_from_shapes(shapes), strategy, backend, wire_bits,
                   bucket_bytes=8192)


def test_parity_single_worker_chunked():
    # chunk_elems % block == 0: the block groupings of the chunked per-leaf
    # and bucketed paths coincide, so bit-identity must survive chunking
    _parity_w1(_tree_from_shapes(RAGGED_TREES[0]), "fpisa", "jnp", 32,
               bucket_bytes=8192, chunk=2048)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        sizes=st.lists(st.integers(1, 4000), min_size=1, max_size=8),
        combo=st.sampled_from(COMBOS),
        bucket_kb=st.sampled_from([1, 4, 16]),
        seed=st.integers(0, 2**16),
    )
    def test_parity_single_worker_property(sizes, combo, bucket_kb, seed):
        strategy, backend, wire_bits = combo
        tree = _tree_from_shapes([(n,) for n in sizes], seed=seed)
        _parity_w1(tree, strategy, backend, wire_bits,
                   bucket_bytes=bucket_kb * 1024)


# ---------------------------------------------------------------------------
# 3. parity: multi-worker flat + hierarchical meshes (subprocess, 8 devices)
# ---------------------------------------------------------------------------

PARITY_CODE = r"""
import itertools
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import allreduce as AR

rng = np.random.default_rng(0)
mesh_flat = compat.make_mesh((8,), ("data",))
mesh_hier = compat.make_mesh((2, 4), ("pod", "data"))

def mk(shape, scale=0.01, dtype=np.float32):
    return jnp.asarray((rng.standard_normal((8,) + shape) * scale).astype(dtype))

# ragged: non-block-multiple leaves, a scalar, a large-magnitude leaf, a
# bf16 leaf (its own dtype group) and an int32 leaf (passthrough)
tree = {"a": mk((37, 13)), "b": mk((5000,)), "c": mk(()),
        "d": mk((700,), 100.0), "e": mk((1300,)),
        "f": jnp.asarray((rng.standard_normal((8, 400)) * 0.01), jnp.bfloat16),
        "g": jnp.asarray(rng.integers(0, 100, (8, 16)), jnp.int32)}

def run(cfg, hier, t=tree):
    mesh = mesh_hier if hier else mesh_flat
    axes = ("pod", "data") if hier else ("data",)
    spec = jax.tree.map(lambda _: P(axes if hier else "data"), t)
    fn = jax.jit(compat.shard_map(
        lambda s: AR.allreduce_tree(jax.tree.map(lambda x: x[0], s), axes, cfg),
        mesh=mesh, in_specs=(spec,), out_specs=jax.tree.map(lambda _: P(), t),
        check_vma=False))
    return fn(jax.tree.map(lambda x: x.reshape((8, 1) + x.shape[1:]), t))

def assert_equal(a, b, tag, t=tree):
    for k in t:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        assert av.dtype == bv.dtype and av.shape == bv.shape, (tag, k)
        assert np.array_equal(av.view(np.int32) if av.dtype.itemsize == 4
                              else av.view(np.int16),
                              bv.view(np.int32) if bv.dtype.itemsize == 4
                              else bv.view(np.int16)), (tag, k)

for hier, (strat, backend, wire) in itertools.product((False, True), [
        ("native", "jnp", 32), ("switchml", "jnp", 32),
        ("fpisa_seq", "jnp", 32),
        ("fpisa", "jnp", 32), ("fpisa", "jnp", 16), ("fpisa", "jnp", 8),
        ("fpisa", "pallas", 32), ("fpisa", "pallas", 16),
        ("fpisa", "pallas", 8)]):
    kw = dict(strategy=strat, backend=backend, wire_bits=wire)
    a = run(AR.AggConfig(**kw), hier)
    b = run(AR.AggConfig(bucket_bytes=8192, **kw), hier)
    assert_equal(a, b, (hier, strat, backend, wire))

# narrow cross-pod wire (pod_wire_bits) through the striped hierarchical path
for pw in (16, 8):
    kw = dict(strategy="fpisa", pod_wire_bits=pw)
    assert_equal(run(AR.AggConfig(**kw), True),
                 run(AR.AggConfig(bucket_bytes=8192, **kw), True),
                 ("pod_wire", pw))

# chunked (chunk_elems % block == 0) through the bucketed generic path
kw = dict(strategy="fpisa", chunk_elems=2048)
assert_equal(run(AR.AggConfig(**kw), False),
             run(AR.AggConfig(bucket_bytes=8192, **kw), False), "chunked")

# switch_emu: the host-callback dataplane strategy, tiny tree (it is slow)
small = {"a": tree["a"], "c": tree["c"]}
kw = dict(strategy="switch_emu")
assert_equal(run(AR.AggConfig(**kw), False, small),
             run(AR.AggConfig(bucket_bytes=4096, **kw), False, small),
             "switch_emu", small)
print("BUCKETED_PARITY_OK")
"""


def test_parity_multi_worker(multi_device_runner):
    out = multi_device_runner(PARITY_CODE, n_devices=8, timeout=900)
    assert "BUCKETED_PARITY_OK" in out


TRAIN_BUCKET_CODE = r"""
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat
from repro.configs import get_smoke_config
from repro.models.registry import build
from repro.core.allreduce import AggConfig
from repro.optim import optimizers
from repro.sharding import rules
from repro.train.step import make_train_step
from repro.data.pipeline import SyntheticCorpus, ShardedLoader

# fully-manual (pod, data) mesh (see tests/test_backend_parity.py for why)
mesh = compat.make_mesh((2, 4), ("pod", "data"))
cfg = get_smoke_config("internlm2-20b").with_(num_kv_heads=2, num_heads=8)
model = build(cfg)
params0 = model.init(jax.random.PRNGKey(0))
pspecs = rules.param_pspecs(params0, cfg, mesh)
opt_cfg = optimizers.OptConfig(name="adamw", lr=1e-3, warmup_steps=5)
ospecs = rules.opt_pspecs(pspecs, params0, mesh)
GB = 8
loader = ShardedLoader(SyntheticCorpus(cfg.vocab_size), GB, 64)
losses = {}
for bucket_bytes in [0, 1 << 18]:
    params = jax.device_put(params0, rules.named(mesh, pspecs))
    opt = optimizers.init(params, opt_cfg)
    opt = optimizers.OptState(step=jax.device_put(opt.step, NamedSharding(mesh, P())),
                              m=jax.device_put(opt.m, rules.named(mesh, ospecs)),
                              v=jax.device_put(opt.v, rules.named(mesh, ospecs)))
    agg = AggConfig(strategy="fpisa", bucket_bytes=bucket_bytes)
    step = jax.jit(make_train_step(model, mesh, agg, opt_cfg, GB))
    ls = []
    # one fixed batch: three different batches during lr warm-up need not
    # give a falling loss, with native aggregation as much as with fpisa
    batch = {"tokens": jax.device_put(loader.batch_at(0)["tokens"],
                                      NamedSharding(mesh, P(("pod","data"), None)))}
    for i in range(3):
        params, opt, m = step(params, opt, batch)
        ls.append(float(m["loss"]))
    losses[bucket_bytes] = ls
# the bucketed collective is bit-identical, so the training trajectories
# must agree exactly — not just approximately
assert losses[0] == losses[1 << 18], losses
assert losses[0][-1] < losses[0][0], losses
print("TRAIN_BUCKETED_OK")
"""


def test_train_step_bucketed(multi_device_runner):
    out = multi_device_runner(TRAIN_BUCKET_CODE, n_devices=8, timeout=900)
    assert "TRAIN_BUCKETED_OK" in out
