"""Backend parity: allreduce(backend="pallas") must be BIT-identical to
backend="jnp" for every strategy x wire_bits x chunk_elems combination, on
both the flat (single-axis) and hierarchical (pod,data) reduction paths,
including edge cases (all-zero gradients, denormal flush, NaN/Inf clamping).

Runs under shard_map on an 8-device host mesh (subprocess — this process
keeps 1 device per the project brief)."""
import pytest


PARITY_CODE = r"""
import numpy as np, jax
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import allreduce as AR

mesh_flat = compat.make_mesh((8,), ("data",))
mesh_hier = compat.make_mesh((2, 4), ("pod", "data"))
x = (np.random.default_rng(0).standard_normal((8, 3000)) * 0.01).astype(np.float32)

def run(cfg, hier):
    mesh = mesh_hier if hier else mesh_flat
    axes = ("pod", "data") if hier else ("data",)
    spec = P(axes if hier else "data")
    fn = jax.jit(compat.shard_map(lambda xs: AR.allreduce(xs[0], axes, cfg),
                                  mesh=mesh, in_specs=spec, out_specs=P(),
                                  check_vma=False))
    return np.asarray(fn(x.reshape(8, 1, 3000)))

# fpisa differs by backend on both reduction paths: full sweep
for hier in (False, True):
    for wire in (32, 16, 8):
        for chunk in (0, 2048):
            a = run(AR.AggConfig(strategy="fpisa", wire_bits=wire,
                                 chunk_elems=chunk, backend="jnp"), hier)
            b = run(AR.AggConfig(strategy="fpisa", wire_bits=wire,
                                 chunk_elems=chunk, backend="pallas"), hier)
            assert np.array_equal(a.view(np.int32), b.view(np.int32)), \
                ("fpisa", hier, wire, chunk)

# remaining strategies route around the transform backend — parity must
# still hold (trivially) so backend="pallas" is safe fleet-wide
for strat in ("native", "switchml", "fpisa_seq"):
    for chunk in (0, 2048):
        a = run(AR.AggConfig(strategy=strat, chunk_elems=chunk, backend="jnp"), True)
        b = run(AR.AggConfig(strategy=strat, chunk_elems=chunk, backend="pallas"), True)
        assert np.array_equal(a.view(np.int32), b.view(np.int32)), (strat, chunk)
print("PARITY_OK")
"""


EDGE_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import allreduce as AR

mesh = compat.make_mesh((2, 4), ("pod", "data"))

def run(cfg, x, axes=("pod", "data")):
    fn = jax.jit(compat.shard_map(lambda xs: AR.allreduce(xs[0], axes, cfg),
                                  mesh=mesh, in_specs=P(("pod", "data")),
                                  out_specs=P(), check_vma=False))
    return np.asarray(fn(x.reshape(8, 1, -1)))

cases = {
    # all-zero gradients: bmax pmax sees exp=0 everywhere, decode must give 0
    "zeros": np.zeros((8, 2000), np.float32),
    # denormals flush to zero inside encode on every worker
    "denormal": np.full((8, 2000), 1e-42, np.float32),
    # NaN/Inf clamp to max finite per fpisa.encode (documented deviation);
    # the SUM may still overflow back to inf at renormalize, but never NaN
    "special": np.where(np.arange(16000).reshape(8, 2000) % 7 == 0,
                        np.inf, 1.0).astype(np.float32),
}
cases["special"][0, :5] = np.nan

for name, x in cases.items():
    for chunk in (0, 512):
        a = run(AR.AggConfig(strategy="fpisa", chunk_elems=chunk, backend="jnp"), x)
        b = run(AR.AggConfig(strategy="fpisa", chunk_elems=chunk, backend="pallas"), x)
        assert np.array_equal(a.view(np.int32), b.view(np.int32)), (name, chunk)
        if name == "zeros":
            assert not a.any(), "all-zero input must aggregate to exact zero"
        if name == "denormal":
            assert not a.any(), "denormals must flush to zero"
        if name == "special":
            assert not np.isnan(a).any(), "NaN must be clamped out by encode"
print("EDGE_OK")
"""


TRAIN_PALLAS_CODE = r"""
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

# fully-manual (pod, data) mesh: the aggregation backend is orthogonal to TP,
# and on TPU a Mosaic kernel cannot sit in a shard_map region with an auto
# axis of size > 1 (it cannot be partitioned automatically).
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
for backend in ["jnp", "pallas"]:
    params = jax.device_put(params0, rules.named(mesh, pspecs))
    opt = optimizers.init(params, opt_cfg)
    opt = optimizers.OptState(step=jax.device_put(opt.step, NamedSharding(mesh, P())),
                              m=jax.device_put(opt.m, rules.named(mesh, ospecs)),
                              v=jax.device_put(opt.v, rules.named(mesh, ospecs)))
    agg = AggConfig(strategy="fpisa", backend=backend)
    step = jax.jit(make_train_step(model, mesh, agg, opt_cfg, GB))
    ls = []
    # one fixed batch: three different batches during lr warm-up need not
    # give a falling loss, with native aggregation as much as with fpisa
    batch = {"tokens": jax.device_put(loader.batch_at(0)["tokens"],
                                      NamedSharding(mesh, P(("pod","data"), None)))}
    for i in range(3):
        params, opt, m = step(params, opt, batch)
        ls.append(float(m["loss"]))
    losses[backend] = ls
# the fused-kernel backend is bit-identical, so the training trajectories
# must agree exactly — not just approximately
assert losses["pallas"] == losses["jnp"], losses
assert losses["pallas"][-1] < losses["pallas"][0], losses
print("TRAIN_PALLAS_OK")
"""


def test_backend_parity_all_strategies(multi_device_runner):
    out = multi_device_runner(PARITY_CODE, n_devices=8, timeout=900)
    assert "PARITY_OK" in out


def test_backend_parity_edge_cases(multi_device_runner):
    out = multi_device_runner(EDGE_CODE, n_devices=8, timeout=600)
    assert "EDGE_OK" in out


def test_train_step_pallas_backend(multi_device_runner):
    out = multi_device_runner(TRAIN_PALLAS_CODE, n_devices=8, timeout=900)
    assert "TRAIN_PALLAS_OK" in out
