"""Gradient-aggregation strategies under shard_map on an 8-device host mesh
(subprocess — this process keeps 1 device per the project brief)."""
import numpy as np
import pytest


CODE = r"""
import numpy as np, jax
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import allreduce as AR

mesh = compat.make_mesh((2, 4), ("pod", "data"))
x = (np.random.default_rng(0).standard_normal((8, 5000)) * 0.01).astype(np.float32)
ref = x.astype(np.float64).sum(0)
scale = np.abs(ref).max()

def run(cfg):
    fn = jax.jit(compat.shard_map(lambda xs: AR.allreduce(xs[0], ("pod","data"), cfg),
                                  mesh=mesh, in_specs=P(("pod","data")), out_specs=P(),
                                  check_vma=False))
    return np.asarray(fn(x.reshape(8,1,5000)))

results = {}
for strat, wire, pw in [("native",32,None), ("switchml",32,None), ("fpisa",32,None),
                        ("fpisa",16,None), ("fpisa",32,16), ("fpisa_seq",32,None)]:
    out = run(AR.AggConfig(strategy=strat, wire_bits=wire, pod_wire_bits=pw))
    err = np.abs(out.astype(np.float64) - ref)
    results[f"{strat}-{wire}-{pw}"] = float(np.quantile(err, 0.99) / scale)

# error budgets per strategy (p99 relative to max-magnitude)
assert results["native-32-None"]   < 1e-6, results
assert results["switchml-32-None"] < 1e-5, results
assert results["fpisa-32-None"]    < 1e-6, results
assert results["fpisa-16-None"]    < 2e-3, results
assert results["fpisa-32-16"]      < 1e-3, results
assert results["fpisa_seq-32-None"]< 1e-5, results

# permutation invariance: FPISA integer path must be BIT-exact under any
# worker order (int add is associative+commutative) — the paper's
# reproducibility claim, strengthened to order-independence by our block path
cfg = AR.AggConfig(strategy="fpisa")
fn = jax.jit(compat.shard_map(lambda xs: AR.allreduce(xs[0], ("pod","data"), cfg),
                              mesh=mesh, in_specs=P(("pod","data")), out_specs=P(),
                              check_vma=False))
a = np.asarray(fn(x.reshape(8,1,5000)))
perm = np.random.default_rng(1).permutation(8)
b = np.asarray(fn(x[perm].reshape(8,1,5000)))
assert np.array_equal(a.view(np.int32), b.view(np.int32)), "fpisa not perm-invariant"
print("ALLREDUCE_OK")
"""


def test_allreduce_strategies_multi_device(multi_device_runner):
    out = multi_device_runner(CODE, n_devices=8, timeout=600)
    assert "ALLREDUCE_OK" in out


TRAIN_CODE = r"""
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

# the production-shaped 3-axis mesh, exercising the PARTIALLY manual
# shard_map (manual replica axes + auto 'model') the real fleet uses
mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke_config("internlm2-20b").with_(num_kv_heads=2, num_heads=8)
model = build(cfg)
params0 = model.init(jax.random.PRNGKey(0))
pspecs = rules.param_pspecs(params0, cfg, mesh)
opt_cfg = optimizers.OptConfig(name="adamw", lr=1e-3, warmup_steps=5)
ospecs = rules.opt_pspecs(pspecs, params0, mesh)
GB = 8
loader = ShardedLoader(SyntheticCorpus(cfg.vocab_size), GB, 64)
losses, gnorms = {}, {}
for strat in ["native", "fpisa", "switchml"]:
    params = jax.device_put(params0, rules.named(mesh, pspecs))
    opt = optimizers.init(params, opt_cfg)
    opt = optimizers.OptState(step=jax.device_put(opt.step, NamedSharding(mesh, P())),
                              m=jax.device_put(opt.m, rules.named(mesh, ospecs)),
                              v=jax.device_put(opt.v, rules.named(mesh, ospecs)))
    step = jax.jit(make_train_step(model, mesh, AggConfig(strategy=strat), opt_cfg, GB))
    ls, gs = [], []
    for i in range(4):
        batch = {"tokens": jax.device_put(loader.batch_at(i)["tokens"],
                                          NamedSharding(mesh, P(("pod","data"), None)))}
        params, opt, m = step(params, opt, batch)
        ls.append(float(m["loss"]))
        gs.append(float(m["grad_norm"]))
    losses[strat], gnorms[strat] = ls, gs
# FPISA and SwitchML training must track native float training closely
for strat in ("fpisa", "switchml"):
    for a, b in zip(losses[strat], losses["native"]):
        assert abs(a - b) < 1e-3, (strat, losses)
    # every strategy steps on the global-batch MEAN gradient, as native does
    # (the aggregators return the sum over the 4 replicas)
    for a, b in zip(gnorms[strat], gnorms["native"]):
        assert abs(a - b) <= 1e-2 * b, (strat, gnorms)
# and the loss must decrease
assert losses["fpisa"][-1] < losses["fpisa"][0]
print("TRAIN_EQUIV_OK")
"""


def test_train_step_strategy_equivalence(multi_device_runner):
    out = multi_device_runner(TRAIN_CODE, n_devices=8, timeout=900)
    assert "TRAIN_EQUIV_OK" in out
