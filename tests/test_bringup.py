"""What keeps the program honest about its device: the interpret-mode
decision, the compile-cache placement, ``chip_smoke.py``'s contract (no
verdict without a TPU; its HLO check finds an s32 all-reduce), and the train
step's diagnostic outputs and sharded init that its checks read."""
import importlib.util
import os

import pytest

import jax

from repro.kernels import ops
from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("platform,expected", [("cpu", True), ("tpu", False)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.interpret() is expected


def test_interpret_mode_refuses_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="neither"):
        ops.interpret()


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_dir_is_fixed_in_repo():
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_gives_no_verdict_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert _chip_smoke().main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("line,sizes", [
    ('  %psum.1 = s32[270336,256]{1,0:T(8,128)} all-reduce(%x), channel_id=3, '
     'replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add', [4]),
    ('  %all-reduce.3 = (s32[96]{0}, s32[4]{0}) all-reduce(%a, %b), '
     'replica_groups=[1,4]<=[4], to_apply=%max', [4]),
    ('  %ar = f32[128]{0} all-reduce(%g), replica_groups={{0,1,2,3}}, to_apply=%add', []),
    ('  %gte = s32[96]{0} get-tuple-element(%all-reduce.3), index=0', []),
    ('  %ar.2 = s32[8]{0} all-reduce(%m), replica_groups={{0,1},{2,3}}, to_apply=%add', [2]),
])
def test_chip_smoke_finds_s32_all_reduce_groups(line, sizes):
    assert _chip_smoke().s32_allreduce_groups(line) == sizes


DIAG_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.configs import get_smoke_config
from repro.core.agg import AggConfig, Aggregator
from repro.data.pipeline import ShardedLoader, SyntheticCorpus
from repro.launch.train import build_step, init_state, state_shardings
from repro.runtime.elastic import make_mesh_for
from repro.sharding import rules
from jax.sharding import NamedSharding

cfg = get_smoke_config("qwen1.5-0.5b")
mesh = make_mesh_for()
assert dict(mesh.shape) == {"data": 4, "model": 1}, mesh.shape
GB = 8
agg = AggConfig(strategy="fpisa", backend="pallas")
model, opt_cfg, step = build_step(cfg, mesh, agg, GB, diagnostics=True)
params, opt = init_state(model, cfg, mesh, opt_cfg)
shardings = state_shardings(model, cfg, mesh, opt_cfg)

def same_shardings(state):
    return all(x.sharding == s for x, s in zip(jax.tree.leaves(state),
                                               jax.tree.leaves(shardings)))

# the init lands in the mesh's shardings: params replicated, Adam state
# data-sharded wherever a dimension divides (ZeRO-1)
assert same_shardings((params, opt))
assert any(len({sh.index for sh in m.addressable_shards}) == 4 for m in jax.tree.leaves(opt.m))
loader = ShardedLoader(SyntheticCorpus(cfg.vocab_size, 0), GB, 32)
batch = {"tokens": jax.device_put(loader.batch_at(0)["tokens"],
                                  NamedSharding(mesh, P(*rules.batch_pspec(mesh, GB), None)))}
params, opt, m = step(params, opt, batch)
# the step hands the state back in the shardings it took, so the next step
# runs the same compiled program
assert same_shardings((params, opt))
params, opt, _ = step(params, opt, batch)
assert step._cache_size() == 1
local, summed = m["local_grads"], m["agg_grads"]
assert all(l.shape == (4,) + p.shape for l, p in
           zip(jax.tree.leaves(local), jax.tree.leaves(params)))
# the aggregation inside the step is the Aggregator's, bit for bit
ag = Aggregator(agg, ("data",))
alone = jax.jit(compat.shard_map(
    lambda t: ag.allreduce_tree(jax.tree.map(lambda x: x[0], t)),
    mesh=mesh, in_specs=P("data"), out_specs=P()))(local)
for a, b in zip(jax.tree.leaves(summed), jax.tree.leaves(alone)):
    assert (a.view(jnp.int16) == b.view(jnp.int16)).all()
# the optimizer clips the MEAN of the 4 replicas' gradients
mean_norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32) / 4))
                         for g in jax.tree.leaves(summed)))
assert abs(float(mean_norm) - float(m["grad_norm"])) <= 1e-5 * float(mean_norm)
print("DIAG_OK")
"""


def test_train_step_diagnostics_and_sharded_init(multi_device_runner):
    assert "DIAG_OK" in multi_device_runner(DIAG_CODE, n_devices=4, timeout=600)


def test_diagnostics_need_an_explicit_aggregation_boundary():
    from repro.configs import get_smoke_config
    from repro.core.agg import AggConfig
    from repro.launch.train import build_step
    from repro.runtime.elastic import make_mesh_for

    with pytest.raises(ValueError, match="diagnostics"):
        build_step(get_smoke_config("qwen1.5-0.5b"), make_mesh_for(),
                   AggConfig(strategy="native"), 8, diagnostics=True)
