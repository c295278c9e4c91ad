"""Pipeline parallelism: PP forward/loss must equal the plain (non-PP) model,
and gradients must flow through the ppermute schedule (subprocess, 4 devices)."""

CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models.registry import build
from repro.train.pipeline import make_pp_loss, split_stages

from repro import compat
# ('pod','model') mesh: the stage axis MANUAL with the TP axis auto
mesh = compat.make_mesh((2, 2), ("pod", "model"))
cfg = get_smoke_config("stablelm-3b").with_(num_layers=4, d_model=64)
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
staged = split_stages(params, 2)

batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
ref_loss = float(model.loss(params, batch))

pp_loss = make_pp_loss(cfg, mesh, stage_axis="pod", n_micro=4)
got = float(jax.jit(pp_loss)(staged, batch))
assert abs(got - ref_loss) < 2e-3, (got, ref_loss)

# gradients flow and match the non-PP gradients
g_pp = jax.jit(jax.grad(pp_loss))(staged, batch)
g_ref = jax.grad(model.loss)(params, batch)
a = np.asarray(g_pp["layers"]["mlp"]["wi"]).reshape(4, 64, -1)
b = np.asarray(g_ref["layers"]["mlp"]["wi"])
assert np.allclose(a, b, rtol=2e-2, atol=2e-4), np.abs(a-b).max()
print("PP_OK")
"""


def test_pipeline_matches_reference(multi_device_runner):
    out = multi_device_runner(CODE, n_devices=4, timeout=900)
    assert "PP_OK" in out
