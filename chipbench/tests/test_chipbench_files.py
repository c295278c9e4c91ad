"""Every configuration, traffic mix, cell and metric file loads, names what it
should, and a cell and a metric that no code names are found by their names."""
import importlib
import json
import os
import re
import shutil

import pytest

from chipbench import harness, models
from repro.configs import ARCH_NAMES

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(kind):
    return sorted(f[:-len(".json")] for f in os.listdir(os.path.join(BENCH, kind)) if f.endswith(".json"))


def test_benchmark_json_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for kind, want in keys.items():
        for entry in b[kind]:
            assert set(entry) - {"workloads"} == want, entry
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(m["moves"] in e2e for m in b["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in b["end_to_end"])
    cells = {w["name"] for w in b["workloads"]}
    assert all(set(m.get("workloads", cells)) <= cells for m in b["per_layer"])


@pytest.mark.parametrize("name", _names("configs"))
def test_config_file_names_a_registry_config(name):
    cfg = harness._json(os.path.join(BENCH, "configs", f"{name}.json"))
    entry = next(c for c in _bench()["configs"] if c["name"] == name)
    assert entry["file"] == f"chipbench/configs/{name}.json"
    assert cfg["name"] == name and cfg["registry"] in ARCH_NAMES
    assert os.path.isfile(os.path.join(BENCH, "models", f"{cfg['model']}.py"))
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, (published, run) in cfg["reduced"].items():
        assert cfg[key] == run != published


@pytest.mark.parametrize("name", sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "models"))
                                        if f.endswith(".py") and f != "__init__.py"))
def test_model_module_gives_what_the_harness_reads(name):
    mod = models.of({"model": name})
    assert all(callable(getattr(mod, f)) for f in
               ("leaf_specs", "nll_sum", "model_flops_per_token", "program_config"))
    assert isinstance(mod.SMALL, dict) and isinstance(mod.WIDE, dict)


@pytest.mark.parametrize("name", _names("workloads"))
def test_workload_file_loads_its_cell(name):
    cell = harness.load_cell(name)
    assert cell["config"] in {c["name"] for c in _bench()["configs"]}
    assert set(cell["limits"]) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}
    assert cell["end_to_end"] and cell["per_layer"]


@pytest.mark.parametrize("name", _names("traffic"))
def test_traffic_file_is_data(name):
    t = harness._json(os.path.join(BENCH, "traffic", f"{name}.json"))
    assert t["name"] == name and t["generator"] == "zipf_motif"


@pytest.mark.parametrize("name", sorted(m["name"] for m in _bench()["per_layer"]))
def test_every_declared_metric_has_a_reader(name):
    assert callable(harness.load_metric(name).read)


def test_reader_files_are_all_declared():
    declared = {m["name"] for m in _bench()["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")}
    assert files == declared


def test_new_cell_and_metric_found_by_file_name(tmp_path):
    """A cell and a metric that exist only as files (and entries) are found."""
    shutil.copytree(BENCH, tmp_path / "chipbench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = _bench()
    b["workloads"].append({"name": "qwen1.5-0.5b.train.b4x512", "config": "qwen1.5-0.5b",
                           "traffic": "train.b4x512", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                           "source": "host_clock", "layer": "train loop", "moves": "tokens_per_s",
                           "workloads": ["qwen1.5-0.5b.train.b4x512"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    traffic = harness._json(os.path.join(BENCH, "traffic", "dp4.b8x512.json"))
    (tmp_path / "chipbench" / "traffic" / "train.b4x512.json").write_text(
        json.dumps({**traffic, "name": "train.b4x512", "per_chip_batch": 4}))
    cell = harness._json(os.path.join(BENCH, "workloads", "qwen1.5-0.5b.dp4.b8x512.json"))
    (tmp_path / "chipbench" / "workloads" / "qwen1.5-0.5b.train.b4x512.json").write_text(
        json.dumps({**cell, "traffic": "train.b4x512", "chips": 1}))
    (tmp_path / "chipbench" / "metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return ctx.steps\n")

    got = harness.load_cell("qwen1.5-0.5b.train.b4x512", root=str(tmp_path))
    assert got["global_batch"] == 4 and got["seq_len"] == 512
    assert [m["name"] for m in got["per_layer"]][-1] == "steps_traced"
    assert "agg_psum_ms" not in {m["name"] for m in got["per_layer"]}
    reader = harness.load_metric("steps_traced", root=str(tmp_path))
    assert reader.read(type("Ctx", (), {"steps": 5})()) == 5
    _named_by_no_code("steps_traced", "train.b4x512")


# A dense decoder whose token embedding passes through a gate of its own: one
# leaf the dense tree lacks, and work the dense count lacks.
TOY = '''
from chipbench.models import dense
from chipbench.weights import ONES

SMALL, WIDE = dense.SMALL, dense.WIDE


def leaf_specs(cfg):
    return {**dense.leaf_specs(cfg), "embed/gate": ((cfg["hidden_size"],), ONES)}


def nll_sum(params, tokens, cfg, precision="f32", targets=None):
    gated = {"tok": params["embed"]["tok"] * params["embed"]["gate"]}
    return dense.nll_sum(dict(params, embed=gated), tokens, cfg, precision, targets)


def model_flops_per_token(cfg, seq_len):
    return dense.model_flops_per_token(cfg, seq_len) + 6.0 * cfg["hidden_size"]


def program_config(cfg):
    raise ValueError("the program has no gated embedding")
'''


def _with_model(tmp_path, model: str, source: str, cfg: dict) -> str:
    """A copy of the benchmark that adds ``models/<model>.py`` (``source``), a
    configuration ``cfg`` named ``model`` and the cell ``<model>.train.b2x64``
    of it, each as a file and an entry; returns the cell's name."""
    shutil.copytree(BENCH, tmp_path / "chipbench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "chipbench" / "models" / f"{model}.py").write_text(source)
    (tmp_path / "chipbench" / "configs" / f"{model}.json").write_text(
        json.dumps({**cfg, "name": model, "model": model}))
    traffic = harness._json(os.path.join(BENCH, "traffic", "train.b8x512.json"))
    (tmp_path / "chipbench" / "traffic" / "train.b2x64.json").write_text(
        json.dumps({**traffic, "name": "train.b2x64", "per_chip_batch": 2, "seq_len": 64}))
    name = f"{model}.train.b2x64"
    cell = harness._json(os.path.join(BENCH, "workloads", "qwen1.5-0.5b.train.b8x512.json"))
    (tmp_path / "chipbench" / "workloads" / f"{name}.json").write_text(
        json.dumps({**cell, "config": model, "traffic": "train.b2x64", "ref_rows_per_block": 2}))
    b = _bench()
    b["configs"].append({"name": model, "source": "test", "file": f"chipbench/configs/{model}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": name, "config": model, "traffic": "train.b2x64",
                           "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return name


def test_new_model_found_by_file_name(tmp_path):
    """A model that exists only as a file, named by a configuration that
    exists only as a file, is what the weights, the reference, the FLOP
    count and a run of the harness use."""
    import time

    import jax

    from chipbench import flops, reference, weights
    from chipbench.models import dense

    qwen = harness._json(os.path.join(BENCH, "configs", "qwen1.5-0.5b.json"))
    name = _with_model(tmp_path, "toy", TOY, {**qwen, **dense.SMALL})

    got = harness.load_cell(name, root=str(tmp_path))
    cfg = got["cfg"]
    assert weights.maker(cfg)(5)["embed"]["gate"].shape == (64,)
    ref = reference.Trainer(cfg, 5, jax.devices()[:1], rows_per_block=2)
    assert set(ref.params) == set(weights.leaf_specs(cfg)) == set(dense.leaf_specs(cfg)) | {"embed/gate"}
    ref.free()
    assert flops.model_flops_per_token(cfg, 64) == dense.model_flops_per_token(cfg, 64) + 6 * 64

    def make(cfg, c, seed, devices):
        return reference.Trainer(cfg, seed, devices, rows_per_block=c["ref_rows_per_block"])

    result = harness.run(name, 2**35 + 17, 0.3, False, t0=time.perf_counter(),
                         make_trainer=make, devices=jax.devices()[:1], root=str(tmp_path))
    assert result["correct"] and result["attempted"] > 0, result["checks"]
    assert all(t["value"] == 0 for t in result["checks"].values())
    _named_by_no_code("toy", "train.b2x64")


# A model in the program's Mamba2 layout (``repro.models.mamba2``): A, D and
# dt's bias in float32, A drawn from U[1, 16] and dt log-uniform on
# [1e-3, 1e-1] through the inverse softplus, and a layer scope of its own. Its
# loss is a toy that reaches the float32 leaves, not the SSD scan.
TOY_SSM = '''
import math

import jax
import jax.numpy as jnp

from chipbench.reference import _mm, _rms
from chipbench.weights import ONES, ZEROS

SMALL = WIDE = {}
LAYERS = {"ssm_ms": ("model.ssm",)}
DT = (1e-3, 1e-1)
F32 = ("layers/mamba/a_log", "layers/mamba/d_skip", "layers/mamba/dt_bias")


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(DT[0]), math.log(DT[1])))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt


def leaf_specs(cfg):
    d, L, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    di, n = 2 * d, cfg["state_size"]
    h = di // cfg["head_dim"]
    return {
        "embed/tok": ((v, d), 0.02),
        "layers/ln1/w": ((L, d), ONES),
        "layers/mamba/in_proj": ((L, d, 2 * di + 2 * n + h), 0.02),
        "layers/mamba/conv_w": ((L, 4, di + 2 * n), 0.1),
        "layers/mamba/conv_b": ((L, di + 2 * n), ZEROS),
        "layers/mamba/a_log": ((L, h), _a_log, "float32"),
        "layers/mamba/d_skip": ((L, h), ONES, "float32"),
        "layers/mamba/dt_bias": ((L, h), _dt_bias, "float32"),
        "layers/mamba/norm_w": ((L, di), ONES),
        "layers/mamba/out_proj": ((L, di, d), 0.02),
        "final_norm/w": ((d,), ONES),
        "head/w": ((d, v), 0.02),
    }


def nll_sum(params, tokens, cfg, precision="f32", targets=None):
    targets = targets or tokens.shape[1] - 1
    x = params["embed"]["tok"][tokens]

    def layer(x, lp):
        m = lp["mamba"]
        di, h = m["norm_w"].shape[0], m["a_log"].shape[0]
        keep = jnp.exp(-jax.nn.softplus(m["dt_bias"]) * jnp.exp(m["a_log"])) * m["d_skip"]
        u = _mm("bsd,de->bse", _rms(x, lp["ln1"]["w"], 1e-5), m["in_proj"], precision)[..., :di]
        u = (u.reshape(*u.shape[:2], h, -1) * keep[:, None]).reshape(u.shape) * m["norm_w"]
        return x + _mm("bse,ed->bsd", u, m["out_proj"], precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[:, :targets], params["final_norm"]["w"], 1e-5)
    logp = jax.nn.log_softmax(_mm("bsd,dv->bsv", x, params["head"]["w"], precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:targets + 1, None], axis=-1))


def model_flops_per_token(cfg, seq_len):
    specs = leaf_specs(cfg)
    return 6.0 * sum(math.prod(specs[p][0]) for p in
                     ("layers/mamba/in_proj", "layers/mamba/out_proj", "head/w"))


def program_config(cfg):
    from repro.configs import get_config

    return get_config(cfg["registry"]).with_(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], ssm_state=cfg["state_size"],
        ssm_head_dim=cfg["head_dim"], ssm_chunk=cfg["chunk_size"],
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"],
        optimizer=cfg["optimizer"]["name"])
'''

SSM_HLO = """
  %p.0 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[8]{0} fusion(%p.0), kind=kLoop, calls=%fc.1, metadata={op_name="jit(f)/model.attn/model.ssm/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(f)/transpose(jvp(model.ssm))/dot_general"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fc.3, metadata={op_name="jit(f)/model.attn/add"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fc.4, metadata={op_name="jit(f)/optim/mul"}
  ROOT %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fc.5, metadata={op_name="jit(f)/while/body/squeeze"}
"""


def test_non_dense_model_needs_no_other_edit(tmp_path):
    """A model given as one file, with float32 leaves, leaves drawn by a
    function and a layer scope of its own, is taken by the weights, the
    program's tree check, the reference, the byte count and the scope
    partition, and no other file is edited."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import flops, program, reference, scopes, trace, weights

    qwen = harness._json(os.path.join(BENCH, "configs", "qwen1.5-0.5b.json"))
    name = _with_model(tmp_path, "toy_ssm", TOY_SSM, {
        "registry": "mamba2-780m", "hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 512,
        "state_size": 16, "head_dim": 16, "chunk_size": 16, "torch_dtype": "bfloat16",
        "reduced": {}, "optimizer": qwen["optimizer"]})
    cell = harness.load_cell(name, root=str(tmp_path))
    cfg = cell["cfg"]
    mod = models.of(cfg)
    devices = jax.devices()[:1]

    drawn = weights.flatten(weights.maker(cfg)(2**35 + 17))
    assert {p: str(x.dtype) for p, x in drawn.items() if p in mod.F32} == dict.fromkeys(mod.F32, "float32")
    assert {str(x.dtype) for p, x in drawn.items() if p not in mod.F32} == {"bfloat16"}
    dt = np.asarray(jax.nn.softplus(drawn["layers/mamba/dt_bias"]))
    assert mod.DT[0] * (1 - 1e-5) <= dt.min() < dt.max() <= mod.DT[1] * (1 + 1e-5)
    a = np.exp(np.asarray(drawn["layers/mamba/a_log"]))
    assert 1 <= a.min() < a.max() <= 16 and len(np.unique(a)) == a.size

    trainer = program.Trainer(cfg, cell, 5, devices)  # raises where the trees differ
    assert {str(x.dtype) for x in weights.flatten(trainer.params).values()} == {"bfloat16", "float32"}
    trainer.free()
    ref = reference.Trainer(cfg, 5, devices, rows_per_block=2)
    for step in range(2):
        ref.step(np.arange(2 * 64, dtype=np.int32).reshape(2, 64) * (step + 3) % 512)
    assert all(ref.params[p].dtype == jnp.float32 for p in mod.F32)
    assert all(ref.first_grad_norms()[p] > 0 for p in mod.F32)
    ref.free()

    counts = weights.element_counts(cfg)
    n, f32 = sum(counts.values()), sum(counts[p] for p in mod.F32)
    assert flops.fpisa_bytes_per_step(cfg) == 2 * (2 * (n - f32) + 4 * f32 + 4 * n + 4 * n / 256)

    table = scopes.layer_table(cfg)
    assert table == {**scopes.layer_table(), "model.ssm": "ssm_ms"}
    got = scopes.hlo_paths(SSM_HLO, table)
    dense = scopes.hlo_paths(SSM_HLO)
    ops = [[op, 0, 0, opcode, out_type] for op, (opcode, out_type, _) in got.items()]
    by_toy = {op[0]: scopes.layer_of(op, got, table) for op in ops}
    by_dense = {op[0]: scopes.layer_of(op, dense) for op in ops}
    ssm = {"p.0", "fusion.1", "fusion.2"}  # the parameter takes its consumer's scope
    assert {op for op, metric in by_toy.items() if metric == "ssm_ms"} == ssm
    assert {op: m for op, m in by_toy.items() if op not in ssm} == {
        op: m for op, m in by_dense.items() if op not in ssm}
    assert by_dense["fusion.1"] == "attention_ms"  # the innermost scope wins

    events = {"host": [["bench.batch", 0, 100]], "devices": {0: [
        [op, 10 * i, i + 1, opcode, out_type, path]
        for i, (op, (opcode, out_type, path)) in enumerate(got.items())]}}
    ctx = trace.Context(events, steps=1, chips=1, cell=cell, peaks={})
    read = {m: scopes.layer_ms(ctx, m) for m in (*scopes.LAYERS, "ssm_ms", scopes.UNSCOPED)}
    leaf_ms = ctx.per_chip_s(lambda ops: sum(op[2] for op in ops)) * 1e3
    assert read["ssm_ms"] == pytest.approx((1 + 2 + 3) * 1e-6)
    assert sum(read.values()) == pytest.approx(leaf_ms)
    assert scopes.layer_ms(trace.Context(events, steps=1, chips=1, cell={"cfg": qwen}, peaks={}),
                           "ssm_ms") is None
    _named_by_no_code("toy_ssm", "model.ssm", "ssm_ms")


def _named_by_no_code(*names):
    for mod in ("harness", "program", "trace", "check", "weights", "reference", "flops", "models",
                "scopes"):
        src = importlib.import_module(f"chipbench.{mod}").__file__
        with open(src) as f:
            text = f.read()
        assert not any(n in text for n in names), (mod, names)
