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


def test_new_model_found_by_file_name(tmp_path):
    """A model that exists only as a file, named by a configuration that
    exists only as a file, is what the weights, the reference, the FLOP
    count and a run of the harness use."""
    import time

    import jax

    from chipbench import flops, reference, weights
    from chipbench.models import dense

    shutil.copytree(BENCH, tmp_path / "chipbench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "chipbench" / "models" / "toy.py").write_text(TOY)
    qwen = harness._json(os.path.join(BENCH, "configs", "qwen1.5-0.5b.json"))
    (tmp_path / "chipbench" / "configs" / "toy.json").write_text(
        json.dumps({**qwen, **dense.SMALL, "name": "toy", "model": "toy"}))
    traffic = harness._json(os.path.join(BENCH, "traffic", "train.b8x512.json"))
    (tmp_path / "chipbench" / "traffic" / "train.b2x64.json").write_text(
        json.dumps({**traffic, "name": "train.b2x64", "per_chip_batch": 2, "seq_len": 64}))
    cell = harness._json(os.path.join(BENCH, "workloads", "qwen1.5-0.5b.train.b8x512.json"))
    (tmp_path / "chipbench" / "workloads" / "toy.train.b2x64.json").write_text(
        json.dumps({**cell, "config": "toy", "traffic": "train.b2x64", "ref_rows_per_block": 2}))
    b = _bench()
    b["configs"].append({"name": "toy", "source": "test", "file": "chipbench/configs/toy.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "toy.train.b2x64", "config": "toy", "traffic": "train.b2x64",
                           "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    got = harness.load_cell("toy.train.b2x64", root=str(tmp_path))
    cfg = got["cfg"]
    assert weights.maker(cfg)(5)["embed"]["gate"].shape == (64,)
    ref = reference.Trainer(cfg, 5, jax.devices()[:1], rows_per_block=2)
    assert set(ref.params) == set(weights.leaf_specs(cfg)) == set(dense.leaf_specs(cfg)) | {"embed/gate"}
    ref.free()
    assert flops.model_flops_per_token(cfg, 64) == dense.model_flops_per_token(cfg, 64) + 6 * 64

    def make(cfg, c, seed, devices):
        return reference.Trainer(cfg, seed, devices, rows_per_block=c["ref_rows_per_block"])

    result = harness.run("toy.train.b2x64", 2**35 + 17, 0.3, False, t0=time.perf_counter(),
                         make_trainer=make, devices=jax.devices()[:1], root=str(tmp_path))
    assert result["correct"] and result["attempted"] > 0, result["checks"]
    assert all(t["value"] == 0 for t in result["checks"].values())
    _named_by_no_code("toy", "train.b2x64")


def _named_by_no_code(*names):
    for mod in ("harness", "program", "trace", "check", "weights", "reference", "flops", "models"):
        src = importlib.import_module(f"chipbench.{mod}").__file__
        with open(src) as f:
            text = f.read()
        assert not any(n in text for n in names), (mod, names)
