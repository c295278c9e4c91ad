"""The rest of a run, with the timed path broken underneath, comes out not
correct: a step that hands its state back unchanged, half the batch left out,
the exchange between chips left out, a loss altered where it is produced, and
the control (the reference in the program's place at fp8). The reference
itself in the program's place comes out correct. Each cell's own limits, at
a size a CPU holds: the faults on a tiny model, the control at the published
widths with one layer and a 4,096-row vocabulary (below the widths, a model's
logits are too small for fp8 to move its loss). The harness's look for a chip
is skipped."""
import json
import os
import shutil
import time

import jax
import pytest

from chipbench import harness, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=128, vocab_size=512, num_hidden_layers=2)
CELLS = ("stablelm-3b.train.b1x4096", "qwen1.5-0.5b.dp4.b8x512")


WIDE = dict(num_hidden_layers=1, vocab_size=4096)
WIDE_SEQ = {"train.b1x4096": 128, "dp4.b8x512": 64}


def _root(tmp_path_factory, name, sizes, seq_len):
    root = tmp_path_factory.mktemp(name)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for path in (root / "chipbench" / "configs").iterdir():
        path.write_text(json.dumps({**json.loads(path.read_text()), **sizes}))
    for path in (root / "chipbench" / "traffic").iterdir():
        traffic = json.loads(path.read_text())
        path.write_text(json.dumps({**traffic, "seq_len": seq_len(traffic["name"])}))
    return str(root)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _root(tmp_path_factory, "tiny", TINY, lambda _: 64)


@pytest.fixture(scope="module")
def wide_root(tmp_path_factory):
    return _root(tmp_path_factory, "wide", WIDE, WIDE_SEQ.get)


def _run(root, cell, **kw):
    def make(cfg, c, seed, devices):
        return reference.Trainer(cfg, seed, devices, rows_per_block=c["ref_rows_per_block"],
                                 replicas=c["chips"], **kw)

    return harness.run(cell, 2**35 + 17, 0.3, False, t0=time.perf_counter(),
                       make_trainer=make, devices=jax.devices()[:1], root=root)


def _over_a_limit(result):
    checks = result["checks"]
    assert all(t["limit"] is not None for t in checks.values()), checks
    return any(t["value"] > t["limit"] for t in checks.values())


@pytest.mark.parametrize("cell", CELLS)
def test_reference_in_the_programs_place_is_correct(tiny_root, cell):
    result = _run(tiny_root, cell)
    assert result["correct"], result["checks"]
    assert all(t["value"] == 0 for t in result["checks"].values())


@pytest.mark.parametrize("cell, fault", [
    (cell, fault) for cell in CELLS for fault in ("frozen", "half", "loss_off")
] + [("qwen1.5-0.5b.dp4.b8x512", "one_replica")])
def test_fault_is_not_correct(tiny_root, cell, fault):
    result = _run(tiny_root, cell, variant=fault)
    assert not result["correct"] and _over_a_limit(result), result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(wide_root, cell):
    result = _run(wide_root, cell, precision="fp8")
    assert not result["correct"] and _over_a_limit(result), result["checks"]
