"""The rest of a run, with the timed path broken underneath, comes out not
correct: a step that hands its state back unchanged, half the batch left out,
the exchange between chips left out, a loss altered where it is produced, and
the control (the reference in the program's place at fp8). The reference
itself in the program's place comes out correct. Each cell's own limits, at
sizes a CPU holds, which each configuration's model gives: the faults on its
``SMALL`` model, the control on its ``WIDE`` one (the published widths at
little depth). Every cell of ``workloads/`` is covered, the exchange left out
in each that spans more than one chip. The harness's look for a chip is
skipped."""
import json
import os
import shutil
import time

import jax
import pytest

from chipbench import harness, models, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKLOADS = os.path.join(ROOT, "chipbench", "workloads")
CELLS = {name[:-len(".json")]: harness._json(os.path.join(WORKLOADS, name))
         for name in sorted(os.listdir(WORKLOADS)) if name.endswith(".json")}


def _wide_seq(traffic):
    """The control's row length: a 32nd of the mix's, and at least 64 tokens
    (128 for 4096-token rows, 64 for 512-token ones)."""
    return max(64, traffic["seq_len"] // 32)


def _root(tmp_path_factory, name, sizes, seq_len):
    """A copy of the benchmark with each configuration cut to its model's
    ``sizes`` (``SMALL`` or ``WIDE``) and each traffic mix to ``seq_len``."""
    root = tmp_path_factory.mktemp(name)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for path in (root / "chipbench" / "configs").iterdir():
        cfg = json.loads(path.read_text())
        path.write_text(json.dumps({**cfg, **getattr(models.of(cfg), sizes)}))
    for path in (root / "chipbench" / "traffic").iterdir():
        traffic = json.loads(path.read_text())
        path.write_text(json.dumps({**traffic, "seq_len": seq_len(traffic)}))
    return str(root)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _root(tmp_path_factory, "tiny", "SMALL", lambda _: 64)


@pytest.fixture(scope="module")
def wide_root(tmp_path_factory):
    return _root(tmp_path_factory, "wide", "WIDE", _wide_seq)


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
] + [(cell, "one_replica") for cell, workload in CELLS.items() if workload["chips"] > 1])
def test_fault_is_not_correct(tiny_root, cell, fault):
    result = _run(tiny_root, cell, variant=fault)
    assert not result["correct"] and _over_a_limit(result), result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(wide_root, cell):
    result = _run(wide_root, cell, precision="fp8")
    assert not result["correct"] and _over_a_limit(result), result["checks"]
