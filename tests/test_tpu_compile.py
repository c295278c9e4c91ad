"""Compile the FPISA Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jax compiles each kernel for a
chip that is described, not attached, and refuses what Mosaic would refuse on
the chip (unsupported vector types, tiling, VMEM). Interpret-mode tests
cannot see those refusals.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU library, and every test worker imports this file.
Keep every chip-compile test in this one file for the same reason.
"""
import collections
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import fpisa
from repro.kernels.fpisa_accum import fpisa_accum
from repro.kernels.fpisa_decode import fpisa_decode
from repro.kernels.fpisa_encode import fpisa_extract
from repro.kernels.fpisa_fused import fused_decode, fused_encode_align

R, B = 4096, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("fmt_name", ["fp32", "bf16", "fp16"])
@pytest.mark.parametrize("rows", [R, 1000])
def test_fused_encode_align_compiles(one_chip, fmt_name, rows):
    x = jax.ShapeDtypeStruct((rows, B), fpisa.PACKED_DTYPE[fmt_name], sharding=one_chip)
    txt = _compiled_text(functools.partial(fused_encode_align, fmt_name=fmt_name), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("fmt_name,wire", [
    ("fp32", jnp.int32), ("bf16", jnp.int32), ("fp16", jnp.int32),
    ("fp32", jnp.int16), ("fp32", jnp.int8),
])
def test_fused_decode_compiles(one_chip, fmt_name, wire):
    man = jax.ShapeDtypeStruct((R, B), wire, sharding=one_chip)
    bmax = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    txt = _compiled_text(functools.partial(fused_decode, fmt_name=fmt_name), man, bmax)
    assert "tpu_custom_call" in txt


def test_fused_decode_ragged_rows_compiles(one_chip):
    man = jax.ShapeDtypeStruct((1000, B), jnp.int32, sharding=one_chip)
    bmax = jax.ShapeDtypeStruct((1000,), jnp.int32, sharding=one_chip)
    txt = _compiled_text(functools.partial(fused_decode, fmt_name="fp16"), man, bmax)
    assert "tpu_custom_call" in txt


# the two-pass kernels and the switch-order accumulator take the same
# integer bits as the fused ones; fp16 is the format Mosaic used to refuse
@pytest.mark.parametrize("fmt_name", ["fp32", "fp16"])
def test_fpisa_extract_compiles(one_chip, fmt_name):
    x = jax.ShapeDtypeStruct((R, B), fpisa.PACKED_DTYPE[fmt_name], sharding=one_chip)
    txt = _compiled_text(functools.partial(fpisa_extract, fmt_name=fmt_name), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("fmt_name", ["fp32", "fp16"])
def test_fpisa_decode_compiles(one_chip, fmt_name):
    man = jax.ShapeDtypeStruct((R, B), jnp.int32, sharding=one_chip)
    bmax = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    txt = _compiled_text(functools.partial(fpisa_decode, fmt_name=fmt_name), man, bmax)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("fmt_name", ["fp32", "bf16", "fp16"])
def test_fpisa_accum_compiles(one_chip, fmt_name):
    x = jax.ShapeDtypeStruct((8, R, B), fpisa.PACKED_DTYPE[fmt_name], sharding=one_chip)
    txt = _compiled_text(functools.partial(fpisa_accum, fmt_name=fmt_name), x)
    assert "tpu_custom_call" in txt


def _fusions_outside_aggregation(hlo: str) -> collections.Counter:
    """The compiled step's fusion bodies that hold none of the aggregation's
    integer work, with names, metadata and memory spaces stripped: what must
    compile the same whichever backend aggregates."""
    out, body = collections.Counter(), None
    for line in hlo.splitlines():
        if re.match(r"^%[\w.\-]*fus[\w.\-]* \(.*\) -> .* \{$", line):
            body = []
        elif body is not None and line.startswith("}"):
            text = "\n".join(body)
            if not re.search(r"s32|s16|custom-call|shift-|optimization-barrier", text):
                out[text] += 1
            body = None
        elif body is not None:
            line = re.sub(r", metadata=\{[^}]*\}|backend_config=\S+|"
                          r"frontend_attributes=\{[^}]*\}|S\(\d\)", "", line)
            body.append(re.sub(r"%[\w.\-]+", "%v", line).strip())
    return out


def test_train_step_compiles_the_same_around_either_backend(one_chip, monkeypatch):
    """The code around the aggregation (the optimizer's grad-norm reduction
    above all) compiles the same on both backends: ``Aggregator.
    allreduce_tree``'s barrier and row-major pin. Without them the jnp
    backend's elementwise code fuses into that reduction and hands it
    another layout, and the two trajectories part on TPU."""
    from repro.configs import get_smoke_config
    from repro.core import allreduce
    from repro.core.agg import AggConfig
    from repro.launch.train import build_step, state_shardings
    from repro.optim import optimizers
    from repro.runtime.elastic import make_mesh_for

    # this process sees the CPU; the described chip compiles Mosaic kernels
    monkeypatch.setattr(allreduce, "_interpret", lambda: False)
    mesh = make_mesh_for(devices=list(one_chip.device_set))
    cfg = get_smoke_config("qwen1.5-0.5b")
    fusions = {}
    for backend in ("pallas", "jnp"):
        model, opt_cfg, step = build_step(cfg, mesh, AggConfig(strategy="fpisa", backend=backend), 8)
        shardings = state_shardings(model, cfg, mesh, opt_cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shapes = (shapes, jax.eval_shape(lambda p: optimizers.init(p, opt_cfg), shapes))
        state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                             shapes, shardings)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                                sharding=NamedSharding(mesh, P()))}
        hlo = step.lower(*state, batch).compile().as_text()
        assert ("tpu_custom_call" in hlo) == (backend == "pallas")
        fusions[backend] = _fusions_outside_aggregation(hlo)
    assert sum(fusions["pallas"].values()) > 50
    assert fusions["pallas"] == fusions["jnp"], (
        fusions["pallas"] - fusions["jnp"], fusions["jnp"] - fusions["pallas"])


def test_flash_attention_compiles(one_chip, monkeypatch):
    """The flash-attention kernel at the stablelm-3b cell's shape (one row of
    4096 tokens, 32 heads of 80, bf16): its forward and its backward (one
    fused dq/dkv kernel), each a Mosaic call."""
    from repro.kernels import ops
    from repro.models import attention

    # this process sees the CPU; the described chip compiles Mosaic kernels
    monkeypatch.setattr(ops, "interpret", lambda: False)
    qkv = [jax.ShapeDtypeStruct((1, 32, 4096, 80), jnp.bfloat16, sharding=one_chip)] * 3

    def forward_backward(q, k, v):
        out, vjp = jax.vjp(attention.flash_attention, q, k, v)
        return out, vjp(out)

    txt = _compiled_text(forward_backward, *qkv)
    calls = re.findall(r'custom_call_target="tpu_custom_call"', txt)
    assert len(calls) == 2, len(calls)
