"""The flash-attention kernel's ops in the compiled train step, as
``chipbench.scopes`` and ``attn_kernel_ms`` read them: every op the kernel
contributes, forward, recomputed and backward, carries ``attn.kernel`` and
counts under ``attention_ms``; a step that bypasses the kernel reads
nothing."""
import functools

import jax
import pytest

from chipbench import harness, scopes, trace

KERNEL_OPS = "splash_mha"  # the kernel's own name stack: splash_mha_fwd, _dq, _dkv


@functools.lru_cache(maxsize=None)
def _paths(seq_len: int) -> dict:
    """``scopes.hlo_paths`` of a two-layer stablelm-shaped train step over
    one sequence of ``seq_len`` tokens, compiled for the CPU."""
    cell = harness.load_cell("stablelm-3b.train.b1x4096")
    cfg = dict(cell["cfg"], hidden_size=64, intermediate_size=128, num_attention_heads=2,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    cell = dict(cell, cfg=cfg, seq_len=seq_len, global_batch=1)
    return scopes.hlo_paths(scopes.step_hlo(cell, jax.devices()[:1]))


def _ctx(paths: dict) -> trace.Context:
    """A one-step trace that runs each instruction of ``paths`` once, for
    1 us, each op keeping its path as the recordings do."""
    ops = [[name, i * 1000, 1000, opcode, out_type, path]
           for i, (name, (opcode, out_type, path)) in enumerate(paths.items())]
    events = {"host": [["bench.wait", 0, len(ops) * 1000]], "devices": {0: ops}}
    cell = harness.load_cell("stablelm-3b.train.b1x4096")
    ctx = trace.Context(events, steps=1, chips=1, cell=cell, peaks={},
                        metric=lambda n: harness.load_metric(n).read(ctx))
    return ctx


def test_kernel_ops_carry_the_scope_and_count_under_attention():
    paths = _paths(2048)
    kernel = {name: path for name, (_, _, path) in paths.items() if KERNEL_OPS in path}
    assert kernel and all("attn.kernel" in path for path in kernel.values())
    assert all(scopes.layer_of([name, 0, 0, *paths[name][:2]], paths) == "attention_ms"
               for name in kernel)
    forward = [p for p in kernel.values() if "splash_mha_fwd" in p and "transpose(" not in p]
    remat = [p for p in kernel.values() if "splash_mha_fwd" in p and "transpose(" in p]
    backward = [p for p in kernel.values() if "splash_mha_dq" in p or "splash_mha_dkv" in p]
    assert forward and remat and backward
    assert all("transpose(" in p for p in backward)


def test_attn_kernel_ms_reads_the_kernel_inside_attention_ms():
    ctx = _ctx(_paths(2048))
    got = {n: harness.load_metric(n).read(ctx) for n in ("attn_kernel_ms", "attention_ms")}
    n_kernel = sum("attn.kernel" in op[5] for op in ctx.devices[0])  # loops and calls left out
    assert got["attn_kernel_ms"] == pytest.approx(n_kernel * 1e-3)
    assert 0 < got["attn_kernel_ms"] < got["attention_ms"]


@pytest.mark.parametrize("seq_len", [1024, 32])
def test_attn_kernel_ms_silent_where_the_kernel_is_bypassed(seq_len):
    """One kernel block or fewer: the chunked path, no op under
    ``attn.kernel``, while ``attention_ms`` still reads."""
    ctx = _ctx(_paths(seq_len))
    assert harness.load_metric("attn_kernel_ms").read(ctx) in (None, 0)
    assert harness.load_metric("attention_ms").read(ctx) > 0


J = "jit(train_step)/jvp()/while/body/closed_call/model.attn"
TPU_HLO = f"""
  %p.1 = bf16[32,4096,80]{{2,1,0}} parameter(0)
  %splash_mha_fwd_residuals.16 = (f32[512,128]{{1,0:T(8,128)}}, bf16[32,4096,80]{{2,1,0:T(8,128)(2,1)}}) custom-call(%p.1), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\\"block_q\\": 1024}}"
}}}}, metadata={{op_name="{J}/attn.kernel/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=28}}
  %pallas_call.155 = bf16[32,4096,80]{{2,1,0:T(8,128)(2,1)}} get-tuple-element(%splash_mha_fwd_residuals.16), index=1, frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\\"block_q\\": 1024}}"
}}}}, metadata={{op_name="{J}/attn.kernel/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=28}}
  %fusion.7 = bf16[1,4096,2560]{{2,1,0}} fusion(%pallas_call.155), kind=kOutput, calls=%fc.7, metadata={{op_name="{J}/bhsk,hkd->bsd/dot_general"}}
  %fusion.8 = bf16[32,4096,80]{{2,1,0}} fusion(%p.1), kind=kLoop, calls=%fc.8, metadata={{op_name="{J}/attn.kernel/vmap(jit(_splash_attention))/mul"}}
"""


def test_attn_kernel_ms_finds_the_kernel_calls_of_a_tpu_program():
    """On a TPU the kernel is a custom call named after it, whose op_name
    follows its block sizes on a line of its own: it counts for the kernel
    by its name, the projection after it does not, and all of them count
    under ``attention_ms``."""
    paths = scopes.hlo_paths(TPU_HLO)
    ctx = _ctx({name: paths[name] for name in ("splash_mha_fwd_residuals.16", "fusion.7", "fusion.8")})
    assert harness.load_metric("attn_kernel_ms").read(ctx) == pytest.approx(2e-3)
    assert harness.load_metric("attention_ms").read(ctx) == pytest.approx(3e-3)
