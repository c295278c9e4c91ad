"""Device time per layer from each op's scope path (``chipbench.scopes``): the
innermost-scope rule, the partition of the step's leaf-op time among the six
readers, the silence on traces without paths, and the compiled step's HLO
as the source of the paths."""
import copy
import os

import numpy as np
import pytest

from chipbench import harness, scopes, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("attention_ms", "mlp_ms", "vocab_ms", "agg_ms", "optimizer_ms", "unscoped_ms")
J = "jit(train_step)/jit(main)"

# one chip, 2 steps of 100 ns; each op [name, start, dur, opcode, type, op_name]
HAND = {
    "host": [["bench.batch", 0, 10], ["bench.wait", 10, 100], ["bench.batch", 100, 110],
             ["bench.wait", 110, 200]],
    "devices": {0: [
        ["fusion.1", 10, 10, "fusion", "bf16[8]", f"{J}/model.embed/gather"],
        ["while.2", 20, 30, "while", "(s32[])", f"{J}/jvp()/while"],
        ["fusion.3", 20, 10, "fusion", "bf16[8]", f"{J}/jvp()/while/body/closed_call/model.attn/dot_general"],
        ["fusion.4", 30, 5, "fusion", "bf16[8]",
         f"{J}/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/model.mlp/mul"],
        ["fusion.5", 35, 5, "fusion", "bf16[8]", f"{J}/jvp()/while/body/dynamic_update_slice"],
        ["fusion.6", 50, 4, "fusion", "f32[8]", f"{J}/transpose(jvp(model.head))/log_softmax"],
        ["fused_encode_align.7", 54, 6, "custom-call", "s32[8]", f"{J}/agg/agg/agg.encode/pallas_call"],
        ["all-reduce-start.8", 60, 1, "all-reduce-start", "s32[8]", f"{J}/agg/agg.psum/psum"],
        ["fusion.9", 61, 9, "fusion", "f32[8]", f"{J}/optim/mul"],
        ["all-reduce-done.8", 70, 2, "all-reduce-done", "s32[8]", f"{J}/agg/agg.psum/psum"],
        ["copy.10", 72, 3, "copy", "s32[8]", f"{J}/agg/copy"],
        ["fusion.11", 150, 20, "fusion", "bf16[8]", f"{J}/model.attn/model.mlp/add"],
    ]},
    "async": {0: [["all-reduce-start.8", 60, 12, "all-reduce-start", "s32[8]"]]},
}


def _ctx(events, chips=1, steps=2):
    cell = {"cfg": {}, "agg": {}, "seq_len": 1, "global_batch": 1}
    return trace.Context(events, steps=steps, chips=chips, cell=cell, peaks={},
                         metric=lambda n: harness.load_metric(n).read(ctx))


def _read(ctx):
    return {name: harness.load_metric(name).read(ctx) for name in READERS}


def test_innermost_scope_rule():
    assert scopes.innermost(f"{J}/transpose(jvp(model.attn))/dot_general") == "model.attn"
    assert scopes.innermost(f"{J}/agg/agg/agg.psum/psum") == "agg.psum"
    assert scopes.innermost(f"{J}/model.attn/model.mlp/add") == "model.mlp"
    assert scopes.innermost(f"{J}/jvp()/while/body/jit(_where)/select_n") is None
    assert scopes.innermost("model.attention/agg.allreduce") is None


def test_hand_trace_partition():
    ctx = _ctx(HAND)
    got = _read(ctx)
    per_step = 1 / 2 / 1e6  # ns per 2 steps -> ms per step
    assert got == pytest.approx({
        "attention_ms": 10 * per_step,
        "mlp_ms": (5 + 20) * per_step,           # the innermost scope wins
        "vocab_ms": (10 + 4) * per_step,
        "agg_ms": (6 + 1 + 2 + 3) * per_step,    # start and done, not in flight
        "optimizer_ms": 9 * per_step,
        "unscoped_ms": 5 * per_step,             # the loop's own slicing
    })
    leaf_ms = ctx.per_chip_s(lambda ops: sum(op[2] for op in ops)) / ctx.steps * 1e3
    assert sum(got.values()) == pytest.approx(leaf_ms)


def test_readers_silent_without_paths():
    """Ops without a sixth element, off the chip, or under no scope: no reading."""
    events = copy.deepcopy(HAND)
    events["devices"][0] = [op[:5] for op in events["devices"][0]]
    assert set(_read(_ctx(events)).values()) == {None}
    events["devices"][0] = [op[:5] + [f"{J}/mul"] for op in events["devices"][0]]
    assert set(_read(_ctx(events)).values()) == {None}


@pytest.mark.parametrize("name,chips", [("stablelm-3b.train.b1x4096", 1), ("qwen1.5-0.5b.dp4.b8x512", 4)])
def test_readers_none_on_unscoped_recordings(name, chips):
    events = trace.read(os.path.join(RECORDED, f"{name}.step.json.gz"))
    cell = harness.load_cell(name)
    ctx = trace.Context(events, steps=1, chips=chips, cell=cell, peaks=cell["peaks"]["TPU v5 lite"],
                        metric=lambda n: harness.load_metric(n).read(ctx))
    assert set(_read(ctx).values()) == {None}


def test_hlo_paths_follow_the_op_parse():
    """Names, opcodes and types as ``trace.parse_op`` reads an event; the
    op_name of a traced op; and for the compiler's own instructions (no
    op_name, or an argument's name) the path of the nearest scoped consumer,
    else of the nearest scoped producer."""
    text = """
  %p.1 = f32[8]{0:T(256)} parameter(0), metadata={op_name="opt_state.m"}
  %copy.2 = f32[8]{0:T(1024)} copy(f32[8]{0:T(256)} %p.1), metadata={op_name="opt_state.m"}
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%copy.2)
  %copy-done.3 = f32[8]{0} copy-done(%copy-start.3)
  %fusion.686 = bf16[1,2,2048]{2,1,0:T(8,128)(2,1)} fusion(%copy-done.3), kind=kOutput, calls=%fused_computation.600, metadata={op_name="jit(f)/transpose(jvp(optim))/mul" source_file="x.py" source_line=3}
  %fusion.7 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fc.1, metadata={op_name="jit(f)/while/body/squeeze"}
  %copy.8 = bf16[1,2,2048]{0,1,2} copy(%fusion.686)
  ROOT %tuple.9 = (bf16[1,2,2048]{0,1,2}, f32[8]{0}) tuple(%copy.8, %fusion.7)
"""
    optim = "jit(f)/transpose(jvp(optim))/mul"
    assert scopes.hlo_paths(text) == {
        "p.1": ("parameter", "f32[8] ", optim),
        "copy.2": ("copy", "f32[8] ", optim),
        "copy-start.3": ("copy-start", "(f32[8], f32[8], u32[]) ", optim),
        "copy-done.3": ("copy-done", "f32[8] ", optim),
        "fusion.686": ("fusion", "bf16[1,2,2048] ", optim),
        "fusion.7": ("fusion", "f32[8] ", "jit(f)/while/body/squeeze"),
        "copy.8": ("copy", "bf16[1,2,2048] ", optim),
        "tuple.9": ("tuple", "(bf16[1,2,2048], f32[8]) ", optim),
    }


def test_step_hlo_is_the_trainers_program():
    """The step compiled again from shapes names its instructions as the
    step the Trainer compiled from its arrays, with the same op paths."""
    import jax

    from chipbench.program import Trainer

    cell = harness.load_cell("stablelm-3b.train.b1x4096")
    cfg = dict(cell["cfg"], hidden_size=64, intermediate_size=128, num_attention_heads=2,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    cell = dict(cell, cfg=cfg, seq_len=32, global_batch=2)
    devices = jax.devices()[:1]
    trainer = Trainer(cfg, cell, 3, devices)
    batch = trainer.place(np.zeros((2, 32), np.int32))
    want = scopes.hlo_paths(trainer.step_fn.lower(trainer.params, trainer.opt_state, batch)
                            .compile().as_text())
    got = scopes.hlo_paths(scopes.step_hlo(cell, devices))
    assert got == want
    layers = {scopes.layer_of([name, 0, 0, opcode, out_type], got)
              for name, (opcode, out_type, _) in got.items()}
    assert layers == set(READERS)


def test_recorded_scoped_step():
    """One train step of stablelm-3b.train.b1x4096 traced on a TPU v5e, each
    op given its path from the compiled step's HLO (``scopes.hlo_paths``) as
    a sixth element: the six readers partition the step's leaf-op time, and
    the aggregation holds FPISA's kernels."""
    events = trace.read(os.path.join(RECORDED, "stablelm-3b.train.b1x4096.scoped.json.gz"))
    cell = harness.load_cell("stablelm-3b.train.b1x4096")
    ctx = trace.Context(events, steps=1, chips=1, cell=cell, peaks=cell["peaks"]["TPU v5 lite"],
                        metric=lambda n: harness.load_metric(n).read(ctx))
    got = _read(ctx)
    leaf_ms = ctx.per_chip_s(lambda ops: sum(op[2] for op in ops)) * 1e3
    assert sum(got.values()) == pytest.approx(leaf_ms, rel=1e-9)
    assert all(v > 0 for k, v in got.items() if k != "unscoped_ms")
    assert got["unscoped_ms"] < 0.05 * leaf_ms
    kernels = harness.load_metric("fpisa_kernel_ms").read(ctx)
    assert got["agg_ms"] >= kernels + (harness.load_metric("agg_psum_ms").read(ctx) or 0)
    # backward ops carry their forward op's scope; a fusion its root's path
    paths = {op[0]: op[5] for op in ctx.devices[0]}
    assert any("transpose(" in p and scopes.innermost(p) == "model.attn" for p in paths.values())
    assert all(scopes.innermost(paths[op[0]]) == "agg.encode"
               for op in ctx.devices[0] if op[0].startswith("fused_encode_align"))
    assert all(scopes.innermost(paths[op[0]]) == "agg.decode"
               for op in ctx.devices[0] if op[0].startswith("fused_decode"))
