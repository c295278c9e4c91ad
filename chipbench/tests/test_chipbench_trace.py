"""The trace reduction: busy union, idle share, kernel time by name, exposed
collective time and gap labels, on a hand-made trace and on a small trace
recorded on the chip."""
import os

import pytest

from chipbench import harness, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# two chips, 2 steps of 100 ns: host spans; each chip's XLA ops and its
# asynchronous ops in flight, [name, start, dur, opcode, type]. Chip 0 runs its
# s32 all-reduce asynchronously (in flight 50-65, fusion.6 under it from 51
# to 60); chip 1 runs it in line, after a while loop that holds two fusions.
HAND = {
    "host": [["bench.batch", 0, 10], ["bench.place", 10, 15], ["bench.step", 15, 16],
             ["bench.wait", 16, 95], ["bench.fetch", 95, 100],
             ["bench.batch", 100, 120], ["bench.place", 120, 125], ["bench.step", 125, 126],
             ["bench.wait", 126, 195], ["bench.fetch", 195, 200]],
    "devices": {
        0: [["fusion.1", 20, 20, "fusion", "bf16[8]"],
            ["fused_encode_align.2", 40, 10, "custom-call", "(s32[8], s32[1])"],
            ["all-reduce-start.3", 50, 1, "all-reduce-start", "s32[8]"],
            ["fusion.6", 51, 9, "fusion", "bf16[8]"],
            ["all-reduce-done.3", 60, 5, "all-reduce-done", "s32[8]"],
            ["fused_decode.4", 70, 5, "custom-call", "s32[8]"],
            ["fusion.5", 130, 60, "fusion", "bf16[8]"]],
        1: [["while.7", 20, 40, "while", "(s32[], bf16[8])"],
            ["fusion.1", 20, 20, "fusion", "bf16[8]"],
            ["fusion.8", 40, 20, "fusion", "bf16[8]"],
            ["all-reduce.3", 60, 20, "all-reduce", "s32[8]"],
            ["fusion.5", 130, 60, "fusion", "bf16[8]"]],
    },
    "async": {0: [["all-reduce-start.3", 50, 15, "all-reduce-start", "s32[8]"]], 1: []},
}


def _ctx(events, chips=2, steps=2, cell=None):
    cell = cell or {"cfg": {}, "agg": {}, "seq_len": 1, "global_batch": 1}
    return trace.Context(events, steps=steps, chips=chips, cell=cell, peaks={},
                         metric=lambda n: harness.load_metric(n).read(ctx))


def test_interval_arithmetic():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0
    assert trace.uncovered_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace.uncovered_ns([(0, 10)], []) == 10


def test_hand_trace():
    ctx = _ctx(HAND)
    assert ctx.window_s() == 200e-9
    assert [o[0] for o in ctx.devices[1]] == ["fusion.1", "fusion.8", "all-reduce.3", "fusion.5"]
    # chip 0 busy 20-65 + 70-75 + 130-190; chip 1: 20-80, 130-190
    assert ctx.busy_s() == pytest.approx((110 + 120) / 2 * 1e-9)
    kernel = harness.load_metric("fpisa_kernel_ms")
    assert ctx.op_time_s(kernel.is_kernel) == pytest.approx(15 / 2 * 1e-9)
    assert kernel.read(ctx) == pytest.approx(15 / 2 / 2 / 1e6)
    # chip 0 from start to done (15), chip 1 in line (20)
    psum = harness.load_metric("agg_psum_ms")
    assert ctx.op_time_s(psum.is_s32_all_reduce) == pytest.approx((15 + 20) / 2 * 1e-9)
    # chip 0: in flight 50-65, fusion.6 works 51-60 -> 6 exposed; chip 1: 20
    assert ctx.exposed_s(trace.is_collective) == pytest.approx((6 + 20) / 2 * 1e-9)
    exposed = harness.load_metric("collective_exposed_ms").read(ctx)
    assert exposed == pytest.approx((6 + 20) / 2 / 2 / 1e6)
    idle = harness.load_metric("device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - 115 / 200))
    gaps = ctx.idle_gaps()
    # chip 0 idles 75-130 (its midpoint falls in the second step's batch),
    # 0-20, 190-200 and 65-70
    assert [(label, round(g * 1e9)) for label, g in gaps] == [
        ("bench.batch", 55), ("bench.place", 20), ("bench.fetch", 10), ("bench.wait", 5)]
    host = harness.load_metric("host_input_ms").read(ctx)
    assert host == pytest.approx((10 + 5 + 5 + 20 + 5 + 5) / 2 / 1e6)


def _sweep_busy(ops):
    """Busy time by an event sweep, independent of ``trace.union_ns``."""
    points = sorted([(o[1], 1) for o in ops] + [(o[1] + o[2], -1) for o in ops], key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0 and d == -1:
            busy += t - since
    return busy


def test_recorded_one_chip_step():
    """One train step of stablelm-3b.train.b1x4096, traced on a TPU v5e (the
    host spans are those of the recording: batch, dispatch, wait, fetch)."""
    events = trace.read(os.path.join(RECORDED, "stablelm-3b.train.b1x4096.step.json.gz"))
    cell = harness.load_cell("stablelm-3b.train.b1x4096")
    peaks = cell["peaks"]["TPU v5 lite"]
    ctx = trace.Context(events, steps=1, chips=1, cell=cell, peaks=peaks,
                        metric=lambda n: harness.load_metric(n).read(ctx))
    ops = events["devices"][0]
    s, e = ctx.window()
    assert ctx.busy_s() == pytest.approx(_sweep_busy([o for o in ops if o[1] < e]) / 1e9)
    idle = harness.load_metric("device_idle_share").read(ctx)
    assert 0 < idle < 5
    # while loops hold the layers' ops: only ops that hold no other count as work
    leaves = trace.leaves(ops)
    assert len(leaves) < len(ops) and not any(o[3] == "while" for o in leaves)
    kernels = [o for o in ops if o[0].startswith(("fused_encode_align", "fused_decode"))]
    assert len(kernels) == 24  # encode and decode of each of the 12 leaves
    kernel_ms = harness.load_metric("fpisa_kernel_ms").read(ctx)
    assert kernel_ms == pytest.approx(sum(o[2] for o in kernels) / 1e6)
    roofline = harness.load_metric("fpisa_kernel_roofline").read(ctx)
    assert roofline == pytest.approx(100 * 10.7317364e9 / 819e9 / (kernel_ms / 1e3), rel=1e-6)
    assert 0 < roofline < 100
    mfu = harness.load_metric("step_mfu").read(ctx)
    assert 0 < mfu < 100
    # one chip: no collective ran, so the aggregation's readers find nothing
    assert harness.load_metric("collective_exposed_ms").read(ctx) is None
    assert harness.load_metric("agg_psum_ms").read(ctx) is None
    gaps = ctx.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(ctx.window_s() - ctx.busy_s())
    assert {label for label, _ in gaps} <= {h[0] for h in events["host"]}
    assert gaps[0][0] == "bench.wait"  # the longest gap sits inside the step


def test_recorded_four_chip_step():
    """One train step of qwen1.5-0.5b.dp4.b8x512, traced on a 2x2 TPU v5e."""
    events = trace.read(os.path.join(RECORDED, "qwen1.5-0.5b.dp4.b8x512.step.json.gz"))
    cell = harness.load_cell("qwen1.5-0.5b.dp4.b8x512")
    ctx = trace.Context(events, steps=1, chips=4, cell=cell, peaks=cell["peaks"]["TPU v5 lite"],
                        metric=lambda n: harness.load_metric(n).read(ctx))
    s, e = ctx.window()
    busy = [_sweep_busy([o for o in events["devices"][k] if o[1] < e]) for k in range(4)]
    assert ctx.busy_s() == pytest.approx(sum(busy) / 4 / 1e9)
    # FPISA's integer sum: s32 all-reduces over the 4 chips (the planes' psum, the exponents' pmax)
    s32 = [[o for o in trace.leaves(events["devices"][k]) if o[3] == "all-reduce" and "s32[" in o[4]]
           for k in range(4)]
    assert all(len(x) == len(s32[0]) > 0 for x in s32)
    psum_ms = harness.load_metric("agg_psum_ms").read(ctx)
    assert psum_ms == pytest.approx(sum(o[2] for x in s32 for o in x) / 4 / 1e6)
    # the exchange runs in line: nothing overlaps it, so all of it is exposed
    collectives = sum(o[2] for k in range(4) for o in trace.leaves(events["devices"][k])
                      if trace.is_collective(o)) / 4 / 1e6
    exposed = harness.load_metric("collective_exposed_ms").read(ctx)
    assert psum_ms < exposed <= collectives * (1 + 1e-9)
    kernels = harness.load_metric("fpisa_kernel_ms").read(ctx)
    assert 0 < harness.load_metric("fpisa_kernel_roofline").read(ctx) < 100 and kernels > 0
    assert 0 < harness.load_metric("step_mfu").read(ctx) < 100
    assert 0 < harness.load_metric("device_idle_share").read(ctx) < 100
    assert len(ctx.breakdown()["device_ops"]) == 10
