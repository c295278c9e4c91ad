"""Recovery figure (beyond-paper) — what a worker death costs, end to end.

Two halves, results in ``BENCH_recovery.json``:

1. Switch-side: an all-reduce through the batched dataplane with a worker
   killed mid-stream vs an uninterrupted run. Measures the reclaimed slot
   count, the completion-time overhead of the failure (detection latency +
   survivor resubmission from shadow copies) and the accepted-packet goodput
   in both runs. No slot stays parked: the faulted run COMPLETES — that is
   the property the ``reclaimed`` machinery buys (the pre-reclamation
   dataplane would spin until ``max_rounds`` and raise).

2. Training-side: the elastic controller (runtime/controller.py) in a
   subprocess with 8 virtual CPU devices, one host killed mid-run. The child
   is pinned to the CPU backend (the parent already holds any accelerator),
   so its timings are labelled ``platform: cpu``. Measures
   steps-to-detect (heartbeat timeout), steps replayed (checkpoint cadence),
   wall-clock recovery overhead vs the uninterrupted run, and post-failure
   goodput (tok/s on the survivor mesh vs before the kill) — while asserting
   the loss trajectories are bit-identical (the acceptance invariant).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import emit, scaled, write_json

W = 8
ELEMS = 256
DROP = 0.01


def _switch_half() -> dict:
    from repro import switchsim as ss

    rng = np.random.default_rng(0)
    nchunks = scaled(2048, 128)
    vecs = (rng.standard_normal((W, nchunks * ELEMS)) * 0.01).astype(np.float32)
    # window (slots * pipelines = 32) << nchunks: the kill lands mid-stream
    # with a full in-flight window to reclaim
    cfg = ss.DataplaneConfig(num_workers=W, num_slots=16,
                             elems_per_packet=ELEMS, num_pipelines=2)

    def run(fail_round):
        dp = ss.BatchedDataplane(cfg)
        ss.run_aggregation(ss.BatchedDataplane(cfg), vecs, drop_prob=DROP,
                           seed=3, fail_worker=3 if fail_round else None,
                           fail_round=fail_round)  # warm the jit variants
        t0 = time.perf_counter()
        ss.run_aggregation(dp, vecs, drop_prob=DROP, seed=3,
                           fail_worker=3 if fail_round else None,
                           fail_round=fail_round, detect_rounds=2)
        dt = time.perf_counter() - t0
        return dt, dp.stats

    clean_dt, clean_stats = run(None)
    fault_dt, fault_stats = run(1)
    out = {
        "num_workers": W,
        "drop_prob": DROP,
        "nchunks": nchunks,
        "clean_s": clean_dt,
        "faulted_s": fault_dt,
        "overhead_x": fault_dt / clean_dt,
        "reclaimed": fault_stats["reclaimed"],
        "clean_goodput_pps": clean_stats["packets"] / clean_dt,
        "faulted_goodput_pps": fault_stats["packets"] / fault_dt,
        "completed": True,  # run_aggregation raises on parked slots
        "stats": fault_stats,
    }
    emit("recovery.switch_reclaimed", 0, f"slots={out['reclaimed']}")
    emit("recovery.switch_overhead", fault_dt * 1e6,
         f"x_clean={out['overhead_x']:.2f}")
    return out


_TRAIN_CODE = r"""
import json, tempfile, sys
from repro.configs import get_smoke_config
from repro.core.agg import AggConfig
from repro.runtime.controller import ElasticController

steps, kill_at = {steps}, {kill_at}
cfg = get_smoke_config("qwen1.5-0.5b")
agg = AggConfig(strategy="fpisa", bucket_bytes=1 << 16)

def run(fault):
    return ElasticController(cfg, steps=steps, global_batch=8, seq_len=64,
                             agg=agg, ckpt_dir=tempfile.mkdtemp(),
                             ckpt_every=3, fault_plan=fault,
                             log_every=10**6).run()

base = run("")
faulted = run("kill:2@" + str(kill_at))
assert base["history"] == faulted["history"], "trajectory diverged"
print("RESULT" + json.dumps({{"base": base["timeline"],
                              "faulted": faulted["timeline"],
                              "recovery": faulted["recoveries"][0]}}))
"""


def _train_half() -> dict:
    steps = scaled(24, 10)
    kill_at = steps // 2
    code = _TRAIN_CODE.format(steps=steps, kill_at=kill_at)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1800, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"controller subprocess failed:\n{res.stderr[-3000:]}")
    payload = json.loads(next(l for l in res.stdout.splitlines()
                              if l.startswith("RESULT"))[len("RESULT"):])
    rec = payload["recovery"]
    faulted = payload["faulted"]
    wall = {"base": sum(e["dt"] for e in payload["base"]),
            "faulted": sum(e["dt"] for e in faulted)}
    # post-failure entries are the tail computed on the survivor mesh
    post = [e for e in faulted if e["mesh"] < W][1:]  # [0] is the re-jit step
    pre = [e for e in faulted if e["mesh"] == W][1:kill_at]
    out = {
        "platform": "cpu",
        "steps": steps,
        "kill_at": kill_at,
        "steps_to_detect": rec["steps_to_detect"],
        "steps_replayed": rec["steps_replayed"],
        "steps_to_recover": rec["steps_to_detect"] + rec["steps_replayed"],
        "reclaimed": rec["reclaimed"],
        "survivor_mesh": rec["mesh_hosts"],
        "wall_clean_s": wall["base"],
        "wall_faulted_s": wall["faulted"],
        "recovery_overhead_x": wall["faulted"] / wall["base"],
        "pre_failure_tok_s": (8 * 64 * len(pre) / sum(e["dt"] for e in pre)
                              if pre else 0.0),
        "post_failure_tok_s": (8 * 64 * len(post) / sum(e["dt"] for e in post)
                               if post else 0.0),
        "bit_identical": True,  # asserted inside the subprocess
    }
    emit("recovery.steps_to_recover", 0,
         f"detect={out['steps_to_detect']};replay={out['steps_replayed']}")
    emit("recovery.post_failure_tok_s", 0,
         f"tok_s={out['post_failure_tok_s']:.0f};"
         f"pre={out['pre_failure_tok_s']:.0f};platform=cpu")
    return out


def run():
    write_json("recovery", {
        "switch": _switch_half(),
        "training": _train_half(),
    })
