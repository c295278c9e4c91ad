"""The harness: one run of one cell.

Everything that belongs to a configuration, a traffic mix, a cell or a
per-layer metric is found by its name: ``configs/<config>.json`` and the
model it names, ``models/<model>.py``, ``traffic/<traffic>.json``,
``workloads/<cell>.json`` and ``metrics/<metric>.py`` under this directory,
and the cell's entries in ``BENCHMARK.json`` at the root of the checkout.
Adding one edits no file here.

A run: set-up builds the program's step and state from the seed and drives
its first steps (the warm-up, which compiles or loads every program the window
calls, and whose numbers the reference checks); the window then repeats
``train_loop``'s per-step work for ``--seconds`` (``--trace 0``), or for the
cell's ``trace_steps`` under the profiler (``--trace 1``); then the program's
state is freed and the reference runs the same first steps.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

import jax
import numpy as np

from chipbench import check, models, reference, trace
from chipbench.corpus import Corpus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_STEPS = 3  # the first steps: set-up drives them, the reference follows them


class NoChip(RuntimeError):
    """The accelerator the cell needs is not there."""


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name``: its BENCHMARK.json entry and metrics, workload,
    configuration and its model, traffic and the table of peaks, all by name."""
    here = os.path.join(root, "chipbench")
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = _json(os.path.join(here, "workloads", f"{name}.json"))
    if (cell["config"], cell["traffic"], cell["chips"]) != (entry["config"], entry["traffic"], entry["chips"]):
        raise ValueError(f"workloads/{name}.json disagrees with BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = _json(os.path.join(root, cfg_entry["file"]))
    cfg[models.FILE_KEY] = models.path(cfg, os.path.join(here, "models"))
    traffic = _json(os.path.join(here, "traffic", f"{entry['traffic']}.json"))

    def applies(m):
        return name in m.get("workloads", [name])

    cell.update(
        name=name,
        cfg=cfg,
        traffic=traffic,
        global_batch=traffic["per_chip_batch"] * entry["chips"],
        seq_len=traffic["seq_len"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        peaks=_json(os.path.join(here, "peaks.json")),
    )
    return cell


def load_metric(name: str, root: str = ROOT):
    """The reader ``chipbench/metrics/<name>.py``: a module with ``read(ctx)``."""
    path = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chips(n: int):
    """The first ``n`` TPU devices, or NoChip."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def use_cache(root: str = ROOT) -> None:
    """Keep JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it, so
    that only a cell's first run in a checkout compiles."""
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts, while it is entered, the compilations and compile-cache loads
    that JAX's monitoring events report."""

    def __init__(self):
        self.count = 0

    def _duration(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def _p90(values):
    if len(values) < 2:
        return max(values)
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def token_source(cell: dict, seed: int):
    """step -> the host token rows of that step, from the cell's traffic."""
    tr = cell["traffic"]
    corpus = Corpus(cell["cfg"]["vocab_size"], seed, tr["zipf_a"], tr["motif_len"])
    return lambda step: corpus.batch(step, 0, cell["global_batch"], cell["seq_len"])


def first_steps(trainer, tokens) -> dict:
    """Drive ``trainer`` through the first steps; the numbers ``check`` compares."""
    out = {"losses": []}
    for step in range(WARM_STEPS):
        out["losses"].append(float(trainer.step(trainer.place(tokens(step)))))
        if step == 0:
            out["grad_norms"] = trainer.first_grad_norms()
    out["change_norms"] = trainer.change_norms()
    return out


def reference_steps(cell: dict, seed: int, devices, **kw) -> dict:
    """The reference's first steps for ``cell`` and ``seed`` (``kw`` picks a
    precision or a planted fault)."""
    ref = reference.Trainer(cell["cfg"], seed, devices, rows_per_block=cell["ref_rows_per_block"],
                            replicas=cell["chips"], **kw)
    out = first_steps(ref, token_source(cell, seed))
    ref.free()
    return out


def run(name: str, seed: int, seconds: float, traced: bool, *, t0: float,
        make_trainer=None, devices=None, root: str = ROOT, log=sys.stderr) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``make_trainer(cfg, cell, seed, devices)`` builds what the window drives
    (the program by default); ``devices`` defaults to the chips the cell asks
    for, and anything else than TPUs raises NoChip."""
    cell = load_cell(name, root)
    cfg = cell["cfg"]
    if devices is None:
        devices = chips(cell["chips"])
    kind = devices[0].device_kind
    if devices[0].platform == "tpu" and kind not in cell["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    tag = f"[chipbench {devices[0].platform} {kind} x{len(devices)}]"
    if make_trainer is None:
        from chipbench.program import Trainer as make_trainer
    b, s = cell["global_batch"], cell["seq_len"]
    tokens = token_source(cell, seed)
    trainer = make_trainer(cfg, cell, seed, devices)
    prog = first_steps(trainer, tokens)
    setup_s = time.perf_counter() - t0
    print(f"{tag} {name} seed {seed}: set-up {setup_s:.3f} s, first losses "
          f"{prog['losses']}", file=log, flush=True)

    trace_dir = os.path.join(root, ".chipbench_trace")
    times, losses, parts = [], [], []
    step = WARM_STEPS
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with CompileCounter() as compiles:
        start = time.perf_counter()
        while (len(times) < cell["trace_steps"]) if traced else (time.perf_counter() - start < seconds):
            t = [time.perf_counter()]
            with jax.profiler.TraceAnnotation("bench.batch"):
                toks = tokens(step)
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.place"):
                batch = trainer.place(toks)
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.step"):
                loss = trainer.step(batch)
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.wait"):
                loss = jax.block_until_ready(loss)
            t.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench.fetch"):
                losses.append(float(loss))
            t.append(time.perf_counter())
            times.append(t[-1] - t[0])
            parts.append([b - a for a, b in zip(t, t[1:])])
            step += 1
        window_s = time.perf_counter() - start
    if traced:
        jax.profiler.stop_trace()
    print(f"{tag} window: {len(times)} steps in {window_s:.3f} s, "
          f"compilations in the window: {compiles.count}", file=log, flush=True)
    slow = sorted(range(len(times)), key=lambda i: -times[i])[:3]
    print(f"{tag} slowest steps (step: batch, place, dispatch, wait, fetch s): " + "; ".join(
        f"{i}: " + ", ".join(f"{x:.4f}" for x in parts[i]) for i in slow), file=log, flush=True)
    device = device_info(devices)
    trainer.free()
    del trainer
    gc.collect()

    t = time.perf_counter()
    values = check.readings(prog, reference_steps(cell, seed, devices))
    print(f"{tag} reference: {time.perf_counter() - t:.3f} s", file=log, flush=True)
    values["nonfinite_losses"] = sum(not math.isfinite(x) for x in losses)
    ok, table = check.verdict(values, {**cell["limits"], "nonfinite_losses": 0})
    result = {"correct": ok, "attempted": len(times), "failed": values["nonfinite_losses"]}

    steps_per_sample = cell["steps_per_sample"]
    if traced:
        events = trace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = trace.Context(events, steps=len(times), chips=len(devices), cell=cell,
                            peaks=cell["peaks"].get(kind, {}),
                            metric=lambda n: load_metric(n, root).read(ctx))
        metrics = {}
        for m in cell["per_layer"]:
            value = load_metric(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=ctx.busy_s(), window_s=ctx.window_s())
        result.update(metrics=metrics, device=device, breakdown=ctx.breakdown())
    else:
        k = min(steps_per_sample, len(times))
        n = len(times) // k * k
        samples = np.asarray(times[:n]).reshape(-1, k).mean(axis=1)
        e2e = {
            "tokens_per_s": len(times) * b * s / window_s,
            "step_ms_p90": _p90(samples.tolist()) * 1e3,
            "setup_s": setup_s,
        }
        result.update(metrics={m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                               for m in cell["end_to_end"]}, device=device)
    result["checks"] = table
    for k, t in table.items():
        print(f"{tag} check {k} {t['value']!r} limit {t['limit']!r}", file=log)
    return result
