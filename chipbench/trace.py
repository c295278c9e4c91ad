"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into plain lists: per device,
the XLA ops that ran and the asynchronous ops in flight (name, start,
duration, opcode and output type, read from the HLO text the profiler names
each event with); and the benchmark's own host spans (``bench.*``), on the
same clock. Everything else here is arithmetic on those lists, so it can be checked
on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_SPAN = "bench."
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def parse_op(text: str, start: float, dur: float) -> list:
    """[name, start_ns, dur_ns, opcode, output type] of one XLA op event, whose
    name is the op's HLO text (``%fusion.3 = bf16[8]{0} fusion(...)``)."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    out = rest[:m.start()] if m else rest
    return [name.lstrip("%"), start, dur, opcode, _LAYOUT.sub("", out)[:120]]


def load(trace_dir: str) -> dict:
    """{"devices": {id: ops}, "async": {id: ops}, "host": [[name, start_ns, end_ns], ...]}
    from the one ``.xplane.pb`` under ``trace_dir``: each device's XLA ops
    (``parse_op``) and its asynchronous ops in flight, and the benchmark's
    host spans, on one clock."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    events = {"devices": {}, "async": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                key = "devices" if line.name == OPS_LINE else "async"
                events[key][int(m.group(1))] = [parse_op(e.name, e.start_ns, e.duration_ns)
                                                 for e in line.events]
            elif not m:
                events["host"] += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                                   for e in line.events if e.name.startswith(HOST_SPAN)]
    events["host"].sort(key=lambda s: s[1])
    return events


def save(events: dict, path: str) -> None:
    """Write ``load``'s lists as gzipped JSON (how ``tests/data`` was recorded)."""
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    for key in ("devices", "async"):
        events[key] = {int(k): v for k, v in events.get(key, {}).items()}
    return events


def _merge(intervals) -> list:
    """The union of [start, end) intervals, as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    return sum(e - s for s, e in _merge(intervals))


def _spans(ops, pred=lambda op: True):
    return [(op[1], op[1] + op[2]) for op in ops if pred(op)]


def is_collective(op) -> bool:
    return bool(COLLECTIVE.match(op[3]))


def is_async_edge(op) -> bool:
    """The start or done of an asynchronous op: no work of its own."""
    return op[3].endswith(("-start", "-done"))


CONTROL_FLOW = ("while", "conditional", "call")


def leaves(ops) -> list:
    """The ops that do work themselves: a loop's or a call's event spans the
    ops of its body, which the trace lists beside it."""
    return [op for op in ops if op[3] not in CONTROL_FLOW]


def uncovered_ns(targets, cover) -> float:
    """Length of the union of ``targets`` that no interval of ``cover`` overlaps."""
    return union_ns(targets) - union_ns(
        [(max(s, cs), min(e, ce)) for s, e in _merge(targets) for cs, ce in _merge(cover)
         if min(e, ce) > max(s, cs)])


class Context:
    """What a metric reader sees: the trace's lists, the number of traced
    steps, the cell (with its configuration and traffic), the chip's peaks, and
    ``metric(name)``, another reader's value.
    Times are per chip (the mean over the chips), in seconds. ``devices``
    holds each chip's ops that contain no other op, ``all_ops`` every op."""

    def __init__(self, events: dict, *, steps: int, chips: int, cell: dict, peaks: dict,
                 metric=None):
        self.steps, self.chips = steps, chips
        self.metric = metric  # another metric's value, by name
        self.cell, self.cfg, self.peaks = cell, cell["cfg"], peaks
        ids = sorted(events["devices"])[:chips]
        self.all_ops = [events["devices"][k] for k in ids]
        self.devices = [leaves(ops) for ops in self.all_ops]
        self.async_ops = [events.get("async", {}).get(k, []) for k in ids]
        self.host = events["host"]

    def window(self):
        """(start, end) ns of the traced steps, from the benchmark's host spans."""
        return self.host[0][1], max(s[2] for s in self.host)

    def window_s(self) -> float:
        s, e = self.window()
        return (e - s) / 1e9

    def _in_window(self, ops):
        s, e = self.window()
        return [op for op in ops if op[1] + op[2] > s and op[1] < e]

    def per_chip_s(self, fn, lines=None) -> float:
        """The mean over chips of ``fn(ops in the window)``, ns -> s; ``lines``
        picks the asynchronous ops in flight instead of the XLA ops."""
        lines = self.devices if lines is None else lines
        if not lines:
            return 0.0
        return sum(fn(self._in_window(ops)) for ops in lines) / len(lines) / 1e9

    def op_time_s(self, pred) -> float:
        """Device time of the ops that match ``pred``, per chip: synchronous
        ops for their run, asynchronous ones from start to done."""
        ran = self.per_chip_s(lambda ops: sum(op[2] for op in ops if pred(op) and not is_async_edge(op)))
        return ran + self.per_chip_s(lambda ops: sum(op[2] for op in ops if pred(op)), self.async_ops)

    def busy_s(self) -> float:
        """Time in which the device runs an op (a loop's own span included)."""
        return self.per_chip_s(lambda ops: union_ns(_spans(ops)), self.all_ops)

    def device_span_s(self) -> float:
        """From the first op's start to the last op's end, per chip."""
        return self.per_chip_s(lambda ops: (max(o[1] + o[2] for o in ops) - min(o[1] for o in ops))
                               if ops else 0)

    def exposed_s(self, pred) -> float:
        """Time of the ops that match ``pred`` (in flight, where asynchronous)
        during which no other op does work on that device."""
        s, e = self.window()

        def one(ops, flying):
            flying = [op for op in flying if op[1] + op[2] > s and op[1] < e]
            busy = _spans(ops, lambda op: not pred(op) and not is_async_edge(op))
            mine = _spans(ops, lambda op: pred(op) and not is_async_edge(op)) + _spans(flying, pred)
            return uncovered_ns(mine, busy)

        if not self.devices:
            return 0.0
        return sum(one(self._in_window(ops), fl) for ops, fl in zip(self.devices, self.async_ops)) / len(self.devices) / 1e9

    def idle_gaps(self):
        """[(host span open during the gap, gap s)] of every gap between device
        ops on the first chip, longest first."""
        if not self.devices:
            return []
        merged = _merge(_spans(self._in_window(self.all_ops[0])))
        s, e = self.window()
        bounds = [[s, s]] + merged + [[e, e]]
        gaps = []
        for (_, a), (b, _) in zip(bounds, bounds[1:]):
            if b > a:
                mid = (a + b) / 2
                open_ = [h for h in self.host if h[1] <= mid < h[2]]
                label = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "no span"
                gaps.append((label, (b - a) / 1e9))
        return sorted(gaps, key=lambda g: -g[1])

    def breakdown(self) -> dict:
        totals: dict = {}
        for ops in self.devices:
            for op in self._in_window(ops):
                label = f"{op[0]} {op[4]} {op[3]}"
                totals[label] = totals.get(label, 0) + op[2]
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, t / max(len(self.devices), 1) / 1e9] for n, t in top],
                "idle_gaps": [[n, g] for n, g in self.idle_gaps()[:10]]}
