"""Trace serialization: JSONL, the cost-model interchange format. Schema
documented in DESIGN.md §13. (For a timeline, capture a profiler trace:
enabled spans appear in it as ``TraceAnnotation`` events.)

JSONL layout — line 1 is a header object carrying the schema version, every
following line is one span dict::

    {"schema": 1, "kind": "repro-trace", "clock": "perf_counter", ...}
    {"name": "autotune.probe", "id": 3, "parent": 2, "depth": 1, ...}

``read_jsonl`` refuses files whose header major version it does not know, so
a cost model never silently fits fields that changed meaning.
"""
from __future__ import annotations

import json
import platform
from typing import Iterable

from repro.trace.tracer import SCHEMA_VERSION, Tracer


def _spans_of(trace) -> list[dict]:
    if isinstance(trace, Tracer):
        return trace.spans
    return list(trace)


def header(extra: dict | None = None) -> dict:
    h = {
        "schema": SCHEMA_VERSION,
        "kind": "repro-trace",
        "clock": "perf_counter",
        "host": platform.node(),
    }
    if extra:
        h.update(extra)
    return h


def write_jsonl(trace: Tracer | Iterable[dict], path, *,
                extra_header: dict | None = None) -> str:
    """Write header + one span per line; returns the path written."""
    spans = _spans_of(trace)
    with open(path, "w") as f:
        f.write(json.dumps(header(extra_header)) + "\n")
        for sp in spans:
            f.write(json.dumps(sp) + "\n")
    return str(path)


def read_jsonl(path) -> tuple[dict, list[dict]]:
    """Load (header, spans) back; raises ValueError on a missing header or
    an unknown schema version."""
    with open(path) as f:
        lines = [ln for ln in f if ln.strip()]
    if not lines:
        raise ValueError(f"empty trace file: {path}")
    head = json.loads(lines[0])
    if head.get("kind") != "repro-trace":
        raise ValueError(
            f"{path} is not a repro trace (missing header line; "
            f"first line: {lines[0][:80]!r})")
    if head.get("schema") != SCHEMA_VERSION:
        raise ValueError(
            f"{path} has trace schema {head.get('schema')!r}; this reader "
            f"understands schema {SCHEMA_VERSION}")
    return head, [json.loads(ln) for ln in lines[1:]]
