"""CLI threading for the tracer — the ``add_agg_args`` pattern applied to
tracing: every entry point (launchers, examples, benchmarks) calls
``add_trace_args(parser)`` once and ``from_args(ns)`` after parsing, instead
of re-declaring ``--trace`` flags by hand::

    add_trace_args(ap)
    args = ap.parse_args()
    session = trace.from_args(args)
    ...                      # instrumented code records spans
    session.finish()         # writes --trace-out and prints a line

``--trace-out`` ending in ``.jsonl`` gets the recorded spans as JSONL (what
``--bucket-bytes auto`` reads); any other path is a directory, and the run
between ``from_args`` and ``finish`` is captured there by the JAX profiler:
the device ops, with the program's scopes in their metadata, and the host
spans beside them (``TraceAnnotation``), on one clock.

``from_args`` enables the GLOBAL tracer, so instrumentation deep in
core/switchsim/serve/runtime records without any handle threading.
"""
from __future__ import annotations

import argparse

import jax

from repro.trace import export, tracer


def add_trace_args(parser: argparse.ArgumentParser):
    g = parser.add_argument_group("tracing", "span tracer (repro.trace)")
    g.add_argument(
        "--trace", action="store_true",
        help="record host timing spans (train loop/switchsim/serve/runtime); "
             "implied by --trace-out")
    g.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="a PATH ending in .jsonl gets the recorded spans as JSONL with a "
             "schema header (feeds 'python -m repro.autotune' / --bucket-bytes "
             "auto); any other PATH is a directory for a JAX profiler capture "
             "of the run (device ops and host spans on one clock)")
    g.add_argument(
        "--trace-capacity", type=int, default=tracer._DEFAULT_CAPACITY,
        metavar="N", help="ring-buffer capacity in spans (oldest dropped)")
    return g


class TraceSession:
    """Handle returned by :func:`from_args`; ``finish()`` flushes the file or
    stops the profiler capture."""

    def __init__(self, enabled: bool, path: str | None, capacity: int):
        self.path = path
        self.enabled = enabled
        self.capture = bool(path) and not str(path).endswith(".jsonl")
        self.tracer = tracer.enable(capacity) if enabled else None
        if self.capture:
            jax.profiler.start_trace(str(path))

    def finish(self) -> str | None:
        """Write ``--trace-out`` (if any), stop the capture and disable the
        global tracer. Returns the path written, or None."""
        if not self.enabled:
            return None
        tracer.disable()
        tr = self.tracer
        if self.capture:
            jax.profiler.stop_trace()
            print(f"trace: profiler capture ({len(tr.spans)} spans) -> {self.path}")
            return str(self.path)
        if self.path:
            out = export.write_jsonl(tr, self.path)
            print(f"trace: {len(tr.spans)} spans -> {out}"
                  + (f" ({tr.dropped} dropped)" if tr.dropped else ""))
            return out
        print(f"trace: {len(tr.spans)} spans recorded (no --trace-out; "
              f"inspect repro.trace.get().spans)")
        return None


def from_args(ns: argparse.Namespace) -> TraceSession:
    """Enable the global tracer when ``--trace``/``--trace-out`` was given."""
    path = getattr(ns, "trace_out", None)
    enabled = bool(getattr(ns, "trace", False) or path)
    capacity = getattr(ns, "trace_capacity", tracer._DEFAULT_CAPACITY)
    return TraceSession(enabled, path, capacity)
