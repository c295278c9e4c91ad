"""Host spans and device scopes (DESIGN.md §13).

Host work is timed with spans (near-zero when disabled; an enabled span also
enters a ``jax.profiler.TraceAnnotation``, so a profiler capture shows it)::

    from repro import trace

    with trace.span("train.step", step=i):
        state = step_fn(state, batch)

Device work inside a jitted program is named with scopes (always on, free at
run time: ``jax.named_scope``, one of ``trace.SCOPES``)::

    with trace.scope("agg.psum"):
        man_sum = lax.psum(man, axes)

Control/export API::

    trace.enable(); ... ; trace.export.write_jsonl(trace.get(), path)

CLI threading: ``trace.add_trace_args(parser)`` + ``trace.from_args(ns)``.
"""
from repro.trace import export  # noqa: F401
from repro.trace.cli import TraceSession, add_trace_args, from_args  # noqa: F401
from repro.trace.export import read_jsonl, write_jsonl  # noqa: F401
from repro.trace.scopes import SCOPES, scope  # noqa: F401
from repro.trace.tracer import (  # noqa: F401
    NULL_SPAN, SCHEMA_VERSION, Span, Tracer, disable, enable, enabled, get,
    span,
)
