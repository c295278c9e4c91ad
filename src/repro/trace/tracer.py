"""Lightweight span tracer: nestable context-manager spans over
``time.perf_counter`` with a ring-buffer recorder (DESIGN.md §13).

Spans time HOST work: the train loop's batch/place/step/fetch, switchsim
rounds, serve prefill/decode, controller recovery, the autotune replay
profiler's synced phases. Device work inside a jitted program is named with
``repro.trace.scope`` instead (a ``jax.named_scope``), because the Python
body of a jitted function runs only while it is traced. Design constraints,
in order:

1. **Near-zero disabled path.** Instrumentation lives in hot loops that run
   with tracing off in production. ``span()`` with the tracer disabled is one
   attribute load, one bool test, and the return of a shared no-op singleton
   — no allocation, no clock read (pinned by tests/test_trace.py).
2. **One clock with the device.** An enabled span also enters a
   ``jax.profiler.TraceAnnotation`` of its name, so a profiler capture shows
   the program's host spans beside the device ops, on the profiler's clock.
3. **Attribution through sync boundaries.** jax dispatch is asynchronous: a
   ``perf_counter`` pair around an eager op measures dispatch, not device
   work. A span therefore exposes ``sync(value)`` which calls
   ``jax.block_until_ready`` *inside* the span, so the device work lands in
   the span that issued it. Under a jit trace the values are abstract
   Tracers — sync detects that, skips the block, and leaves the span marked
   ``synced=False`` so the cost model can ignore trace-time artifacts.
4. **Bounded memory.** Spans land in a ``deque(maxlen=capacity)`` ring:
   long-running jobs keep the most recent ``capacity`` spans and never grow.

Spans are used in the ``with`` form only (enforced by the ``timing-
discipline`` lint rule — a bare ``.start()`` with a forgotten end corrupts
the nesting stack)::

    with trace.span("autotune.probe", phase="encode", elems=n) as sp:
        state = sp.sync(encode_fn(x))
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from time import perf_counter

import jax
from jax.profiler import TraceAnnotation

SCHEMA_VERSION = 1

_DEFAULT_CAPACITY = 1 << 16


def _block_until_ready(value) -> bool:
    """Block on a pytree of device values; False when abstract (jit trace)."""
    if value is None:
        return False
    leaves = jax.tree_util.tree_leaves(value)
    if not leaves:
        return False
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return False  # inside a jit trace: timings would be trace-time lies
    jax.block_until_ready(leaves)
    return True


class _NullSpan:
    """The disabled path: a shared, stateless no-op (falsy, so callers can
    gate expensive tag computation with ``if sp:``)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def tag(self, **tags):
        return self

    def sync(self, value):
        return value


NULL_SPAN = _NullSpan()


class Span:
    """One timed region. Context-manager only (see module doc)."""

    __slots__ = ("name", "tags", "sid", "parent", "depth", "tid",
                 "t0", "t1", "synced", "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, tags: dict):
        self._tracer = tracer
        self.name = name
        self.tags = tags
        self.sid = -1
        self.parent = -1
        self.depth = 0
        self.tid = 0
        self.t0 = 0.0
        self.t1 = 0.0
        self.synced = False
        self._annotation = None

    def __bool__(self):
        return True

    def tag(self, **tags) -> "Span":
        """Attach/overwrite tags after entry (e.g. counts known only at the
        end of the region)."""
        self.tags.update(tags)
        return self

    def sync(self, value):
        """Block until ``value`` (a jax pytree) is ready, attributing its
        device time to this span; marks the span ``synced``. No-op (and
        ``synced`` stays False) for abstract values under a jit trace."""
        if _block_until_ready(value):
            self.synced = True
        return value

    # -- context manager ---------------------------------------------------

    def __enter__(self) -> "Span":
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.end()
        return False

    def start(self) -> "Span":
        # internal: callers use the ``with`` form (lint: timing-discipline)
        stack = self._tracer._stack()
        self.sid = next(self._tracer._ids)
        self.parent = stack[-1].sid if stack else -1
        self.depth = len(stack)
        self.tid = threading.get_ident()
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = perf_counter()
        return self

    def end(self) -> None:
        self.t1 = perf_counter()
        self._annotation.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # mismatched exits: unwind to self
            while stack and stack.pop() is not self:
                pass
        self._tracer._record(self)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "id": self.sid, "parent": self.parent,
            "depth": self.depth, "tid": self.tid, "ts": self.t0,
            "dur": self.t1 - self.t0, "synced": self.synced,
            "tags": self.tags,
        }


class Tracer:
    """Ring-buffer span recorder. One global instance serves the module-level
    ``span()`` helper; tests and the autotune profiler may build private
    ones."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY, *,
                 active: bool = True):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self.active = bool(active)
        self._ring: deque = deque(maxlen=capacity)
        self._ids = itertools.count()
        self._local = threading.local()
        self.dropped = 0

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, sp: Span) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(sp.to_dict())

    def span(self, name: str, **tags) -> Span | _NullSpan:
        if not self.active:
            return NULL_SPAN
        return Span(self, name, tags)

    @property
    def spans(self) -> list[dict]:
        """Recorded span dicts, oldest first."""
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0


# ---------------------------------------------------------------------------
# the global tracer — what instrumented modules talk to
# ---------------------------------------------------------------------------

_GLOBAL = Tracer(active=False)


def span(name: str, **tags):
    """Open a span on the global tracer (``with trace.span(...) as sp:``).

    THE hot-path entry point: when tracing is disabled this is one attribute
    load + bool test + shared-singleton return."""
    tr = _GLOBAL
    if not tr.active:
        return NULL_SPAN
    return Span(tr, name, tags)


def enable(capacity: int = _DEFAULT_CAPACITY) -> Tracer:
    """Turn the global tracer on (fresh ring) and return it."""
    global _GLOBAL
    _GLOBAL = Tracer(capacity, active=True)
    return _GLOBAL


def disable() -> None:
    _GLOBAL.active = False


def enabled() -> bool:
    return _GLOBAL.active


def get() -> Tracer:
    """The current global tracer (inspect ``.spans`` after a traced run)."""
    return _GLOBAL
