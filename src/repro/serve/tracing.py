"""Host spans of the serving path, on the wall clock.

A `SpanRecorder` keeps `(name, start_ns, dur_ns)` triples in memory, timed
with `time.time_ns()`: the clock the JAX profiler stamps its device events
with, so a reader can lay the spans over a device trace.  The engines hold
one in their public `tracer` attribute; `DISABLED`, the default, records
nothing and allocates nothing (`span()` hands back one shared no-op
context), so a span site costs an attribute load and a call when tracing
is off.

    engine.tracer = SpanRecorder()      # between pumps
    engine.pump()
    engine.tracer.spans                 # [("wave.fill", t, dur), ...]
    engine.tracer = DISABLED
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

Span = Tuple[str, int, int]            # (name, start_ns, dur_ns)

_OFF = contextlib.nullcontext()


class _Timed:
    """One open span: appends its triple on exit, exception or not."""

    __slots__ = ("_spans", "_name", "_t")

    def __init__(self, spans: List[Span], name: str):
        self._spans = spans
        self._name = name

    def __enter__(self):
        self._t = time.time_ns()

    def __exit__(self, *exc):
        t = self._t
        self._spans.append((self._name, t, time.time_ns() - t))
        return False


class SpanRecorder:
    """Host spans (name, start, duration) in wall-clock nanoseconds."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: List[Span] = []

    def span(self, name: str):
        """A context manager timing one span named `name`; switched off,
        the shared no-op context."""
        return _Timed(self.spans, name) if self.enabled else _OFF


DISABLED = SpanRecorder(enabled=False)
