"""Host spans and markers of the served path, on the profiler's clock.

``span(name, **meta)`` is a :class:`jax.profiler.TraceAnnotation`: while
a profiler trace is being recorded it lands on the same clock as the
device's events, so a reader can split the device's idle time by what
the host was doing; otherwise it costs one enabled-check.  ``mark(name,
**meta)`` is an empty span, a timed counter.  Importing this module
registers one ``jax.monitoring`` listener that marks every backend
compile as ``jax.compile`` with its ``ms``.

Metadata are keyword arguments of host Python ints, never device arrays
(formatting one while a trace is on would wait for the device).  The
names, their metadata and the metrics that read them are listed in
docs/TELEMETRY.md ("Spans").
"""
from __future__ import annotations

import jax
from jax._src.dispatch import BACKEND_COMPILE_EVENT


def span(name: str, **meta: int) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` over a ``with`` block."""
    return jax.profiler.TraceAnnotation(name, **meta)


def mark(name: str, **meta: int) -> None:
    """An empty span ``name`` at this instant."""
    with jax.profiler.TraceAnnotation(name, **meta):
        pass


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        mark("jax.compile", ms=int(round(duration_secs * 1e3)))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
