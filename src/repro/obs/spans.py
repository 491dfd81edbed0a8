"""Program spans in `jax.profiler`'s trace.

`span(name, **attrs)` is a host span on the profiler's own clock, the
one the device's events share: `jax.profiler.trace(dir)` around a
program's calls captures both, and an idle gap of the device can be put
down to the span the host was in. The profiler keeps the spans and
writes them when it stops; with no profiler running, entering and
leaving one is a single native check. Attributes become the event's
stats (`ProfileData` event ``stats``; TensorBoard's event details), and
``set_metadata`` on the object the ``with`` gives adds more after entry.

Every span of the program starts with ``flip.`` (docs/OBSERVABILITY.md,
"Profiler spans").
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **attrs) -> TraceAnnotation:
    """A context manager that records `name` with `attrs` as one host
    event of the profiler's trace (free when no trace is running)."""
    return TraceAnnotation(name, **attrs)
