"""Named spans on the profiler's timeline.

Every host span the program puts on a ``jax.profiler`` trace is opened
here, so that one place names them all ``repro.<name>``: the serving
frontend and engine stages (``repro.frontend.*``, ``repro.engine.*``) and
the recovery phases that ``PhaseClock`` times (``repro.phase.<phase>``).
The profiler stamps each span on the same timeline as the device planes
of its ``.xplane.pb``, so an idle gap on the chip can be put down to the
host stage that was running across it.

A span goes to the profiler only: nothing is kept in Python, so the cost
does not grow with the steps served. With the profiler off a span costs
about a microsecond.

jax is imported at the first span, not at import: ``repro.obs`` stays
importable with the standard library alone, and without jax a span does
nothing.
"""
from __future__ import annotations

from contextlib import nullcontext

PREFIX = "repro."

_annotation = None       # jax.profiler.TraceAnnotation, or a no-op factory


def _no_annotation(_name: str):
    return nullcontext()


def _factory():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = _no_annotation
        _annotation = TraceAnnotation
    return _annotation


def span(name: str):
    """Context manager: ``repro.<name>`` on the profiler's host timeline
    for the duration of the block, while a trace is being recorded."""
    return _factory()(PREFIX + name)
