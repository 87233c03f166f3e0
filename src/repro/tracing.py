"""Host spans of the program, on the profiler's clock.

``with span(name):`` (or ``@span(name)`` on a function) opens a
``jax.profiler.TraceAnnotation``, so a profiler trace shows the span on
its host plane, on the same clock as the device's operations, and adds
the span's ``perf_counter`` seconds and one call to this process's table
under ``name``.  :func:`span_totals` reads the table and
:func:`reset_spans` clears it.  There is no switch: a span costs one
annotation and two clock reads, so spans go only around coarse stages.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple

import jax


class SpanTotal(NamedTuple):
    seconds: float
    calls: int


_TOTALS: dict[str, SpanTotal] = {}
_LOCK = threading.Lock()


@contextlib.contextmanager
def span(name: str, **meta):
    """Time the block (or, as a decorator, each call) under ``name``;
    ``meta`` goes to the trace annotation only."""
    with jax.profiler.TraceAnnotation(name, **meta):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with _LOCK:
                s, n = _TOTALS.get(name, (0.0, 0))
                _TOTALS[name] = SpanTotal(s + dt, n + 1)


def span_totals() -> dict[str, SpanTotal]:
    """Copy of the table: ``name -> SpanTotal(seconds, calls)``."""
    with _LOCK:
        return dict(_TOTALS)


def reset_spans() -> None:
    """Empty the table."""
    with _LOCK:
        _TOTALS.clear()
