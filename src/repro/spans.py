"""Host spans that the profiler records and the program counts.

``Spans`` holds one object's ``{name: (seconds, calls)}``.  Each
``with spans("lgd/draw", step=3):`` opens
``jax.profiler.TraceAnnotation("lgd/draw", step=3)``, which the profiler
records only while a trace runs, and adds the interval's
``time.perf_counter`` seconds and one call under its name.  The totals are
host floats, so reading them never waits for the device.

Names are ``train/...`` (the trainer's loop), ``lgd/...`` (a draw) and
``index/...`` (a refresh); ``PERF.md`` lists each span with what reads it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Mapping, Tuple

from jax.profiler import TraceAnnotation

Totals = Dict[str, Tuple[float, int]]


class Spans:
    """One object's span counters.  Safe to enter from several threads
    (a pipeline's refresh worker and the trainer's thread)."""

    def __init__(self):
        self._totals: Totals = {}
        self._lock = threading.Lock()

    def __call__(self, name: str, **meta) -> "_Span":
        return _Span(self, name, meta)

    def _add(self, name: str, seconds: float):
        with self._lock:
            s, n = self._totals.get(name, (0.0, 0))
            self._totals[name] = (s + seconds, n + 1)

    def totals(self) -> Totals:
        """A copy of ``{name: (seconds, calls)}``."""
        with self._lock:
            return dict(self._totals)

    def seconds(self, name: str) -> float:
        with self._lock:
            return self._totals.get(name, (0.0, 0))[0]


class _Span:
    __slots__ = ("_spans", "_name", "_annotation", "_t0")

    def __init__(self, spans: Spans, name: str, meta: dict):
        self._spans, self._name = spans, name
        self._annotation = TraceAnnotation(name, **meta)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._spans._add(self._name, time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)
        return False


def merged(*totals: Mapping[str, Tuple[float, int]]) -> Totals:
    """The sum of several ``{name: (seconds, calls)}`` mappings."""
    out: Totals = {}
    for t in totals:
        for name, (s, n) in t.items():
            s0, n0 = out.get(name, (0.0, 0))
            out[name] = (s0 + s, n0 + n)
    return out
