"""Host spans recorded by the harness around its calls into the program.

Each span is kept in memory (name, start, end on ``time.perf_counter``)
and, at the same time, written into the profiler's trace as a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so that the
trace reduction can say what the host was doing in each idle gap of the
device.

Given ``peak``, a function that reads the device's peak memory in use,
each span also records that reading as it ends (``peaks[name]``, the
latest), so a run can say which phase set its memory peak.
"""
from __future__ import annotations

import collections
import contextlib
import time

import jax


class Spans:
    def __init__(self, peak=None):
        self.intervals: dict[str, list[tuple[float, float]]] = \
            collections.defaultdict(list)
        self.peak = peak
        self.peaks: dict[str, int] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        finally:
            self.intervals[name].append((t0, time.perf_counter()))
            if self.peak is not None:
                self.peaks[name] = self.peak()

    def total(self, name: str) -> float | None:
        """Seconds spent in spans called ``name``; None if there were none."""
        if name not in self.intervals:
            return None
        return sum(b - a for a, b in self.intervals[name])
