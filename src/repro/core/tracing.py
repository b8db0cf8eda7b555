"""Host spans and device stage names, on the JAX profiler's clock.

``span(name)`` times a host phase (``translate``, ``layout.reverse``,
``run.wait``, ...) and writes it into the profiler's trace as a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``: on the same host
plane and clock as the device's operations, so a trace says what the
host was doing while the device ran or idled.  The span also keeps its
own seconds (``perf_counter``), which the caller reads after the block,
so the program's timing reports and the trace come from the same spans.
Spans nest: each knows the seconds of the spans that ran inside it, and
``self_s`` is what is left.

``stage(name)`` is ``jax.named_scope("repro.<name>")``: trace-time
metadata that names every device operation staged inside it (the
``op_name`` of its HLO metadata, kept in a profiler trace as the op's
``tf_op`` scope path).  It adds nothing to the compiled program.

Names are fixed strings, so a trace holds a small set of them.  Costs:
an annotation is about a microsecond with the profiler off.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import jax

PREFIX = "repro."

_open = threading.local()       # the spans open on this thread, innermost last
_sinks: list[list] = []         # collect() lists, each given every ended span


@dataclasses.dataclass
class Span:
    name: str                   # with the ``repro.`` prefix
    seconds: float = 0.0        # wall seconds, set when the block ends
    child_s: float = 0.0        # seconds of the spans nested directly in it

    @property
    def self_s(self) -> float:
        return self.seconds - self.child_s


@contextlib.contextmanager
def span(name: str):
    """Time the block as the host span ``repro.<name>``; yields its
    :class:`Span`, whose ``seconds`` are set when the block ends."""
    stack = _open.__dict__.setdefault("stack", [])
    sp = Span(PREFIX + name)
    stack.append(sp)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(sp.name):
            yield sp
    finally:
        sp.seconds = time.perf_counter() - t0
        stack.pop()
        if stack:
            stack[-1].child_s += sp.seconds
        for sink in tuple(_sinks):
            sink.append(sp)


@contextlib.contextmanager
def collect():
    """Yield a list that receives every span ending inside the block, in
    the order they end (innermost first)."""
    ended: list[Span] = []
    _sinks.append(ended)
    try:
        yield ended
    finally:
        _sinks.remove(ended)


def stage(name: str):
    """Name the device operations staged inside the block
    ``repro.<name>`` (a ``jax.named_scope``)."""
    return jax.named_scope(PREFIX + name)
