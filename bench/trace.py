"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* ``window_s``: the length of the harness's ``bench.window`` host span.
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the events of its ``XLA Ops`` line), clipped to the window and
  averaged over the devices.
* ``device_ops``: the ten operations that took most device time, by HLO
  name (a ``while`` holds the operations of its body, so both count).
* ``idle_gaps``: device idle time inside the window, summed by what the
  host was doing meanwhile (the innermost ``bench.*`` span covering each
  stretch of a gap, ``none`` outside them all), the ten largest.
"""
from __future__ import annotations

import collections
import glob
import os

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_trace(directory: str) -> str:
    """The newest ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def op_name(event_name: str) -> str:
    """The HLO operation's name: the trace names an event by its whole
    instruction text, ``%fusion.2 = s32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merge ``(start, end)`` pairs; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _complement(union, lo, hi):
    """The gaps of a sorted disjoint union inside ``[lo, hi]``."""
    out, prev = [], lo
    for s, e in union:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out


def _label_gaps(gaps, spans) -> collections.Counter:
    """Idle time by the innermost (shortest) span covering it, ``none``
    where no span does.  ``spans`` are ``(start, end, label)``."""
    events = sorted([(a, 1, i) for i, (a, _, _) in enumerate(spans)]
                    + [(b, 0, i) for i, (_, b, _) in enumerate(spans)])
    # elementary stretches between span boundaries, each with the label of
    # the shortest span open over it (None outside every span)
    stretches, open_spans, prev = [], set(), None
    for t, opening, i in events:
        if prev is not None and t > prev:
            inner = min(open_spans, default=None,
                        key=lambda k: spans[k][1] - spans[k][0])
            stretches.append((prev, t, None if inner is None
                              else spans[inner][2]))
        (open_spans.add if opening else open_spans.discard)(i)
        prev = t
    out = collections.Counter()
    k = 0
    for a, b in gaps:
        while k < len(stretches) and stretches[k][1] <= a:
            k += 1
        covered = 0.0
        j = k
        while j < len(stretches) and stretches[j][0] < b:
            s, e, label = stretches[j]
            part = min(b, e) - max(a, s)
            if part > 0 and label is not None:
                out[label] += part
                covered += part
            j += 1
        if b - a > covered:
            out["none"] += b - a - covered
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_planes(planes, devices: int | None = None) -> dict:
    """The reduction over ``(plane_name, [(line_name, [(name, start_ns,
    duration_ns)])])`` tuples: what :func:`reduce` reads from a file."""
    spans, dev_ops = [], []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            ops = [ev for lname, evs in lines if lname == OPS_LINE
                   for ev in evs]
            if ops:
                dev_ops.append(ops)
        elif pname.startswith("/host:"):
            spans += [ev for _, evs in lines for ev in evs
                      if ev[0].startswith(SPAN_PREFIX)]
    windows = [(s, s + d) for n, s, d in spans
               if n == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one {SPAN_PREFIX}window span, found "
                         f"{len(windows)}")
    if not dev_ops:
        raise ValueError("no device operations in the trace")
    if devices is not None and len(dev_ops) != devices:
        raise ValueError(f"operations on {len(dev_ops)} devices, expected "
                         f"{devices}")
    lo, hi = windows[0]
    busy_ns = 0.0
    by_name = collections.Counter()
    unions = []
    for ops in dev_ops:
        iv = _clip([(s, s + d) for _, s, d in ops], lo, hi)
        u = _union(iv)
        unions.append(u)
        busy_ns += sum(e - s for s, e in u)
        for name, s, d in ops:
            if s + d > lo and s < hi:
                by_name[op_name(name)] += min(s + d, hi) - max(s, lo)
    gaps = _label_gaps(_complement(unions[0], lo, hi),
                       [(s, s + d, n[len(SPAN_PREFIX):]) for n, s, d in spans
                        if n != SPAN_PREFIX + "window"])
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_ns / len(dev_ops) * ns,
        "device_ops": [[n, t * ns] for n, t in by_name.most_common(10)],
        "idle_gaps": [[n, t * ns] for n, t in gaps.most_common(10)],
    }


def load_planes(path: str):
    """The planes of an ``.xplane.pb`` file, as plain tuples."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(line.name, [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events])
                      for line in p.lines])
            for p in pd.planes]


def reduce(path: str, devices: int | None = None) -> dict:
    return reduce_planes(load_planes(path), devices)
