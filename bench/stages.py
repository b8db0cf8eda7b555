"""Device time by the program's stage names, idle time by its spans.

The program names its run loop's stages with ``jax.named_scope``
(``repro.loop.direction``, ``repro.push.tier0.gather``,
``repro.push.fallback.sweep``, ...) and its host phases with profiler
annotations (``repro.translate``, ``repro.run.dispatch``,
``repro.run.wait``, ...); see ``src/repro/core/tracing.py``.  A trace
keeps each device operation's scope path as the ``tf_op`` stat of the
op's event metadata, which ``jax.profiler.ProfileData`` does not expose,
so this module reads the ``.xplane.pb`` itself, with a small protobuf
wire-format reader.  Over the harness's ``bench.window`` span:

* ``device_stages``: each ``XLA Ops`` event's self time (its duration
  less the events nested in it, so a ``while`` or ``cond`` does not count
  its body twice), summed by the innermost ``repro.*`` component of its
  scope path (``none`` for an op outside every stage), averaged over the
  devices.  The stages sum to ``busy_s``.
* ``idle_gaps``: device idle time by the innermost ``bench.*`` or
  ``repro.*`` host span covering it (``bench.`` labels lose their prefix,
  as in ``bench/trace.py``; ``none`` outside every span).
* ``loop_runs``: the run loop's executions (``jit_loop`` on the
  ``XLA Modules`` line) and, for each, the ``repro.run`` span it lies in:
  how many lie inside one, and ``skew_s``, the most any of them reaches
  outside its span (0 when the host and device clocks agree).

``python3 -m bench.stages <trace.xplane.pb>`` prints the reduction as JSON.
"""
from __future__ import annotations

import collections
import json
import sys

from bench import trace

STAGE_PREFIX = "repro."
NO_STAGE = "none"
LOOP_MODULE = "jit_loop("


# -- the protobuf wire format ------------------------------------------------

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf, span):
    """``(field number, value)`` of the message in ``buf[lo:hi]``: an int
    for a varint or fixed-width field, a ``(lo, hi)`` span for a
    length-delimited one."""
    i, hi = span
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 and
# stat_metadata = 5 (maps: key = 1, value = 2); XLine: name = 2,
# timestamp_ns = 3, events = 4; XEvent: metadata_id = 1, offset_ps = 2,
# duration_ps = 3, stats = 4; XEventMetadata: name = 2, stats = 5;
# XStatMetadata: name = 2; XStat: metadata_id = 1, str_value = 5,
# ref_value = 7 (the id of a stat metadata whose name is the string).

def _map_values(buf, span):
    key = val = None
    for f, v in _fields(buf, span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _scope(buf, stats, scope_id, names):
    """The ``tf_op`` string among ``stats`` (spans of XStat), or None."""
    for span in stats:
        sid = text = None
        for f, v in _fields(buf, span):
            if f == 1:
                sid = v
            elif f == 5:
                text = _text(buf, v)
            elif f == 7:
                text = names.get(v)
        if sid == scope_id and text is not None:
            return text
    return None


def _plane(buf, span):
    name, lines, emeta, smeta = "", [], {}, {}
    for f, v in _fields(buf, span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, m = _map_values(buf, v)
            emeta[k] = m
        elif f == 5:
            k, m = _map_values(buf, v)
            smeta[k] = next((_text(buf, s) for g, s in _fields(buf, m)
                             if g == 2), "")
    scope_id = next((k for k, n in smeta.items() if n == "tf_op"), None)
    meta = {}
    for k, m in emeta.items():
        mname, stats = "", []
        for f, v in _fields(buf, m or (0, 0)):
            if f == 2:
                mname = _text(buf, v)
            elif f == 5:
                stats.append(v)
        meta[k] = (mname, _scope(buf, stats, scope_id, smeta)
                   if scope_id is not None else None)
    out = []
    for lspan in lines:
        lname, ts_ns, events = "", 0, []
        for f, v in _fields(buf, lspan):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                ts_ns = v
            elif f == 4:
                events.append(v)
        evs = []
        for espan in events:
            mid = offset_ps = dur_ps = 0
            for f, v in _fields(buf, espan):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    dur_ps = v
            ename, scope = meta.get(mid, ("", None))
            # whole nanoseconds, as ProfileData gives them
            evs.append((ename, float((ts_ns * 1000 + offset_ps) // 1000),
                        float(dur_ps // 1000), scope))
        out.append((lname, evs))
    return name, out


def load_planes(path: str):
    """The device and host planes of an ``.xplane.pb``: ``(plane name,
    [(line name, [(event name, start_ns, duration_ns, scope)])])``, where
    ``scope`` is the event's ``tf_op`` scope path or None."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for field, span in _fields(buf, (0, len(buf))):
        if field != 1:
            continue
        name = next((_text(buf, v) for f, v in _fields(buf, span)
                     if f == 2), "")
        if name.startswith(("/device:", "/host:CPU")):
            planes.append(_plane(buf, span))
    return planes


# -- the reduction -----------------------------------------------------------

def stage_of(scope: str | None) -> str:
    """The innermost ``repro.*`` component of a scope path (whose last
    component may end in ``:<op type>``)."""
    parts = [p.split(":")[0] for p in (scope or "").split("/")
             if p.startswith(STAGE_PREFIX)]
    return parts[-1] if parts else NO_STAGE


def self_times(ops, lo: float, hi: float) -> collections.Counter:
    """Self nanoseconds inside ``[lo, hi]`` by stage, over ``(name, start,
    duration, scope)`` events of one line: an event's duration less the
    events nested in it (a child reaching past its parent's end is cut
    there)."""
    def inside(s, e):
        return max(0.0, min(e, hi) - max(s, lo))

    out = collections.Counter()
    stack = []                            # [end, stage, self], outermost first
    for _, s, d, scope in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            _, stage, own = stack.pop()
            out[stage] += own
        e = s + d
        if stack:
            e = min(e, stack[-1][0])
            stack[-1][2] -= inside(s, e)
        stack.append([e, stage_of(scope), inside(s, e)])
    for _, stage, own in stack:
        out[stage] += own
    return out


def _loop_runs(modules, runs) -> dict:
    """Each run-loop execution against the ``repro.run`` span overlapping
    it most: how many lie inside one, and the most any reaches outside."""
    inside, skew = 0, 0.0
    for s, e in modules:
        best = max(runs, default=None,
                   key=lambda r: min(e, r[1]) - max(s, r[0]))
        if best is None or min(e, best[1]) <= max(s, best[0]):
            out = e - s
        else:
            out = max(best[0] - s, 0.0) + max(e - best[1], 0.0)
        inside += out == 0
        skew = max(skew, out)
    return {"executions": len(modules), "inside_run": inside,
            "skew_s": skew * 1e-9}


def reduce_planes(planes, devices: int | None = None) -> dict:
    """The reduction over :func:`load_planes`' tuples (see the module
    docstring), with ``window_s`` and ``busy_s`` as ``bench/trace.py``
    computes them."""
    base = trace.reduce_planes(
        [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
         for p, lines in planes], devices)
    spans, dev = [], []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            ops = [ev for ln, evs in lines if ln == trace.OPS_LINE
                   for ev in evs]
            if ops:
                dev.append((ops, [ev for ln, evs in lines
                                  if ln == "XLA Modules" for ev in evs]))
        else:
            spans += [ev for _, evs in lines for ev in evs
                      if ev[0].startswith((trace.SPAN_PREFIX, STAGE_PREFIX))]
    lo, hi = next((s, s + d) for n, s, d, _ in spans
                  if n == trace.SPAN_PREFIX + "window")
    stages = collections.Counter()
    for ops, _ in dev:
        stages.update(self_times(ops, lo, hi))
    union = trace._union(trace._clip([(s, s + d) for _, s, d, _ in dev[0][0]],
                                     lo, hi))
    labelled = [(s, s + d, n[len(trace.SPAN_PREFIX):]
                 if n.startswith(trace.SPAN_PREFIX) else n)
                for n, s, d, _ in spans if n != trace.SPAN_PREFIX + "window"]
    gaps = trace._label_gaps(trace._complement(union, lo, hi), labelled)
    runs = [(s, s + d) for n, s, d, _ in spans if n == STAGE_PREFIX + "run"]
    modules = [(s, s + d) for n, s, d, _ in dev[0][1]
               if n.startswith(LOOP_MODULE) and s < hi and s + d > lo]
    ns = 1e-9
    total = sum(stages.values())
    return {
        "window_s": base["window_s"],
        "busy_s": base["busy_s"],
        "device_stages": [[n, t / len(dev) * ns]
                          for n, t in stages.most_common()],
        "staged_share": 1.0 - stages[NO_STAGE] / total if total else 0.0,
        "idle_gaps": [[n, t * ns] for n, t in gaps.most_common()],
        "loop_runs": _loop_runs(modules, runs),
    }


def stage_seconds(summary: dict, prefix: str) -> float:
    """Device seconds of the stages whose names start with ``prefix``
    (``repro.push.fallback`` reads the fallback and its parts)."""
    return sum(t for n, t in summary["device_stages"] if n.startswith(prefix))


def reduce(path: str, devices: int | None = None) -> dict:
    return reduce_planes(load_planes(path), devices)


if __name__ == "__main__":
    for p in sys.argv[1:]:
        print(json.dumps({"trace": p, **reduce(p)}))
