"""The program's own host spans in a traced run, and the device's idle
time charged to them.

The program records dotted spans (``magnus.*``, ``engine.*``,
``radix.*``: ``repro.serving.trace``) as profiler annotations, in the
same ``.xplane.pb`` and on the same clock as the device ops.  Here:

- ``read_spans`` reads them, with their attrs;
- ``window`` is the traced window exactly as ``run.py`` computes it for
  ``device_idle`` (the span of the events ``devtrace.read_events``
  returns);
- ``attribute`` takes the device's busy intervals from those events
  (the union of device ops, as ``devtrace.reduce`` counts busy time) and
  charges each idle instant of the device to the innermost program span
  open at that instant: a parent gets only what none of its children
  covers, and idle time under no program span is charged to ``None``.
  It also sums each span's self time (its length less its children's);
- ``of(ctx)`` does this once per run and keeps the result in ``ctx``, so
  the readers in ``bench/metrics/`` parse the trace once.

A trace with no program span (a program that records none) gives None:
the readers then report nothing.
"""
from __future__ import annotations

import ast
import bisect
import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace as T

PREFIXES = ("magnus.", "engine.", "radix.")
FRONTEND = ("magnus.",)
ENGINE_HOST = ("engine.", "radix.")


@dataclasses.dataclass
class Span:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _value(v):
    """An attr as recorded: ints and floats as they are; a list the
    program passed (``req_ids``) comes back as its text and is parsed."""
    if isinstance(v, str) and v.startswith("["):
        try:
            return ast.literal_eval(v)
        except (ValueError, SyntaxError):
            return v
    return v


def read_spans(path: str) -> List[Span]:
    """The program's spans in the xplane at ``path``."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns),
                                    {k: _value(v) for k, v in e.stats}))
    return out


def window(events: List[T.Event]) -> Tuple[float, float]:
    """``[lo, hi)`` as ``run.py`` reads ``device_idle``: the span of the
    device events, widened to the host events read with them."""
    lo, hi = T.window_of(events)
    host = [e for e in events if e.plane.startswith("/host:")]
    if host:
        lo = min(lo, min(e.start_ns for e in host))
        hi = max(hi, max(e.end_ns for e in host))
    return lo, hi


def _innermost(spans: Sequence[Span]) -> List[Tuple[float, float, int]]:
    """Piecewise-constant innermost span: ``(t0, t1, i)`` segments from
    the first span's start to the last one's end; ``i`` indexes
    ``spans``, -1 where none is open.  The innermost of the spans open at
    an instant is the one that started last (the shorter on a tie)."""
    points = sorted({s.start_ns for s in spans} | {s.end_ns for s in spans})
    order = sorted(range(len(spans)), key=lambda i: spans[i].start_ns)
    heap: List[Tuple[float, float, int]] = []
    out, j = [], 0
    for t0, t1 in zip(points, points[1:]):
        while j < len(order) and spans[order[j]].start_ns <= t0:
            i = order[j]
            heapq.heappush(heap, (-spans[i].start_ns, spans[i].end_ns, i))
            j += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        out.append((t0, t1, heap[0][2] if heap else -1))
    return out


def _idle(events: List[T.Event], lo: float, hi: float
          ) -> Tuple[List[List[Tuple[float, float]]], int]:
    """Each device plane's idle intervals inside ``[lo, hi)``: the gaps
    in the union of its ops, as ``devtrace.reduce`` counts busy time."""
    planes = sorted({e.plane for e in events if e.plane.startswith("/device:")
                     and e.line == T.OPS_LINE
                     and e.end_ns > lo and e.start_ns < hi})
    gaps = []
    for plane in planes:
        busy = T._merge((max(e.start_ns, lo), min(e.end_ns, hi))
                        for e in events if e.plane == plane
                        and e.line == T.OPS_LINE
                        and e.end_ns > lo and e.start_ns < hi)
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps.append([(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]])
    return gaps, len(planes)


def attribute(events: List[T.Event], spans: List[Span], lo: float,
              hi: float) -> dict:
    """Device idle time in ``[lo, hi)`` charged to the innermost program
    span, and each span name's self time.

    Returns ``window_s``; ``idle_s``: {span name or None: seconds of
    device idle}, averaged over device planes; ``self_s``: {name:
    seconds} and ``count``: {name: spans}, over every span read."""
    segs = _innermost(spans)
    starts = [s[0] for s in segs]
    gaps, n = _idle(events, lo, hi)
    idle: Dict[Optional[str], float] = {}

    def charge(i: int, ns: float) -> None:
        key = spans[i].name if i >= 0 else None
        idle[key] = idle.get(key, 0.0) + ns / n / 1e9

    for plane_gaps in gaps:
        for g0, g1 in plane_gaps:
            covered = 0.0
            k = max(bisect.bisect_right(starts, g0) - 1, 0)
            while k < len(segs) and segs[k][0] < g1:
                t0, t1, i = segs[k]
                over = min(g1, t1) - max(g0, t0)
                if over > 0:
                    charge(i, over)
                    covered += over
                k += 1
            if g1 - g0 > covered:
                charge(-1, g1 - g0 - covered)
    self_s: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for t0, t1, i in segs:
        if i >= 0:
            self_s[spans[i].name] = self_s.get(spans[i].name, 0.0) \
                + (t1 - t0) / 1e9
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle, "self_s": self_s,
            "count": count, "devices": n}


def of(ctx) -> Optional[dict]:
    """``attribute`` over the run's trace, once per run (kept in
    ``ctx["spans"]``); None without a trace, a device op or a program
    span in it."""
    if "spans" not in ctx:
        ctx["spans"] = None
        tdir = ctx["facts"].get("trace_dir")
        path = T.latest_xplane(tdir) if tdir else None
        if path is not None:
            spans = read_spans(path)
            events = T.read_events(path)
            if spans and any(e.plane.startswith("/device:") for e in events):
                lo, hi = window(events)
                ctx["spans"] = attribute(events, spans, lo, hi)
    return ctx["spans"]


def idle_share(ctx, prefixes: Tuple[str, ...]) -> Optional[float]:
    """% of the traced window in which the device is idle under a span
    whose name starts with one of ``prefixes``; None where the trace
    holds no such span."""
    a = of(ctx)
    if a is None or not a["window_s"] \
            or not any(k.startswith(prefixes) for k in a["count"]):
        return None
    return 100.0 * sum(v for k, v in a["idle_s"].items()
                       if k is not None and k.startswith(prefixes)) \
        / a["window_s"]


def mean_self_ms(ctx, name: str) -> Optional[float]:
    """Mean self time of the spans named ``name`` (ms)."""
    a = of(ctx)
    if a is None or not a["count"].get(name):
        return None
    return 1e3 * a["self_s"].get(name, 0.0) / a["count"][name]
