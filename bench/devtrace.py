"""Device trace reduction: busy time, each program's and each kernel's
device time, the top device ops, and idle gaps attributed to what the
host was doing.

``read_events`` turns the profiler's ``.xplane.pb`` into plain ``Event``
records; ``reduce`` works on those records only, so it is checked on the
CPU against a small trace recorded on the chip (``tests/test_devtrace.py``).

What a TPU trace holds, and how it is read:

- each ``/device:TPU:n`` plane has an ``XLA Modules`` line (one event per
  program execution, named ``jit_<fn>(<fingerprint>)``; the engine's
  programs are jitted partials, so they show as ``jit__unknown``) and an
  ``XLA Ops`` line (one event per HLO op, named by its HLO text; ops of a
  while loop nest inside the loop's own event);
- an op belongs to the program execution whose interval holds it;
- a program execution belongs to the host span (the driver's
  ``TraceAnnotation``) that started last before the host enqueued it
  (the ``DoEnqueueProgram`` event with the execution's ``run_id``; where
  a trace lacks one, last before the execution's start on the device,
  within a skew allowance of the two timelines, ``SKEW_NS``): the engine
  dispatches its fused decode inside ``step_window`` and its admission
  waves inside ``join_many``, so the two are told apart by that span;
- busy time is the union of op intervals, averaged over device planes;
- a Pallas kernel is an op whose HLO is a ``tpu_custom_call``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_SPANS = ("on_request", "next_batch", "join_many", "step_window")
ENQUEUE = "DoEnqueueProgram"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = "tpu_custom_call"
# the device timeline can lead the host's by tens of microseconds (26 us
# seen on a v5e): a program that starts this soon before a host span
# belongs to it
SKEW_NS = 50_000.0


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    run: int = -1        # the device run id of a program or its enqueue

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_KIND = re.compile(r"\s([a-z][\w-]*)\(")


def short_name(hlo: str) -> str:
    """``%inst.3 = <shape> kind(...)`` -> ``%inst.3 = kind``, with the
    custom call's target for a custom call; other names unchanged."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    m = _KIND.search(" " + rhs)
    kind = m.group(1) if m else "op"
    if kind == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', rhs)
        if t:
            kind = f"custom-call {t.group(1)}"
    return f"{lhs.strip()} = {kind}"


def latest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_events(path: str) -> List[Event]:
    """Device programs and ops, and the driver's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out += [Event(plane.name, line.name, short_name(e.name),
                                  float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    out += [Event(plane.name, line.name, e.name,
                                  float(e.start_ns), float(e.duration_ns),
                                  int(dict(e.stats).get("run_id", -1)))
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out.append(Event(plane.name, line.name, e.name,
                                         float(e.start_ns),
                                         float(e.duration_ns)))
                    elif e.name == ENQUEUE:
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            out.append(Event(plane.name, line.name, e.name,
                                             float(e.start_ns),
                                             float(e.duration_ns),
                                             int(run)))
    return out


def _merge(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


_SUFFIX = re.compile(r"(\(\d+\)|[._]\d+)$")


def module_of(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``."""
    return _SUFFIX.sub("", name)


def op_label(name: str) -> str:
    """``%copy.88 = copy`` -> ``copy:copy``; a kernel reads
    ``custom-call tpu_custom_call:<instruction>``."""
    if " = " not in name:
        return name
    lhs, kind = name.split(" = ", 1)
    return f"{kind}:{_SUFFIX.sub('', lhs.strip().lstrip('%'))}"


def _leaves(ops: List[Event]) -> List[Event]:
    """Ops that hold no other op (a while loop's event holds its body's)."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    parent = [False] * len(ops)
    stack: List[int] = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= ops[stack[-1]].end_ns:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(ops, parent) if not p]


def reduce(events: List[Event], lo_ns: float, hi_ns: float) -> dict:
    """Reduce the events inside ``[lo_ns, hi_ns)`` (the traced window).

    Returns ``busy_s`` and ``window_s``; ``programs``: {``span/module``:
    seconds}, each program execution keyed by the host span that started
    last before it and its module name; ``per_op``: {(program, op label):
    [seconds, count]} over leaf ops; ``device_ops``: the ten op labels
    (across programs) that took most time; ``idle_gaps``: the ten longest
    gaps between device ops, each named by the host span that overlaps it
    most (``host:other`` where none does).  Seconds are per device."""
    def inside(e):
        return e.end_ns > lo_ns and e.start_ns < hi_ns

    def clipped(e):
        return min(e.end_ns, hi_ns) - max(e.start_ns, lo_ns)

    dev = [e for e in events if e.plane.startswith("/device:")
           and inside(e)]
    host = sorted((e for e in events if e.plane.startswith("/host:")
                   and e.name in HOST_SPANS and inside(e)),
                  key=lambda e: e.start_ns)
    host_starts = [h.start_ns for h in host]
    enqueued: Dict[int, float] = {}
    for e in events:
        if e.name == ENQUEUE and e.run >= 0:
            enqueued[e.run] = min(enqueued.get(e.run, e.start_ns),
                                  e.start_ns)
    planes = sorted({e.plane for e in dev if e.line == OPS_LINE})
    n = max(len(planes), 1)
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    programs: Dict[str, List[Tuple[float, float]]] = {}
    per_op: Dict[Tuple[str, str], List[float]] = {}
    by_label: Dict[str, float] = {}
    for plane in planes:
        ops = [e for e in dev if e.plane == plane and e.line == OPS_LINE]
        mods = sorted((e for e in dev if e.plane == plane
                       and e.line == MODULES_LINE), key=lambda e: e.start_ns)
        keys = []
        for m in mods:
            t = enqueued.get(m.run, m.start_ns + SKEW_NS)
            i = bisect.bisect_right(host_starts, t) - 1
            span = host[i].name if i >= 0 else "host:other"
            keys.append(f"{span}/{module_of(m.name)}")
            programs.setdefault(keys[-1], []).append(
                (max(m.start_ns, lo_ns), min(m.end_ns, hi_ns)))
        mod_starts = [m.start_ns for m in mods]
        iv = _merge((max(e.start_ns, lo_ns), min(e.end_ns, hi_ns))
                    for e in ops)
        busy += sum(e - s for s, e in iv)
        edges = [lo_ns] + [x for s, e in iv for x in (s, e)] + [hi_ns]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for e in _leaves(ops):
            j = bisect.bisect_right(mod_starts, e.start_ns) - 1
            prog = keys[j] if j >= 0 and mods[j].end_ns >= e.end_ns \
                else "none"
            label = op_label(e.name)
            d = clipped(e) / 1e9
            acc = per_op.setdefault((prog, label), [0.0, 0])
            acc[0] += d / n
            acc[1] += 1
            by_label[label] = by_label.get(label, 0.0) + d / n
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    idle = []
    for s, e in longest:
        best, over = "host:other", 0.0
        for h in host:
            o = min(e, h.end_ns) - max(s, h.start_ns)
            if o > over:
                best, over = h.name, o
        idle.append([best, (e - s) / 1e9])
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n / 1e9, "window_s": (hi_ns - lo_ns) / 1e9,
            "programs": {k: sum(e - s for s, e in _merge(v)) / n / 1e9
                         for k, v in programs.items()},
            "per_op": per_op, "device_ops": [[k, v] for k, v in top],
            "idle_gaps": idle, "devices": len(planes)}


def window_of(events: List[Event]) -> Tuple[float, float]:
    """The span of all events read: the traced window."""
    return (min(e.start_ns for e in events), max(e.end_ns for e in events))


def program_seconds(red: dict, span: str, module: str) -> float:
    """Device seconds of the programs started under host span ``span``
    whose module name matches ``module`` (a regular expression)."""
    rx = re.compile(module)
    return sum(s for k, s in red["programs"].items()
               if k.split("/", 1)[0] == span and rx.search(k.split("/", 1)[1]))


def kernel_seconds(red: dict, span: str, module: str) -> Tuple[float, int]:
    """Device seconds and calls of the Pallas kernels inside those
    programs."""
    rx = re.compile(module)
    sec, cnt = 0.0, 0
    for (prog, label), (s, c) in red["per_op"].items():
        sp, _, mod = prog.partition("/")
        if sp == span and rx.search(mod) and KERNEL in label:
            sec += s
            cnt += c
    return sec, cnt // max(red["devices"], 1)
