"""Helpers the per-layer metric readers share (``bench/metrics/``).

``ctx`` is what ``run.py`` hands every reader: the configuration
(``conf``), the peak table entry (``peak``), the driver's ``probe`` with
its per-request times and per-window work, the requests that count
(``attempted``), the traced interval on the host clock (``facts``) and
the trace's reduction (``trace``, None without a trace)."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import devtrace as T

# the engine's jitted programs: (host span they are dispatched under,
# module name).  They are jitted partials, which the trace names
# ``jit__unknown``; a named program would read as its function's name
DECODE = ("step_window", r"decode_multi_paged|jit__unknown")
PREFILL = ("join_many", r"prefill_wave|jit__unknown")


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), 95)) \
        if values else None


def in_window(ctx, t: float) -> bool:
    p = ctx["probe"]
    return p.ws <= t < p.we


def traced(ctx, t0: float, t1: float) -> bool:
    f = ctx["facts"]
    return (f["trace_on"] is not None and f["trace_off"] is not None
            and t0 >= f["trace_on"] and t1 <= f["trace_off"])


def traced_windows(ctx) -> list:
    """Decode windows (t_start, t_end, k, rows, ctx) run inside the trace."""
    return [w for w in ctx["probe"].windows if traced(ctx, w[0], w[1])]


def traced_waves(ctx) -> list:
    """Admission waves (t, rows) dispatched inside the trace and finished
    inside it: a traced decode window, whose readback waits for the wave
    on the device, started after each."""
    last = max((w[0] for w in traced_windows(ctx)), default=None)
    return [w for w in ctx["probe"].waves
            if last is not None and traced(ctx, w[0], w[0]) and w[0] <= last]


def _found(sec: float, which: tuple, work, what: str) -> float:
    """``sec``, or an error where the driver saw ``work`` for ``which``
    inside the trace and the trace holds none of it: a program, span or
    kernel renamed by the program would otherwise silence its metrics."""
    if work and not sec:
        raise LookupError(
            f"the trace holds no {what} started under the host span "
            f"{which[0]!r} in a module matching {which[1]!r}, though the "
            f"driver dispatched such work inside the trace")
    return sec


def program_seconds(ctx, which: tuple, work) -> float:
    return _found(T.program_seconds(ctx["trace"], *which), which, work,
                  "program")


def kernel_seconds(ctx, which: tuple, work) -> float:
    return _found(T.kernel_seconds(ctx["trace"], *which)[0], which, work,
                  "Pallas kernel")
