"""Open-loop driver around the program's own serve loop.

Requests are handed to ``MagnusService.on_request`` when they are due on
the wall clock, and served through the program's ``drive_paged`` with
``refill`` and ``backlog`` hooks; the scheduler's ``now`` is in seconds.
The driver times each request from when it was due:

- ``ingest``: when ``on_request`` took it (how late the generator ran);
- ``admit``: when ``join_many`` accepted it (queue wait);
- ``first`` / ``last``: the host readback of the decode window that
  delivered its first / last token (a window's tokens reach the host in
  one readback, so each of its tokens is delivered at that instant).

It watches the engine's public calls (``join_many``, ``step_window``)
and counters, and one private method: the wave dispatch
(``_dispatch_wave``), where it records each admission wave's (suffix,
cached prefix) rows for the prefix cache's share and the prefill FLOP
counts.  An engine without that method, or whose wave plans lack those
fields, is refused: the metrics that need them would go silent.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional


class StopServing(Exception):
    """Raised between decode windows: the measurement is over."""


class DrainCapExceeded(Exception):
    """The requests that count were not finished within the drain cap."""


class Probe:
    """Per-request times and per-window work, on the host clock."""

    def __init__(self, svc, requests: List, *, t0: float, window: tuple,
                 judge: str, drain_cap_s: float, prompt_len: Dict[int, int],
                 spans: bool = False):
        self.svc = svc
        self.reqs = requests                 # sorted by arrival_time
        self.by_id = {r.req_id: r for r in requests}
        self.t0 = t0
        self.ws, self.we = window            # absolute perf_counter times
        self.judge = judge
        self.cap = self.we + drain_cap_s
        self.prompt_len = prompt_len
        self.spans = spans
        self.next_i = 0
        self.t_ingest: Dict[int, float] = {}
        self.t_admit: Dict[int, float] = {}
        self.t_first: Dict[int, float] = {}
        self.t_last: Dict[int, float] = {}
        self.delivered: Dict[int, int] = {}  # tokens of the current attempt
        self.in_window_tokens = 0
        self._win_tok: Dict[int, int] = {}  # per request, counted in window
        self.windows: List[tuple] = []      # (t_start, t_end, k, rows, ctx)
        self.waves: List[tuple] = []        # (t, [(suffix, prefix), ...])
        self.evicted = 0
        self.closed = False
        self.on_tick = None                  # called between windows
        # steady: the requests due in the window, until each is finished
        self._open = {r.req_id for r in self.attempted()} \
            if judge == "steady" else set()

    # -- clock ---------------------------------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def due(self, r) -> float:
        return self.t0 + r.arrival_time

    def span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- the set that counts ---------------------------------------------------
    def attempted(self) -> List:
        """Steady: every request due in the window.  Overload: every
        request admitted in the window."""
        if self.judge == "steady":
            return [r for r in self.reqs if self.ws <= self.due(r) < self.we]
        return [self.by_id[i] for i, t in self.t_admit.items()
                if self.ws <= t < self.we]

    def done_with_window(self, now: float, shed_log) -> bool:
        if now < self.we:
            return False
        if self._open and shed_log:
            self._open -= {s.req.req_id for s in shed_log}
        return not self._open

    # -- hooks for drive_paged -------------------------------------------------
    def ingest(self) -> None:
        """Hand every request now due to the service."""
        if self.closed:
            return
        now = self.now()
        rel = now - self.t0
        while self.next_i < len(self.reqs) \
                and self.reqs[self.next_i].arrival_time <= rel:
            r = self.reqs[self.next_i]
            with self.span("on_request"):
                self.svc.on_request(r, rel)
            self.t_ingest[r.req_id] = self.now()
            self.next_i += 1

    def refill(self, steps: int):
        self.ingest()
        if self.closed:
            return None
        with self.span("next_batch"):
            b = self.svc.next_batch(self.now() - self.t0)
        return b.requests if b is not None else None

    def backlog(self) -> bool:
        return not self.closed and (
            self.next_i < len(self.reqs) or len(self.svc.batcher.queue) > 0)

    # -- engine events -----------------------------------------------------------
    def on_admit(self, reqs: List) -> None:
        t = self.now()
        for r in reqs:
            self.t_admit.setdefault(r.req_id, t)
            self.delivered[r.req_id] = 0

    def on_wave(self, rows: List[tuple]) -> None:
        self.waves.append((self.now(), rows))

    def on_window(self, t_start: float, finished: List, evicted: List,
                  k: int) -> None:
        t = self.now()
        gone = {r.req_id for r in evicted}
        for r in evicted:
            self._forget(r.req_id)
        if k > 0:
            rows = [rid for rid in self.delivered if rid not in gone]
            ctx = sum(self.prompt_len[rid] + self.delivered[rid]
                      for rid in rows)
            self.windows.append((t_start, t, k, len(rows), ctx))
            counted = self.ws <= t < self.we
            for rid in rows:
                self.delivered[rid] += k
                self.t_first.setdefault(rid, t)
                if counted:
                    self._win_tok[rid] = self._win_tok.get(rid, 0) + k
                    self.in_window_tokens += k
        for r in finished:
            self.t_last[r.req_id] = t
            self.delivered.pop(r.req_id, None)
            self._open.discard(r.req_id)

    def _forget(self, rid: int) -> None:
        """An evicted request restarts: the tokens it was delivered are
        generated again, so they are not counted as delivered."""
        self.evicted += 1
        self.delivered.pop(rid, None)
        self.in_window_tokens -= self._win_tok.pop(rid, 0)

    def tick(self, shed_log) -> None:
        """Between windows: trace control, end of measurement, drain cap."""
        now = self.now()
        if self.on_tick is not None:
            self.on_tick(now)
        if not self.closed and self.done_with_window(now, shed_log):
            self.closed = True
            raise StopServing
        if now > self.cap:
            raise DrainCapExceeded(
                f"drain cap passed {now - self.we:.1f}s after the window")


def timed_engine_class(base):
    """A subclass of the program's engine class ``base`` whose public
    serve calls report to ``self.probe``."""
    if not callable(getattr(base, "_dispatch_wave", None)):
        raise TypeError(f"{base.__name__} has no _dispatch_wave(plans): "
                        "the admission waves cannot be recorded")

    class Timed(base):
        probe: Optional[Probe] = None

        def join_many(self, reqs):
            reqs = list(reqs)
            p = self.probe
            if p is None:
                return super().join_many(reqs)
            with p.span("join_many"):
                n = super().join_many(reqs)
            p.on_admit(reqs[:n])
            return n

        def step_window(self, max_steps=None):
            p = self.probe
            if p is None:
                return super().step_window(max_steps)
            p.tick(self.shed_log)
            p.ingest()
            t_start = p.now()
            try:
                with p.span("step_window"):
                    finished, evicted, k = super().step_window(max_steps)
            except Exception as e:
                # a failed grow: its culprit is shed, the rest requeued
                lost = list(getattr(e, "evicted", ()))
                culprit = getattr(e, "culprit", None)
                p.on_window(t_start, [], lost + ([culprit] if culprit
                                                 else []), 0)
                raise
            p.on_window(t_start, finished, evicted, k)
            return finished, evicted, k

        def _dispatch_wave(self, plans):
            rows = [(len(pl["ids"]) - int(pl["cached"]), int(pl["cached"]))
                    for pl in plans]
            out = super()._dispatch_wave(plans)
            if self.probe is not None:
                self.probe.on_wave(rows)
            return out

    return Timed
