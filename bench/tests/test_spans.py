"""Idle time charged to the program's spans (``bench/spans.py``), checked
on the CPU against a small trace recorded on a TPU v5e
(``fixtures/v5e_spans_trace.json``: about 40 ms of smollm-135m serving
the lmaas-steady cell, from a window's readback through an arrival's
``magnus.predict`` to the next decode, with the device ops, program
executions, driver spans and program spans that overlap it), and on
traces made here."""
import json
import os

import numpy as np
import pytest

import devtrace as T
import spans as S

FIX = os.path.join(os.path.dirname(__file__), "fixtures",
                   "v5e_spans_trace.json")
MS = 1e6


def _fixture():
    with open(FIX) as f:
        fx = json.load(f)
    return ([T.Event(*e) for e in fx["events"]],
            [S.Span(*s) for s in fx["spans"]], fx["lo_ns"], fx["hi_ns"])


def _idle_by_grid(events, spans, lo, hi, step=100.0):
    """Device idle time per innermost span, counted on a 100 ns grid: an
    independent reckoning of ``S.attribute``'s ``idle_s``."""
    n = int(np.ceil((hi - lo) / step))
    busy = np.zeros(n, bool)
    for e in events:
        if e.plane.startswith("/device:") and e.line == T.OPS_LINE:
            a = int(max(0.0, (max(e.start_ns, lo) - lo) // step))
            b = int(np.ceil((min(e.end_ns, hi) - lo) / step))
            busy[a:max(a, b)] = True
    mid = lo + (np.arange(n) + 0.5) * step
    owner = np.full(n, -1)
    began = np.full(n, -np.inf)
    for i, s in enumerate(spans):
        inside = (mid >= s.start_ns) & (mid < s.end_ns) & (s.start_ns > began)
        owner[inside] = i
        began[inside] = s.start_ns
    out = {}
    for i in np.unique(owner[~busy]):
        key = spans[i].name if i >= 0 else None
        out[key] = out.get(key, 0.0) \
            + float(np.sum(~busy & (owner == i))) * step / 1e9
    return out


def test_recorded_v5e_trace():
    ev, sp, lo, hi = _fixture()
    a = S.attribute(ev, sp, lo, hi)
    grid = _idle_by_grid(ev, sp, lo, hi)
    assert set(grid) == {k for k, v in a["idle_s"].items() if v > 2e-7}
    for k, v in grid.items():
        assert a["idle_s"][k] == pytest.approx(v, abs=5e-6), k
    # every idle instant is charged once: the shares add up to device_idle
    red = T.reduce(ev, lo, hi)
    idle = red["window_s"] - red["busy_s"]
    assert sum(a["idle_s"].values()) == pytest.approx(idle, rel=1e-9)
    ctx = {"spans": a}
    front = S.idle_share(ctx, S.FRONTEND)
    engine = S.idle_share(ctx, S.ENGINE_HOST)
    assert front > 0 and engine > 0
    assert front + engine <= 100.0 * idle / red["window_s"]
    # the arrival's prediction held the chip idle: the fixture was cut
    # around it
    assert a["idle_s"]["magnus.predict"] > 1e-3
    assert S.mean_self_ms(ctx, "magnus.predict") > 1.0


def test_nested_spans_charge_the_innermost():
    """Idle time under a child is the child's; its parent keeps only its
    self time; idle time under no program span is charged to None."""
    D, H = "/device:TPU:0", "/host:CPU"
    ev = [T.Event(D, T.OPS_LINE, "%a.1 = fusion", 0, 10 * MS),
          T.Event(D, T.OPS_LINE, "%b.2 = fusion", 60 * MS, 10 * MS)]
    sp = [S.Span(H, "python", "engine.window", 0, 100 * MS),
          S.Span(H, "python", "engine.readback", 20 * MS, 30 * MS),
          S.Span(H, "python", "magnus.predict", 110 * MS, 5 * MS,
                 {"req_id": 7})]
    a = S.attribute(ev, sp, 0, 120 * MS)
    assert a["idle_s"] == {"engine.readback": pytest.approx(0.03),
                           "engine.window": pytest.approx(0.05),
                           "magnus.predict": pytest.approx(0.005),
                           None: pytest.approx(0.015)}
    assert a["self_s"] == {"engine.window": pytest.approx(0.07),
                           "engine.readback": pytest.approx(0.03),
                           "magnus.predict": pytest.approx(0.005)}
    assert a["count"] == {"engine.window": 1, "engine.readback": 1,
                          "magnus.predict": 1}
    ctx = {"spans": a}
    assert S.idle_share(ctx, S.ENGINE_HOST) == pytest.approx(100 * 0.08 / 0.12)
    assert S.idle_share(ctx, S.FRONTEND) == pytest.approx(100 * 0.005 / 0.12)
    assert S.mean_self_ms(ctx, "magnus.predict") == pytest.approx(5.0)


def test_a_trace_without_program_spans_reads_nothing():
    """A program that records no span (one from before them) gives
    no number, and no error, from each reader."""
    import run as RUN
    for name in ("idle_frontend.lat", "idle_engine_host.tput",
                 "predict_ms.lat"):
        reader = RUN.load_reader(name)
        assert reader.read({"spans": None}, name) is None
        assert reader.read({"facts": {"trace_dir": None}}, name) is None
    a = S.attribute([T.Event("/device:TPU:0", T.OPS_LINE, "%a.1 = copy",
                             0, MS)], [], 0, 2 * MS)
    assert a["idle_s"] == {None: pytest.approx(0.001)}
    assert S.idle_share({"spans": a}, S.FRONTEND) is None
    assert S.mean_self_ms({"spans": a}, "magnus.predict") is None


def test_reads_the_program_spans_of_a_cpu_trace(tmp_path):
    """The program's own span primitive, recorded here: names and attrs
    come back, a list attr parsed, and the driver's spans left out."""
    import jax
    from repro.serving.trace import span
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("step_window"):
            with span("engine.window") as w:
                with span("engine.prefill_wave") as s:
                    s.set_metadata(rows=2, req_ids=[3, 4])
                w.set_metadata(k=8, rows=2)
    got = S.read_spans(T.latest_xplane(str(tmp_path)))
    assert [s.name for s in sorted(got, key=lambda s: s.start_ns)] == \
        ["engine.window", "engine.prefill_wave"]
    by = {s.name: s for s in got}
    assert by["engine.prefill_wave"].attrs == {"rows": 2, "req_ids": [3, 4]}
    assert by["engine.window"].attrs == {"k": 8, "rows": 2}
    w, p = by["engine.window"], by["engine.prefill_wave"]
    assert w.start_ns <= p.start_ns and p.end_ns <= w.end_ns
