"""The trace reduction, checked on the CPU against a small trace recorded
on a TPU v5e (``fixtures/v5e_decode_trace.json``: the first 30 ms of a
fused decode window of smollm-135m at 8 slots, its device ops, program
execution and host span), and on a trace this test records on the CPU."""
import json
import os

import numpy as np
import pytest

import devtrace as T

FIX = os.path.join(os.path.dirname(__file__), "fixtures",
                   "v5e_decode_trace.json")


def _fixture():
    with open(FIX) as f:
        fx = json.load(f)
    return [T.Event(*e) for e in fx["events"]], fx["lo_ns"], fx["hi_ns"]


def _busy_by_grid(events, lo, hi, step=100.0):
    """Busy time counted on a 100 ns grid: an independent reckoning of
    the union of device-op intervals."""
    n = int(np.ceil((hi - lo) / step))
    grid = np.zeros(n, bool)
    for e in events:
        if e.plane.startswith("/device:") and e.line == T.OPS_LINE:
            a = int((max(e.start_ns, lo) - lo) // step)
            b = int(np.ceil((min(e.end_ns, hi) - lo) / step))
            grid[a:b] = True
    return grid.sum() * step / 1e9


def test_recorded_v5e_trace():
    ev, lo, hi = _fixture()
    red = T.reduce(ev, lo, hi)
    assert red["devices"] == 1
    assert abs(red["busy_s"] - _busy_by_grid(ev, lo, hi)) < 2e-6
    assert 0 < red["busy_s"] <= red["window_s"]
    # the program started 26 us before its host span on the device's
    # clock: the skew allowance still files it under step_window
    dec = T.program_seconds(red, "step_window", "jit__unknown")
    assert dec == pytest.approx(hi - lo - 50_000, rel=1e-6, abs=1e-9) \
        or dec > 0.99 * red["busy_s"]
    assert T.program_seconds(red, "join_many", ".") == 0
    # the paged decode kernel: one custom call a layer, here 21 of them
    sec, calls = T.kernel_seconds(red, "step_window", "jit__unknown")
    assert calls == 21 and 0 < sec < red["busy_s"]
    kernel_ops = [e for e in ev if e.line == T.OPS_LINE
                  and T.KERNEL in e.name and e.end_ns <= hi]
    assert sec == pytest.approx(sum(e.dur_ns for e in kernel_ops) / 1e9)
    labels = [k for k, _ in red["device_ops"]]
    assert labels[0] == "copy:copy"          # pool copies lead the window
    assert not any(k.startswith("while") for k in labels)
    assert len(labels) <= 10 and len(red["idle_gaps"]) <= 10


def test_gap_attribution():
    D, O, M = "/device:TPU:0", T.OPS_LINE, T.MODULES_LINE
    ms = 1e6
    ev = [T.Event(D, M, "jit_f(1)", 0, 400 * ms),
          T.Event(D, O, "%a.1 = fusion", 0, 100 * ms),
          T.Event(D, O, "%b.2 = custom-call tpu_custom_call", 300 * ms,
                  100 * ms),
          T.Event(D, M, "jit__unknown(2)", 350 * ms, 100 * ms),
          T.Event(D, O, "%c.3 = copy", 350 * ms, 100 * ms),
          T.Event("/host:CPU", "python", "join_many", 90 * ms, 150 * ms),
          T.Event("/host:CPU", "python", "step_window", 250 * ms, 30 * ms)]
    red = T.reduce(ev, 0, 600 * ms)
    assert red["busy_s"] == pytest.approx(0.25)
    assert red["window_s"] == pytest.approx(0.6)
    assert red["idle_gaps"][0] == ["join_many", pytest.approx(0.2)]
    assert red["idle_gaps"][1] == ["host:other", pytest.approx(0.15)]
    assert red["programs"] == {"host:other/jit_f": pytest.approx(0.4),
                               "step_window/jit__unknown": pytest.approx(0.1)}
    assert T.kernel_seconds(red, "host:other", "jit_f") == (
        pytest.approx(0.1), 1)
    assert [k for k, _ in red["device_ops"]][0] == "fusion:a"


def test_enqueue_decides_the_span():
    """A program the device starts after the host has moved on belongs to
    the span that enqueued it (the run id ties the two)."""
    D, M, O, H = "/device:TPU:0", T.MODULES_LINE, T.OPS_LINE, "/host:CPU"
    ms = 1e6
    ev = [T.Event(H, "python", "join_many", 0, 10 * ms),
          T.Event(H, "tfrt", T.ENQUEUE, 9 * ms, 0.01 * ms, 7),
          T.Event(H, "python", "step_window", 10.1 * ms, 50 * ms),
          T.Event(H, "tfrt", T.ENQUEUE, 10.2 * ms, 0.01 * ms, 8),
          T.Event(D, M, "jit__unknown(1)", 12 * ms, 5 * ms, 7),
          T.Event(D, O, "%w.1 = fusion", 12 * ms, 5 * ms),
          T.Event(D, M, "jit__unknown(2)", 17 * ms, 30 * ms, 8),
          T.Event(D, O, "%d.2 = fusion", 17 * ms, 30 * ms)]
    red = T.reduce(ev, 0, 60 * ms)
    assert red["programs"] == {"join_many/jit__unknown": pytest.approx(0.005),
                               "step_window/jit__unknown": pytest.approx(0.03)}


def test_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("step_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.read_events(T.latest_xplane(str(tmp_path)))
    assert any(e.name == "step_window" for e in ev)


class _Probe:
    """Decode windows and admission waves as the driver records them."""

    def __init__(self, windows, waves):
        self.windows, self.waves = windows, waves
        self.ws, self.we = 0.0, 10.0


def _reader_ctx(red):
    conf = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "configs",
        "smollm-135m.json")))
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one wave, then one 8-step decode window of 4 rows, inside the trace
    probe = _Probe([(2.0, 2.3, 8, 4, 1200)], [(1.9, [(100, 0)])])
    return {"conf": conf, "peak": peak, "probe": probe, "trace": red,
            "facts": {"trace_on": 1.0, "trace_off": 3.0}}


@pytest.mark.parametrize("metric", ["decode_step_ms", "decode_mfu",
                                    "paged_decode_roofline", "prefill_mfu",
                                    "prefix_prefill_roofline"])
def test_reader_refuses_a_trace_without_its_program(metric):
    """Work the driver dispatched inside the trace, and no program or
    kernel in the trace to time it: the reader fails, it does not go
    silent (a renamed program or span would otherwise drop the metric)."""
    import run as RUN
    D, O, M = "/device:TPU:0", T.OPS_LINE, T.MODULES_LINE
    ms = 1e6
    renamed = [T.Event(D, M, "jit_renamed(1)", 0, 100 * ms),
               T.Event(D, O, "%a.1 = custom-call tpu_custom_call", 0,
                       100 * ms),
               T.Event("/host:CPU", "python", "step_window", 0, 1 * ms),
               T.Event("/host:CPU", "python", "join_many", 200 * ms, 1 * ms)]
    ctx = _reader_ctx(T.reduce(renamed, 0, 300 * ms))
    reader = RUN.load_reader(metric)
    with pytest.raises(LookupError):
        reader.read(ctx, metric)
    # the same work with its programs in the trace reads a number
    named = [T.Event(D, M, "jit__unknown(1)", 0, 100 * ms),
             T.Event(D, O, "%a.1 = custom-call tpu_custom_call", 0, 100 * ms),
             T.Event(D, M, "jit__unknown(2)", 210 * ms, 50 * ms),
             T.Event(D, O, "%b.2 = custom-call tpu_custom_call", 210 * ms,
                     50 * ms),
             T.Event("/host:CPU", "python", "step_window", 0, 1 * ms),
             T.Event("/host:CPU", "python", "join_many", 200 * ms, 1 * ms)]
    assert reader.read(_reader_ctx(T.reduce(named, 0, 300 * ms)),
                       metric) > 0
