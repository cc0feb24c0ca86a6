#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

1. set-up: weights made on the device from the seed, the length predictor
   fitted, the Magnus service and the paged engine built exactly as the
   program's launcher builds them, every shape the cell uses warmed up,
   then a pre-roll of the cell's own traffic;
2. the measured window of ``--seconds``: requests handed to the service
   when due on the wall clock and served through the program's
   ``drive_paged``; nothing may compile here (the count is printed);
3. the drain: requests that count are followed until they finish;
4. the check that decides ``correct``: every request that counts got its
   scripted number of tokens, the pool drained, and a sample of served
   streams lies within the limit of a plain float32 reference.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the end of the window is traced and the line carries
the per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The
last line of standard output is one JSON object; the compared numbers
and their limits are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "traffic"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_SECONDS = 4.0          # the traced part: the end of the window


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def setup_jax(require_tpu: bool, chips: int):
    """Point the persistent compile cache into the checkout; return the
    device record, or None where the chips asked for are not here."""
    cache = os.path.join(CACHE, "jax")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < chips):
        log(f"FAIL: need {chips} TPU chip(s), found {dev}")
        return None
    return dev


class CompileCount:
    """Executables built (XLA compiles and persistent-cache loads) while
    ``on`` is set, from jax.monitoring."""

    def __init__(self):
        from jax import monitoring
        self.on, self.compiles, self.seconds = False, 0, 0.0
        monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs


def pct(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def build(conf: dict, mix: dict, seed: int):
    """Weights, predictor, service and engine, as the launcher builds them."""
    import jax
    import jax.numpy as jnp

    import arch
    import driver
    import generator as G
    import weights as W
    from repro.core.magnus import MagnusConfig, MagnusService
    from repro.core.predictor import GenerationLengthPredictor
    from repro.core.types import Request
    from repro.core.wma import MemoryModel
    from repro.serving.engine import PagedContinuousEngine
    from repro.serving.paged_cache import BlockAllocator, MispredictionEWMA

    sv = conf["serving"]
    cfg = arch.of(conf).program_config(conf)
    dtype = jnp.dtype(sv["dtype"])
    params = W.make_params(seed, conf, dtype)
    jax.block_until_ready(params)
    train = G.training_set(mix, seed, mix["predictor_train_per_task"],
                           max_len=sv["max_len"], max_gen=sv["max_gen"],
                           make=Request)
    predictor = GenerationLengthPredictor(seed=seed).fit(train)
    nb, bt = sv["num_blocks"], sv["block_tokens"]
    pool = nb * bt * cfg.kv_bytes_per_token(dtype.itemsize)
    # Θ is exactly the pool the engine allocates (launch/serve.py's rule)
    memory = MemoryModel(cfg, hbm_bytes=cfg.param_count() * dtype.itemsize
                         + pool, reserve_frac=1.0, max_len=sv["max_len"],
                         max_gen=sv["max_gen"], dtype_bytes=dtype.itemsize,
                         param_dtype_bytes=dtype.itemsize)
    allocator = BlockAllocator(nb, bt)
    svc = MagnusService(memory, MagnusConfig(
        strategy=sv["strategy"], prefix_sharing=sv["prefix_cache"]),
        predictor=predictor, allocator=allocator)
    ewma = MispredictionEWMA()
    svc.memory.headroom = ewma
    Timed = driver.timed_engine_class(PagedContinuousEngine)
    engine = Timed(cfg, params, max_concurrency=sv["slots"],
                   max_len=sv["max_len"], max_gen=sv["max_gen"], dtype=dtype,
                   allocator=allocator,
                   prefix_cache=svc.prefix_cache or False, mispredict=ewma)
    # the engine's own grid: every power-of-two wave of rows up to the
    # slots at every suffix bucket up to max_len, every power-of-two window
    engine.warmup()
    jax.block_until_ready(engine.pages)
    return cfg, params, svc, engine


def load_reader(name: str):
    """``bench/metrics/<name>.py``, else the file of the name without its
    last ``.part`` (``decode_step_ms.lat`` reads ``decode_step_ms.py``)."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(BENCH, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r}")


def cell_metrics(bench: dict, cell: str, key: str) -> list:
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def serve(conf, mix, seed, seconds, trace_on, engine, svc, clock,
          id_base: int = 0):
    """Pre-roll, window and drain.  Returns the probe and run facts.
    ``id_base`` offsets request ids, for several serves on one engine."""
    import arch
    import driver
    import generator as G
    from repro.core.types import Request
    from repro.serving.engine import drive_paged

    sv = conf["serving"]
    pre, cap = float(mix["preroll_s"]), float(mix["drain_cap_s"])
    reqs = G.arrivals(mix, seed, [pre, seconds, cap], max_len=sv["max_len"],
                      max_gen=sv["max_gen"], make=Request)
    for r in reqs:
        r.req_id += id_base
    prompt_ids = arch.of(conf).prompt_ids
    plen = {r.req_id: len(prompt_ids(r, conf, sv["max_len"])) for r in reqs}
    t0 = time.perf_counter()
    window = (t0 + pre, t0 + pre + seconds)
    probe = driver.Probe(svc, reqs, t0=t0, window=window,
                         judge=mix["judge"], drain_cap_s=cap,
                         prompt_len=plen, spans=trace_on)
    facts = {"trace_dir": None, "trace_on": None, "trace_off": None}
    tdir = os.path.join(CACHE, "trace")

    def on_tick(now):
        clock.on = window[0] <= now < window[1]
        if not trace_on:
            return
        import jax
        if facts["trace_on"] is None and now >= window[1] - TRACE_SECONDS:
            shutil.rmtree(tdir, ignore_errors=True)
            # host annotations and device ops; no per-call Python tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            facts["trace_on"] = time.perf_counter()
        elif facts["trace_off"] is None and facts["trace_on"] is not None \
                and now >= window[1]:
            facts["trace_off"] = time.perf_counter()
            jax.profiler.stop_trace()
            facts["trace_dir"] = tdir

    probe.on_tick = on_tick
    engine.probe = probe
    big = 1 << 62
    try:
        try:
            drive_paged(engine, [], max_steps=big, refill=probe.refill,
                        backlog=probe.backlog)
        except driver.StopServing:
            pass
        # drain what was admitted; evictions readmit through the same loop
        drive_paged(engine, [], max_steps=big)
    except driver.DrainCapExceeded as e:
        # what is left unfinished fails, and the pool is not drained
        log(f"FAIL: {e}")
    if facts["trace_on"] is not None and facts["trace_off"] is None:
        import jax
        facts["trace_off"] = time.perf_counter()
        jax.profiler.stop_trace()
        facts["trace_dir"] = tdir
    clock.on = False
    engine.probe = None
    return probe, facts


def check_served(conf, seed, generated, attempted, n_sample, control=None):
    """Run the reference over a sample of finished requests that count:
    the longest one, and the rest drawn from the seed.  Returns the widest
    gap, the number of served tokens compared and, with ``control`` (a
    precision the architecture's ``served_gaps`` knows), the control's
    widest gap on the same positions."""
    import numpy as np

    import arch
    A = arch.of(conf)
    sv = conf["serving"]
    done = [r for r in attempted if r.req_id in generated]
    if not done:
        return None, 0, None
    longest = max(done, key=lambda r: len(generated[r.req_id]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = [longest] + [rest[i] for i in rng.choice(
        len(rest), size=min(n_sample - 1, len(rest)), replace=False)]
    seqs = [(A.prompt_ids(r, conf, sv["max_len"]), generated[r.req_id])
            for r in pick]
    out = A.served_gaps(seed, conf, seqs, control=control)
    cgap = float(out["control_gap"].max()) if control else None
    return float(out["gap"].max()), out["tokens"], cgap


def judge(gap, limit: float, off_script: int, failed: int, leak: str):
    """``correct`` and the numbers it compares, each beside its limit
    (``leak``: the pool's leak, empty where the pool drained)."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "off_script": {"value": off_script, "limit": 0},
        "failed": {"value": failed, "limit": 0},
        "pool_leak": {"value": int(bool(leak)), "limit": 0},
    }
    correct = gap is not None and gap <= limit and not (
        off_script or failed or leak)
    return bool(correct), checks


def tally(probe, engine, svc, max_gen: int):
    """What the serve left: the requests that count, the tokens served,
    each request's scripted count, the ids served off script, the ids
    failed (off script, shed or unfinished) and the pool's leak (empty
    where the pool drained)."""
    attempted = probe.attempted()
    generated = dict(engine.generated)
    shed = {s.req.req_id for s in engine.shed_log}
    want = {r.req_id: min(r.gen_length, max_gen) for r in attempted}
    off_script = [i for i in want if i in generated
                  and len(generated[i]) != want[i]]
    unfinished = [i for i in want if i not in generated and i not in shed]
    failed = set(off_script) | set(unfinished) | (set(want) & shed)
    log(f"attempted={len(attempted)} failed={len(failed)} "
        f"(shed={len(set(want) & shed)} off_script={len(off_script)} "
        f"unfinished={len(unfinished)}) evicted={probe.evicted} "
        f"still queued at the end: "
        f"{sum(b.size for b in svc.batcher.queue)} requests")
    leak = ""
    try:
        engine.assert_drained()
    except Exception as e:      # the drain check's own error type
        leak = f"{type(e).__name__}: {e}"
        log(f"pool not drained: {leak}")
    return attempted, generated, want, off_script, failed, leak


def run_cell(bench: dict, cell: dict, conf: dict, mix: dict, peak: dict,
             seed: int, seconds: float, trace_on: bool, dev: dict) -> dict:
    """One run of ``cell`` (configuration ``conf``, traffic ``mix``) on
    the device ``dev`` whose peaks are ``peak``; returns the result."""
    import jax

    import devtrace as T
    sv = conf["serving"]
    clock = CompileCount()
    t_build = time.perf_counter()
    cfg, params, svc, engine = build(conf, mix, seed)
    log(f"built and warmed in {time.perf_counter() - t_build:.3f}s")
    probe, facts = serve(conf, mix, seed, seconds, trace_on, engine, svc,
                         clock)
    ws, we = probe.ws, probe.we
    setup_s = ws - T_START
    log(f"set-up {setup_s:.3f}s (pre-roll {mix['preroll_s']}s); compiles "
        f"inside the window: {clock.compiles} ({clock.seconds:.3f}s)")

    attempted, generated, want, off_script, failed, leak = tally(
        probe, engine, svc, sv["max_gen"])
    mem = jax.devices()[0].memory_stats() or {}
    dev = dict(dev, memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)))

    def t_or_inf(d, rid):
        return d.get(rid, math.inf) - probe.due(probe.by_id[rid])

    ids = [r.req_id for r in attempted]
    ctx = {"conf": conf, "mix": mix, "peak": peak, "probe": probe,
           "attempted": attempted, "failed": failed, "facts": facts,
           "generated": generated, "trace": None}
    metrics = {}
    if not trace_on:
        ttft = [t_or_inf(probe.t_first, i) for i in ids]
        resp = [t_or_inf(probe.t_last, i) for i in ids]
        tpot = [(probe.t_last[i] - probe.t_first[i]) / (want[i] - 1) * 1e3
                for i in ids if want[i] > 1 and i in probe.t_last
                and i not in failed]
        qwait = [t_or_inf(probe.t_admit, i) for i in ids]
        if ttft:
            # logged, not judged: too few requests in a window for these
            # tails to repeat (PERF.md section 2)
            log(f"ttft p50 {pct(ttft, 50):.4f}s p95 {pct(ttft, 95):.4f}s; "
                f"response p50 {pct(resp, 50):.4f}s p95 {pct(resp, 95):.4f}s; "
                f"queue wait p95 {pct(qwait, 95):.4f}s over {len(ids)} "
                f"requests")
        rate = probe.in_window_tokens / (we - ws)
        values = {"tpot_p95_ms": pct(tpot, 95) if tpot else None,
                  "output_tok_s": rate,
                  "throughput_tok_s": rate,
                  "setup_s": setup_s}
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        path = T.latest_xplane(facts["trace_dir"]) if facts["trace_dir"] \
            else None
        events = T.read_events(path) if path is not None else []
        if any(e.plane.startswith("/device:") for e in events):
            lo, hi = T.window_of(events)
            host = [e for e in events if e.plane.startswith("/host:")]
            if host:
                lo = min(lo, min(e.start_ns for e in host))
                hi = max(hi, max(e.end_ns for e in host))
            red = T.reduce(events, lo, hi)
            ctx["trace"] = red
            dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
            log(f"trace {path}: {len(events)} events, busy "
                f"{red['busy_s']:.4f}s of {red['window_s']:.4f}s")
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            v = load_reader(m["name"]).read(ctx, m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state before the reference runs
    del engine, params, svc
    gc.collect()
    chk = conf["check"]
    gap, ntok, _ = check_served(conf, seed, generated, attempted,
                                chk["sample_requests"])
    correct, checks = judge(gap, chk["max_logit_gap"], len(off_script),
                            len(failed), leak)
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": dev}
    if trace_on and ctx["trace"] is not None:
        out["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                            "idle_gaps": ctx["trace"]["idle_gaps"]}
    out["checks"] = checks
    log(f"reference over {ntok} served tokens")
    return out


def main(argv=None) -> int:
    import generator as G
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    conf = load_json(BENCH, "configs", cell["config"] + ".json")
    mix = G.load_mix(cell["traffic"])
    peaks = load_json(BENCH, "peaks.json")
    dev = setup_jax(require_tpu=True, chips=cell["chips"])
    if dev is None:
        return 2
    if dev["kind"] not in peaks:
        log(f"FAIL: device kind {dev['kind']!r} is not in peaks.json")
        return 2
    out = run_cell(bench, cell, conf, mix, peaks[dev["kind"]], args.seed,
                   args.seconds, bool(args.trace), dev)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
