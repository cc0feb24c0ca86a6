#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates, one after
another on one engine, and print what each sustained.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 20,40,60 [--trace-rate 40] [--out chiprun_out/sweep]

For each rate: requests due in the window, how many of them were
admitted and finished, the output token rate, the tails of those that
finished, and the service's backlog at the window's end (a backlog that
grows with the rate is past the knee).  Each rate's window closes on
time: what it admitted is drained and its backlog dropped.
With ``--trace-rate`` the run at that rate is traced, and the trace file
and a summary of its planes, lines and event names go to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import run as RUN  # puts bench/traffic on the path

import generator as G


def summarize_trace(path: str, out: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    with open(os.path.join(out, "trace_summary.txt"), "w") as f:
        for plane in pd.planes:
            f.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                seen = {}
                for e in evs:
                    if e.name not in seen and len(seen) < 40:
                        seen[e.name] = (e.duration_ns, dict(e.stats))
                for name, (dur, st) in seen.items():
                    f.write(f"    {name!r} dur_ns={dur} stats={st}\n")
    shutil.copy(path, os.path.join(out, "trace.xplane.pb"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace-rate", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(RUN.ROOT, "chiprun_out",
                                                  "sweep"))
    args = ap.parse_args()
    bench = RUN.load_json(RUN.ROOT, "BENCHMARK.json")
    cell = RUN.find_cell(bench, args.workload)
    conf = RUN.load_json(RUN.BENCH, "configs", cell["config"] + ".json")
    mix = G.load_mix(cell["traffic"])
    dev = RUN.setup_jax(require_tpu=True, chips=cell["chips"])
    if dev is None:
        return 2
    import jax
    os.makedirs(args.out, exist_ok=True)
    clock = RUN.CompileCount()
    t = time.perf_counter()
    _, _, svc, engine = RUN.build(conf, mix, args.seed)
    RUN.log(f"build {time.perf_counter() - t:.3f}s (since start "
            f"{time.perf_counter() - RUN.T_START:.3f}s) memory "
            f"{jax.devices()[0].memory_stats()}")
    base = 0
    mix["judge"] = "overload"         # close each window on time
    for rate in [float(x) for x in args.rates.split(",")]:
        mix["arrivals"]["rate"] = rate
        trace_on = args.trace_rate is not None and rate == args.trace_rate
        clock.compiles = 0
        probe, facts = RUN.serve(conf, mix, args.seed, args.seconds,
                                 trace_on, engine, svc, clock, id_base=base)
        base += len(probe.reqs) + 1
        backlog = sum(b.size for b in svc.batcher.queue)
        svc.batcher.queue.clear()
        att = [r for r in probe.reqs
               if probe.ws <= probe.due(r) < probe.we]
        ids = [r.req_id for r in att]
        done = [i for i in ids if i in probe.t_last]
        ttft = [probe.t_first[i] - probe.due(probe.by_id[i]) for i in done]
        resp = [probe.t_last[i] - probe.due(probe.by_id[i]) for i in done]
        qw = [probe.t_admit[i] - probe.due(probe.by_id[i]) for i in ids
              if i in probe.t_admit]
        wins = [w for w in probe.windows if probe.ws <= w[1] < probe.we]
        rows = [len(r) for _, r in probe.waves]
        rec = {"rate": rate, "due_in_window": len(att),
               "admitted": sum(i in probe.t_admit for i in ids),
               "done": len(done), "backlog_at_end": backlog,
               "output_tok_s": probe.in_window_tokens / args.seconds,
               "ttft_p50_s": RUN.pct(ttft, 50) if ttft else None,
               "ttft_p95_s": RUN.pct(ttft, 95) if ttft else None,
               "response_p95_s": RUN.pct(resp, 95) if resp else None,
               "queue_wait_p95_s": RUN.pct(qw, 95) if qw else None,
               "windows": len(wins),
               "steps_per_window": (sum(w[2] for w in wins) / len(wins)
                                    if wins else None),
               "mean_active": (sum(w[3] for w in wins) / len(wins)
                               if wins else None),
               "max_wave_rows": max(rows) if rows else 0,
               "evicted": probe.evicted,
               "compiles_in_window": clock.compiles,
               "drain_s": time.perf_counter() - probe.we}
        print(json.dumps(rec), flush=True)
        if trace_on and facts["trace_dir"]:
            import devtrace as T
            path = T.latest_xplane(facts["trace_dir"])
            if path:
                summarize_trace(path, args.out)
    engine.assert_drained()
    return 0


if __name__ == "__main__":
    sys.exit(main())
