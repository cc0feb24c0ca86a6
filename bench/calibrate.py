#!/usr/bin/env python3
"""Readings the comparison's limit is set from, several seeds in one
process: for each seed, serve the cell's traffic at its own load (a short
window, then the drain of everything that counts), and judge the run as
``run.py`` does: the same tally, the same sample of served streams, the
same comparison with the float32 reference and the same limit.  With
``--control`` the control is put in the program's place and judged too:
the reference computed in a lower precision (``fp8``: every
matrix-product operand rounded to float8 e4m3 with its own scale), read
as the widest gap of the token it puts first at each position.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 20 --preroll 10 [--control fp8]

One JSON line a seed: the program's ``correct`` and checks and, with a
control, the control's.  The limit lies above the program's largest
reading and below the control's smallest (``PERF.md`` gives them).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as RUN  # puts bench/traffic on the path

import generator as G


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--preroll", type=float, default=None)
    ap.add_argument("--control", default=None)
    args = ap.parse_args()
    bench = RUN.load_json(RUN.ROOT, "BENCHMARK.json")
    cell = RUN.find_cell(bench, args.workload)
    conf = RUN.load_json(RUN.BENCH, "configs", cell["config"] + ".json")
    mix = G.load_mix(cell["traffic"])
    if args.preroll is not None:
        mix["preroll_s"] = args.preroll
    dev = RUN.setup_jax(require_tpu=True, chips=cell["chips"])
    if dev is None:
        return 2
    clock = RUN.CompileCount()
    chk = conf["check"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        _, params, svc, engine = RUN.build(conf, mix, seed)
        probe, _ = RUN.serve(conf, mix, seed, args.seconds, False, engine,
                             svc, clock)
        attempted, generated, _, off, failed, leak = RUN.tally(
            probe, engine, svc, conf["serving"]["max_gen"])
        del engine, params, svc
        gc.collect()
        t_ref = time.perf_counter()
        gap, ntok, cgap = RUN.check_served(
            conf, seed, generated, attempted, chk["sample_requests"],
            control=args.control)
        ok, checks = RUN.judge(gap, chk["max_logit_gap"], len(off),
                               len(failed), leak)
        rec = {"seed": seed, "attempted": len(attempted), "tokens": ntok,
               "correct": ok, "checks": checks,
               "reference_s": time.perf_counter() - t_ref,
               "seconds": time.perf_counter() - t}
        if args.control:
            c_ok, c_checks = RUN.judge(cgap, chk["max_logit_gap"], len(off),
                                       len(failed), leak)
            rec.update(control=args.control, control_correct=c_ok,
                       control_checks=c_checks)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
