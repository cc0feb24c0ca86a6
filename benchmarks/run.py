"""Benchmark driver: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig10_11] [--fast]

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import sys

from benchmarks import extensions as E
from benchmarks import paper_tables as T
from repro.compile_cache import enable_compile_cache

SUITES = {
    "table1": lambda fast: T.table1_correlation(60 if fast else 150),
    "table2": lambda fast: T.table2_predictor(*((60, 30) if fast else (200, 60))),
    "fig6": lambda fast: T.fig6_case_study(),
    "fig10_11": lambda fast: T.fig10_11_overall(
        rates=(8.0,) if fast else (4.0, 8.0, 16.0),
        duration=45.0 if fast else 90.0),
    "fig12_13": lambda fast: T.fig12_13_ablation(
        duration=45.0 if fast else 90.0),
    "fig14": lambda fast: T.fig14_continuous_learning(2 if fast else 4),
    "overhead": lambda fast: T.overhead(),
    "kernels": lambda fast: T.kernels(),
    # beyond-paper extension studies
    "sens_phi": lambda fast: E.sens_phi(
        duration=30.0 if fast else 60.0),
    "sens_predictor": lambda fast: E.sens_predictor(
        duration=30.0 if fast else 60.0),
    "multiarch": lambda fast: E.multiarch(
        duration=20.0 if fast else 40.0),
    "paged": lambda fast: E.paged_vs_dense(
        n_requests=8 if fast else 12),
    # perf trajectory: dense vs per-token paged vs fused-paged decode;
    # writes BENCH_engine.json (schema guarded by tests/test_bench_schema.py)
    "engine": lambda fast: E.engine_perf(
        max_gen=16 if fast else 32, repeats=3 if fast else 5),
    # prefix-cache hit sweep: single-dispatch variable-prefix waves vs
    # the no-cache baseline (paired measurement, §12); merges the
    # prefix_cache section into BENCH_engine.json
    "prefix": lambda fast: E.prefix_cache_sweep(
        repeats=2 if fast else 10),
    # radix mixes: exact / head-only / miss prefill-token accounting vs
    # the PR-3 exact-match replay; merges the radix_prefix section
    # (schema v3) into BENCH_engine.json
    "radix": lambda fast: E.radix_prefix_sweep(
        n_requests=6 if fast else 8),
    # §14 degradation contract under a scripted fault storm; merges the
    # chaos section (schema v5) into BENCH_engine.json
    "chaos": lambda fast: E.chaos_storm(
        n_requests=4 if fast else 6, max_gen=8 if fast else 12),
    # §15 suspension contract: a pool-shrink storm preempts through the
    # host swap tier; merges the swap section (schema v6) into
    # BENCH_engine.json
    "swap": lambda fast: E.swap_storm(
        n_requests=6 if fast else 8),
    # §16 speculative-decoding contract: self-draft spec engine vs the
    # spec-off fused engine (acceptance, accepted tokens per target
    # dispatch, bit-exactness); merges the spec_decode section (schema
    # v7) into BENCH_engine.json
    "spec": lambda fast: E.spec_decode_bench(
        max_gen=15 if fast else 30, repeats=2 if fast else 3),
    # §17 crash-safety contract: kill mid-window, recover from the last
    # snapshot + journal tail, prove bit-exact streams and zero
    # re-prefill; merges the recovery section (schema v8) into
    # BENCH_engine.json
    "recovery": lambda fast: E.recovery_storm(
        n_requests=4 if fast else 6, max_gen=8 if fast else 12),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {sorted(SUITES)}")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    names = (args.only.split(",") if args.only else list(SUITES))
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            for row in SUITES[name](args.fast):
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            sys.stdout.flush()
        except Exception as e:  # keep the suite running
            failures += 1
            print(f"{name},nan,ERROR {type(e).__name__}: {e}")
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
