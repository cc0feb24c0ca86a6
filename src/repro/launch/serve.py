"""Serving launcher: run the Magnus service against a Poisson workload.

Two backends:
  --backend sim    : roofline-cost cluster simulator at paper scale
  --backend engine : the real JAX engine, on a reduced f32 config by
                     default (CPU-sized); paged strategies serve the
                     published widths with ``--full-width`` and bf16
                     weights and KV with ``--dtype bfloat16`` (a TPU)

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm-6b \
        --strategy magnus --rate 8 --duration 60
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --backend engine --strategy magnus-paged --prefix-cache \
        --full-width --dtype bfloat16 --rate 3 --duration 3
"""
from __future__ import annotations

import argparse
import json

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serving.cost_model import TPU_V5E, V100_32G
from repro.sim.runner import run_strategy
from repro.workload.apps import make_dataset
from repro.workload.generator import poisson_workload


def run_engine_backend(arch: str, rate: float, duration: float,
                       strategy: str, seed: int = 0) -> dict:
    """Serve a reduced model for real on CPU with Magnus batching."""
    import numpy as np

    from repro.core.magnus import MagnusConfig, MagnusService
    from repro.core.predictor import GenerationLengthPredictor
    from repro.core.wma import MemoryModel
    from repro.serving.engine import BatchEngine

    cfg = get_config(arch).reduced()
    memory = MemoryModel(cfg, hbm_bytes=2 * 2 ** 30, max_len=256, max_gen=32)
    predictor = GenerationLengthPredictor(seed=seed).fit(
        make_dataset(60, seed=seed + 1))
    svc = MagnusService(memory, MagnusConfig(strategy=strategy),
                        predictor=predictor)
    engine = BatchEngine(cfg, max_gen=32)
    wl = poisson_workload(rate, duration, seed=seed, max_len=200, max_gen=32)
    now, served, results = 0.0, 0, []
    for r in wl:
        svc.on_request(r, r.arrival_time)
    while len(svc.batcher.queue) > 0:
        b = svc.next_batch(now)
        if b is None:
            break
        res = engine.serve_batch(b)
        results.append(res)
        served += b.size
        now += res.wall_time
    total_tokens = sum(r.total_tokens for r in results)
    valid = sum(r.valid_tokens for r in results)
    wma = sum(r.wma for r in results)
    return {"requests": served, "batches": len(results),
            "wall_s": round(now, 2),
            "token_tp": round(total_tokens / max(now, 1e-9), 1),
            "valid_token_tp": round(valid / max(now, 1e-9), 1),
            "wma_total": wma}


def run_paged_engine_backend(arch: str, rate: float, duration: float,
                             strategy: str, seed: int = 0, *,
                             num_blocks: int = 128, block_tokens: int = 16,
                             max_concurrency: int = 16,
                             prefix_cache: bool = False,
                             ttl_steps: int | None = None,
                             swap_blocks: int = 0,
                             spec_decode: bool = False,
                             draft_k: int = 4,
                             checkpoint_dir: str | None = None,
                             snapshot_every: int = 8,
                             full_width: bool = False,
                             dtype: str = "float32") -> dict:
    """Continuous paged serving for real: MagnusService drives
    admission (prediction + block accounting) against the same
    BlockAllocator the engine stores KV pages in (DESIGN.md §8).  The
    engine admits whole scheduler batches as single-dispatch variable-
    prefix waves (``join_many``, §12) and decodes in fused multi-step
    windows (§9).  With
    ``prefix_cache`` the service's LCP-aware footprints and the engine's
    ref-counted radix-shared instruction pages use ONE RadixPrefixCache
    (§10-§11).  One :class:`MispredictionEWMA` is shared between the
    batcher's footprints and the engine's reservations (§14), so both
    sides of admission apply the same adaptive headroom; ``ttl_steps``
    sets a default per-request deadline in scheduler-clock ticks;
    ``swap_blocks`` > 0 enables the host-memory KV swap tier (§15), so
    pool pressure suspends victims to pinned host pages instead of
    destroying their KV; ``spec_decode`` turns on §16 speculative
    decoding (self-draft: the draft shares the target's weights, so
    streams stay bit-exact while every verify dispatch emits up to
    ``draft_k + 1`` tokens); ``checkpoint_dir`` turns on §17 crash-safe
    serving — every admission is journaled write-ahead, a full engine
    snapshot lands every ``snapshot_every`` windows, and on start a
    surviving journal from a previous process is recovered first
    (outstanding requests finished bit-exact) before new traffic is
    served.  ``full_width`` serves the published config instead of its
    ``reduced()`` CPU variant; ``dtype`` is the weights' and KV's
    serving dtype.  The service plans against exactly the pool the
    engine allocates.  ``off_script`` counts requests whose stream is
    not ``min(gen_length, max_gen)`` tokens long (shed or lost ones
    included); the engine must drain."""
    import os
    import time

    import jax.numpy as jnp

    from repro.core.magnus import MagnusConfig, MagnusService
    from repro.core.predictor import GenerationLengthPredictor
    from repro.serving.engine import PagedContinuousEngine, drive_paged
    from repro.serving.paged_cache import BlockAllocator, MispredictionEWMA

    cfg = get_config(arch)
    if not full_width:
        cfg = cfg.reduced()
    jdtype = jnp.dtype(dtype)
    memory = _pool_memory_model(cfg, num_blocks * block_tokens,
                               jdtype.itemsize, max_len=200, max_gen=32)
    allocator = BlockAllocator(num_blocks, block_tokens)
    predictor = GenerationLengthPredictor(seed=seed).fit(
        make_dataset(60, seed=seed + 1))
    svc = MagnusService(memory,
                        MagnusConfig(strategy=strategy,
                                     prefix_sharing=prefix_cache),
                        predictor=predictor, allocator=allocator)
    ewma = MispredictionEWMA()
    svc.memory.headroom = ewma
    engine = PagedContinuousEngine(cfg, max_concurrency=max_concurrency,
                                   max_len=200, max_gen=32, dtype=jdtype,
                                   allocator=allocator,
                                   prefix_cache=svc.prefix_cache or False,
                                   mispredict=ewma,
                                   default_ttl=ttl_steps,
                                   swap_blocks=swap_blocks,
                                   spec_decode=spec_decode,
                                   draft_k=draft_k)
    wl = poisson_workload(rate, duration, seed=seed, max_len=200, max_gen=32)
    for r in wl:
        svc.on_request(r, r.arrival_time)   # prediction + Algorithm-1 acct

    recovery = None
    recovered = None
    if checkpoint_dir is not None:
        if spec_decode:
            raise ValueError("--checkpoint-dir does not cover speculative "
                             "engines (§16/§17): snapshot() refuses them")
        from repro.serving import snapshot as snaplib

        def _fresh_engine():
            # same geometry as the serving engine, standalone allocator
            # (the service's allocator belongs to THIS run)
            return PagedContinuousEngine(
                cfg, max_concurrency=max_concurrency, max_len=200,
                max_gen=32, dtype=jdtype,
                allocator=BlockAllocator(num_blocks, block_tokens),
                prefix_cache=prefix_cache, default_ttl=ttl_steps,
                swap_blocks=swap_blocks)

        wal = os.path.join(checkpoint_dir, snaplib.JOURNAL_NAME)
        if os.path.exists(wal):
            # restore-on-start: bring the previous process's journaled
            # work to completion before serving new traffic
            prev, report = snaplib.recover(_fresh_engine, checkpoint_dir,
                                           snapshot_every=snapshot_every)
            prev.assert_drained()
            recovered = {k: report[k] for k in
                         ("journaled", "outstanding", "recovered",
                          "replayed_reprefill_tokens", "restore_s",
                          "torn_records")}
            os.remove(wal)   # recovered: this process's WAL starts fresh
        recovery = snaplib.RecoveryManager(checkpoint_dir,
                                           snapshot_every=snapshot_every)

    def refill(steps: int):
        # admission order comes from the service's scheduler (HRRN for
        # magnus-paged, FCFS for ccb-paged); requests then stream into
        # the continuous engine (one batched prefill per wave) until it
        # refuses
        nb = svc.next_batch(now=float(steps))
        return nb.requests if nb is not None else None

    start = time.perf_counter()
    st = drive_paged(engine, [], max_steps=100_000, refill=refill,
                     backlog=lambda: len(svc.batcher.queue) > 0,
                     recovery=recovery)
    wall = time.perf_counter() - start
    if recovery is not None:
        recovery.close()
    engine.assert_drained()
    util = st["util"]
    total_tokens = sum(len(g) for g in engine.generated.values())
    off_script = sum(len(engine.generated.get(r.req_id, ()))
                     != min(r.gen_length, engine.max_gen) for r in wl)
    return {"requests": st["served"], "steps": st["steps"],
            "off_script": off_script,
            "wall_s": round(wall, 2),
            "token_tp": round(total_tokens / max(wall, 1e-9), 1),
            "peak_concurrency": st["peak"], "evictions": st["evictions"],
            "prefix_hits": engine.prefix_cache.hits
            if engine.prefix_cache else 0,
            "prefix_misses": engine.prefix_cache.misses
            if engine.prefix_cache else 0,
            "prefill_dispatches": engine.prefill_dispatches,
            "prefill_tokens": engine.prefill_tokens,
            "cow_copies": engine.cow_copies,
            "host_syncs": engine.host_syncs,
            "host_syncs_per_token": round(
                engine.host_syncs / max(total_tokens, 1), 4),
            "mean_block_utilization": round(
                sum(util) / max(len(util), 1), 3),
            # robustness counters (DESIGN.md §14)
            "retries_max": st["retries_max"],
            "deadline_misses": st["deadline_misses"],
            "quarantined": st["quarantined"],
            "shed": len(st["shed"]),
            "requeue_prefix_hits": st["requeue_prefix_hits"],
            # host swap tier (DESIGN.md §15)
            "swap_outs": st["swap_outs"],
            "swap_ins": st["swap_ins"],
            "swapped_blocks": engine.swapped_blocks,
            "swap_reused_blocks": engine.swap_reused_blocks,
            "reprefilled_swapped_tokens": st["reprefilled_swapped_tokens"],
            "swap_in_s": round(engine.swap_in_s, 4),
            # speculative decoding (DESIGN.md §16)
            "spec_windows": st["spec_windows"],
            "accepted_per_dispatch": round(st["accepted_per_dispatch"], 3),
            "acceptance_rate": round(st["acceptance_rate"], 3),
            "draft_quarantined": st["draft_quarantined"],
            "draft_prefill_tokens": st["draft_prefill_tokens"],
            # crash-safe serving (DESIGN.md §17)
            "snapshots_taken": recovery.snapshots_taken
            if recovery is not None else 0,
            "journal_records": recovery.journal.records_written
            if recovery is not None else 0,
            "replayed_reprefill_tokens": st["replayed_reprefill_tokens"],
            "recovered_on_start": recovered,
            "headroom": ewma.snapshot()}


def _pool_memory_model(cfg, pool_tokens: int, dtype_bytes: int, *,
                      max_len: int, max_gen: int):
    """A :class:`MemoryModel` whose Θ is exactly ``pool_tokens`` of KV
    at ``dtype_bytes``: the weights plus the engine's pool are the whole
    device, all of it plannable (the pool is already the reserve)."""
    from repro.core.wma import MemoryModel
    pool = pool_tokens * cfg.kv_bytes_per_token(dtype_bytes)
    return MemoryModel(cfg, hbm_bytes=cfg.param_count() * dtype_bytes + pool,
                       reserve_frac=1.0, max_len=max_len, max_gen=max_gen,
                       dtype_bytes=dtype_bytes, param_dtype_bytes=dtype_bytes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm-6b")
    ap.add_argument("--strategy", default="magnus",
                    choices=["vs", "vsq", "ccb", "glp", "abp", "magnus",
                             "ccb-paged", "magnus-paged"])
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--instances", type=int, default=7)
    ap.add_argument("--backend", default="sim", choices=["sim", "engine"])
    ap.add_argument("--hw", default="v100", choices=["v100", "v5e"])
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged strategies: radix-tree instruction-prefix "
                         "sharing across apps with copy-on-write partial "
                         "tails (runtime) / LCP-aware footprints (sim)")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="paged engine block size; matches shorter than "
                         "one block are treated as misses, so short app "
                         "templates need a smaller block to hit")
    ap.add_argument("--ttl-steps", type=int, default=None,
                    help="paged engine: default per-request deadline in "
                         "scheduler-clock ticks from admission; expired "
                         "requests are shed and counted (DESIGN.md §14)")
    ap.add_argument("--swap-blocks", type=int, default=0,
                    help="paged engine: host-memory KV swap tier capacity "
                         "in blocks (0 disables); under pool pressure live "
                         "victims suspend to pinned host pages and resume "
                         "without re-prefilling (DESIGN.md §15)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="paged engine: speculative decoding (DESIGN.md "
                         "§16) — a self-draft proposes draft-k tokens per "
                         "window, one batched target dispatch verifies "
                         "them, rollback is block-table truncation; "
                         "greedy output is bit-exact")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative tokens proposed per window (the "
                         "verify dispatch covers draft-k + 1 positions)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="paged engine: crash-safe serving (DESIGN.md "
                         "§17) — write-ahead admission journal + periodic "
                         "full-engine snapshots in this directory; on "
                         "start a surviving journal is recovered first "
                         "(outstanding requests finished bit-exact)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="windows between full engine snapshots when "
                         "--checkpoint-dir is set")
    ap.add_argument("--full-width", action="store_true",
                    help="paged engine: serve the published config, not "
                         "its reduced() CPU-sized variant")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="paged engine: serving dtype of weights and KV")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.backend == "engine":
        if args.strategy.endswith("-paged"):
            out = run_paged_engine_backend(args.arch, args.rate,
                                           args.duration, args.strategy,
                                           args.seed,
                                           block_tokens=args.block_tokens,
                                           prefix_cache=args.prefix_cache,
                                           ttl_steps=args.ttl_steps,
                                           swap_blocks=args.swap_blocks,
                                           spec_decode=args.spec_decode,
                                           draft_k=args.draft_k,
                                           checkpoint_dir=args.checkpoint_dir,
                                           snapshot_every=args.snapshot_every,
                                           full_width=args.full_width,
                                           dtype=args.dtype)
        else:
            out = run_engine_backend(args.arch, args.rate, args.duration,
                                     args.strategy, args.seed)
        print(json.dumps(out, indent=2))
        return
    cfg = get_config(args.arch)
    wl = poisson_workload(args.rate, args.duration, seed=args.seed)
    hw = V100_32G if args.hw == "v100" else TPU_V5E
    m = run_strategy(args.strategy, wl, cfg, hw=hw,
                     n_instances=args.instances,
                     kv_dtype_bytes=4 if args.hw == "v100" else 2,
                     train_requests=make_dataset(100, seed=args.seed + 1),
                     prefix_sharing=args.prefix_cache,
                     seed=args.seed)
    print(json.dumps(m.summary(), indent=2))


if __name__ == "__main__":
    main()
