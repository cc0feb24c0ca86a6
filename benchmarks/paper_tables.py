"""One benchmark per paper table/figure (Magnus, CS.DC 2024).

Each function returns a list of CSV rows: (name, us_per_call, derived).
``derived`` carries the table's headline quantity so EXPERIMENTS.md can be
regenerated from benchmark output alone.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]


def _timeit(fn, n=3):
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    return (time.perf_counter() - t0) / n * 1e6, out


# ---------------------------------------------------------------- Table I
def table1_correlation(n_per_task: int = 150) -> List[Row]:
    from repro.workload.apps import TASKS, make_dataset, pearson
    reqs = make_dataset(n_per_task, seed=11)
    rows = []
    for task in TASKS:
        sub = [r for r in reqs if r.task == task]
        us, rho = _timeit(lambda: pearson(sub), n=1)
        rows.append((f"table1/pearson/{task}", us, f"rho={rho:.3f}"))
    return rows


# --------------------------------------------------------------- Table II
def table2_predictor(n_train: int = 200, n_test: int = 60) -> List[Row]:
    from repro.core.predictor import (GenerationLengthPredictor,
                                      PerTaskForestPredictor,
                                      PredictorConfig, UILOPredictor)
    from repro.workload.apps import make_dataset
    train = make_dataset(n_train, seed=0)
    test = make_dataset(n_test, seed=1)
    rows = []
    preds = [
        ("UILO", UILOPredictor()),
        ("RAFT", PerTaskForestPredictor()),
        ("INST", GenerationLengthPredictor(
            PredictorConfig(use_user_input=False))),
        ("USIN", GenerationLengthPredictor()),
    ]
    for name, p in preds:
        t0 = time.perf_counter()
        p.fit(train)
        fit_s = time.perf_counter() - t0
        us, rmse = _timeit(lambda: p.rmse(test), n=1)
        rows.append((f"table2/rmse/{name}", us,
                     f"rmse={rmse:.2f} fit_s={fit_s:.1f}"))
    return rows


# ------------------------------------------------------------------ Fig 6
def fig6_case_study() -> List[Row]:
    """21 requests: 18 small (L~10,G~10) + 3 large (L~1000,G~1000).
    Vanilla: 3 FCFS batches of 7; Magnus: batches {18 small}, {3 large}."""
    from repro.configs import get_config
    from repro.serving.cost_model import CostModel, V100_32G
    cfg = get_config("chatglm-6b")
    cost = CostModel(cfg, V100_32G, kv_dtype_bytes=4)
    # arrival order of Fig 6a: interleaved
    sizes = [(10, 10)] * 18 + [(1000, 1000)] * 3
    order = sizes[:6] + [sizes[18]] + sizes[6:12] + [sizes[19]] \
        + sizes[12:18] + [sizes[20]]
    vanilla = 0.0
    for i in range(0, 21, 7):
        chunk = order[i:i + 7]
        bl = max(c[0] for c in chunk)
        bg = max(c[1] for c in chunk)
        vanilla += cost.batch_serving_time(len(chunk), bl, bg)
    magnus = cost.batch_serving_time(18, 10, 10) \
        + cost.batch_serving_time(3, 1000, 1000)
    red = 100 * (1 - magnus / vanilla)
    return [("fig6/vanilla_total_s", vanilla * 1e6, f"t={vanilla:.1f}s"),
            ("fig6/magnus_total_s", magnus * 1e6, f"t={magnus:.1f}s"),
            ("fig6/reduction", 0.0, f"reduction={red:.1f}% (paper: 75.2%)")]


# -------------------------------------------------------------- Figs 10-11
def fig10_11_overall(rates=(4.0, 8.0, 16.0), duration: float = 90.0
                     ) -> List[Row]:
    from repro.configs import get_config
    from repro.core.predictor import GenerationLengthPredictor
    from repro.serving.cost_model import V100_32G
    from repro.sim.runner import run_strategy
    from repro.workload.apps import make_dataset
    from repro.workload.generator import poisson_workload
    cfg = get_config("chatglm-6b")
    predictor = GenerationLengthPredictor(seed=5).fit(
        make_dataset(120, seed=6))
    rows = []
    for rate in rates:
        wl = poisson_workload(rate, duration, seed=0)
        base = {}
        for strat in ("vs", "vsq", "ccb", "magnus"):
            t0 = time.perf_counter()
            m = run_strategy(strat, wl, cfg, hw=V100_32G, kv_dtype_bytes=4,
                             predictor=predictor,
                             train_requests=make_dataset(40, seed=7))
            us = (time.perf_counter() - t0) * 1e6
            base[strat] = m
            rows.append((
                f"fig10_11/{strat}/rate{rate:g}", us,
                f"req_tp={m.request_throughput:.3f} "
                f"tok_tp={m.token_throughput:.0f} "
                f"vtok_tp={m.valid_token_throughput:.0f} "
                f"avg_rt={m.avg_response_time:.1f} "
                f"p95_rt={m.p95_response_time:.1f}"))
        gain = 100 * (base["magnus"].request_throughput
                      / max(base["vs"].request_throughput, 1e-9) - 1)
        rt_red = 100 * (1 - base["magnus"].avg_response_time
                        / max(base["vs"].avg_response_time, 1e-9))
        rows.append((f"fig10_11/headline/rate{rate:g}", 0.0,
                     f"magnus_vs_vs_tp=+{gain:.0f}% rt=-{rt_red:.0f}% "
                     f"(paper: +66..234%, -60..90%)"))
    return rows


# -------------------------------------------------------------- Figs 12-13
def fig12_13_ablation(rate: float = 12.0, duration: float = 90.0
                      ) -> List[Row]:
    from repro.configs import get_config
    from repro.core.predictor import GenerationLengthPredictor
    from repro.serving.cost_model import V100_32G
    from repro.sim.runner import run_strategy
    from repro.workload.apps import make_dataset
    from repro.workload.generator import poisson_workload
    cfg = get_config("chatglm-6b")
    predictor = GenerationLengthPredictor(seed=5).fit(
        make_dataset(120, seed=6))
    wl = poisson_workload(rate, duration, seed=0)
    rows = []
    for strat in ("vs", "glp", "abp", "magnus"):
        t0 = time.perf_counter()
        m = run_strategy(strat, wl, cfg, hw=V100_32G, kv_dtype_bytes=4,
                         predictor=predictor)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"fig12_13/{strat}/rate{rate:g}", us,
                     f"req_tp={m.request_throughput:.3f} "
                     f"tok_tp={m.token_throughput:.0f} "
                     f"vtok_tp={m.valid_token_throughput:.0f} "
                     f"avg_rt={m.avg_response_time:.1f} "
                     f"p95_rt={m.p95_response_time:.1f} oom={m.oom_events}"))
    return rows


# ----------------------------------------------------------------- Fig 14
def fig14_continuous_learning(windows: int = 4) -> List[Row]:
    from repro.core.predictor import (GenerationLengthPredictor,
                                      PredictorConfig)
    from repro.workload.apps import make_dataset
    # train on a small seed set; stream new requests with drift-free
    # distribution; error should fall as retraining accumulates data
    p = GenerationLengthPredictor(
        PredictorConfig(retrain_period=0.0, n_trees=10, max_depth=10)
    ).fit(make_dataset(20, seed=0))
    rows = []
    now = 0.0
    for w in range(windows):
        stream = make_dataset(30, seed=100 + w)
        rmse = p.rmse(stream)
        t0 = time.perf_counter()
        for r in stream:
            r.predicted_gen_length = p.predict(r)
            now += 5.0
            p.observe(r, now)
        us = (time.perf_counter() - t0) * 1e6 / len(stream)
        rows.append((f"fig14/window{w}", us,
                     f"rmse={rmse:.2f} retrains={p.n_retrains}"))
    return rows


# --------------------------------------------------------------- overhead
def overhead() -> List[Row]:
    """Paper §IV-D: per-call latency of each Magnus component."""
    from repro.configs import get_config
    from repro.core.batcher import AdaptiveBatcher, BatcherConfig
    from repro.core.estimator import ServingTimeEstimator
    from repro.core.predictor import GenerationLengthPredictor
    from repro.core.scheduler import HRRNScheduler
    from repro.core.types import Batch
    from repro.core.wma import MemoryModel
    from repro.serving.cost_model import CostModel, V100_32G
    from repro.workload.apps import make_dataset
    cfg = get_config("chatglm-6b")
    reqs = make_dataset(30, seed=3)
    pred = GenerationLengthPredictor(seed=0).fit(reqs)
    cost = CostModel(cfg, V100_32G)
    rows_est = [(i + 1, 100 * i + 8, 50 * i + 1,
                 cost.batch_serving_time(i + 1, 100 * i + 8, 50 * i + 1))
                for i in range(20)]
    est = ServingTimeEstimator().fit(rows_est)
    mem = MemoryModel(cfg, hbm_bytes=32 * 2 ** 30)
    batcher = AdaptiveBatcher(mem, BatcherConfig())
    test = make_dataset(5, seed=9)
    for r in test[:20]:
        r.predicted_gen_length = pred.predict(r)
        batcher.insert(r, 0.0)
    sched = HRRNScheduler(est.estimate)
    rows = []
    us, _ = _timeit(lambda: pred.predict(test[0]), n=20)
    rows.append(("overhead/predict", us, "paper: <0.03s"))
    us, _ = _timeit(lambda: batcher.insert(test[1], 0.0), n=20)
    rows.append(("overhead/batch_insert", us, "paper: <0.001s"))
    us, _ = _timeit(lambda: est.estimate(Batch(requests=test[:3])), n=20)
    rows.append(("overhead/estimate", us, "paper: <0.001s"))
    us, _ = _timeit(lambda: sched.select(batcher.queue, 1.0), n=20)
    rows.append(("overhead/schedule", us, "paper: <0.002s"))
    return rows


# ----------------------------------------------------------------- kernels
def kernels() -> List[Row]:
    """Pallas kernels vs jnp oracle in interpret mode (correctness +
    CPU-interpret timing; TPU wall-time requires hardware)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.kernel import decode_attention_kernel
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    key = jax.random.PRNGKey(0)
    rows = []
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(key, (1, 256, 2, 64))
    v = jax.random.normal(key, (1, 256, 2, 64))
    us, out = _timeit(lambda: flash_attention_kernel(
        q, k, v, block_q=64, block_k=64, interpret=True), n=1)
    err = float(jnp.max(jnp.abs(out - flash_attention_ref(q, k, v))))
    rows.append(("kernels/flash_attention", us, f"max_err={err:.2e}"))
    qd = jax.random.normal(key, (2, 4, 64))
    kd = jax.random.normal(key, (2, 512, 2, 64))
    vd = jax.random.normal(key, (2, 512, 2, 64))
    lens = jnp.array([512, 100])
    us, out = _timeit(lambda: decode_attention_kernel(
        qd, kd, vd, lens, block_k=128, interpret=True), n=1)
    err = float(jnp.max(jnp.abs(out - decode_attention_ref(qd, kd, vd, lens))))
    rows.append(("kernels/decode_attention", us, f"max_err={err:.2e}"))
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_kernel)
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    kp = kd.reshape(-1, 32, 2, 64).swapaxes(1, 2)   # 2*16 head-major pages
    vp = vd.reshape(-1, 32, 2, 64).swapaxes(1, 2)
    tables = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    us, out = _timeit(lambda: paged_decode_attention_kernel(
        qd, kp, vp, tables, lens, interpret=True), n=1)
    err = float(jnp.max(jnp.abs(
        out - paged_decode_attention_ref(qd, kp, vp, tables, lens))))
    rows.append(("kernels/paged_decode_attention", us, f"max_err={err:.2e}"))
    x = jax.random.normal(key, (1, 256, 2, 32))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 256, 2)))
    a = -jnp.exp(jax.random.normal(key, (2,)))
    b = jax.random.normal(key, (1, 256, 16))
    c = jax.random.normal(key, (1, 256, 16))
    us, (y, st) = _timeit(lambda: ssd_scan_kernel(
        x, dt, a, b, c, chunk=64, interpret=True), n=1)
    yr, _ = ssd_scan_ref(x, dt, a, b, c)
    rows.append(("kernels/ssd_scan", us,
                 f"max_err={float(jnp.max(jnp.abs(y - yr))):.2e}"))
    return rows
