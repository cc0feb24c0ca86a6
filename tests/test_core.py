"""Magnus core components: WMA (Eqs. 2-4), memory model (Eqs. 1/5),
Algorithm 1 batcher, estimator, HRRN scheduler, regressors — with
hypothesis property tests on the system's invariants."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:          # bare env: seeded fallback (repro.testing)
    from repro.testing import given, settings
    from repro.testing import strategies as st

from repro.configs import get_config
from repro.core.batcher import AdaptiveBatcher, BatcherConfig
from repro.core.estimator import ServingTimeEstimator
from repro.core.forest import RandomForestRegressor
from repro.core.knn import KNNRegressor
from repro.core.scheduler import FCFSScheduler, HRRNScheduler
from repro.core.types import Batch, Request
from repro.core.wma import MemoryModel, batch_wma, wma_gen, wma_wait
from repro.workload.apps import make_dataset


def _req(length, gen, pred=None, t=0.0):
    r = Request(app="x", task="x", instruction="i", user_input="u",
                arrival_time=t, length=length, user_input_length=length,
                gen_length=gen)
    r.predicted_gen_length = pred if pred is not None else gen
    return r


# ---------------------------------------------------------------- WMA ----
def test_wma_paper_equations():
    # Eq. (2): G(p) * (L(B) - L(p))
    assert wma_gen(req_len=3, gen_len=5, batch_len=10) == 5 * 7
    # Eq. (3): sum_{g=G(p)}^{G(B)} (g + L(B)) for waiting requests; the
    # longest request of the batch never waits (0 by definition).
    assert wma_wait(gen_len=4, batch_len=10, batch_gen_len=4) == 0
    lit = sum(g + 10 for g in range(4, 6 + 1))
    assert wma_wait(gen_len=4, batch_len=10, batch_gen_len=6) == lit


@given(st.lists(st.tuples(st.integers(1, 500), st.integers(1, 500)),
                min_size=1, max_size=12))
@settings(max_examples=200, deadline=None)
def test_wma_properties(pairs):
    lengths = [p[0] for p in pairs]
    gens = [p[1] for p in pairs]
    w = batch_wma(lengths, gens)
    assert w >= 0
    # identical requests => zero waste
    assert batch_wma([lengths[0]] * 3, [gens[0]] * 3) == 0
    # adding a strictly dominated request can only keep or increase WMA
    w2 = batch_wma(lengths + [max(lengths)], gens + [max(gens)])
    assert w2 >= 0


@given(st.integers(1, 400), st.integers(1, 400), st.integers(0, 200),
       st.integers(0, 200))
@settings(max_examples=200, deadline=None)
def test_wma_monotone_in_mismatch(l, g, dl, dg):
    """More length/generation mismatch never decreases WMA."""
    base = batch_wma([l, l], [g, g])
    worse = batch_wma([l, l + dl], [g, g + dg])
    assert worse >= base


# ------------------------------------------------------------- memory ----
def test_eq1_vanilla_beta_matches_paper():
    """fp32 KV on a 32 GB V100 reproduces the paper's beta (~7) for
    ChatGLM-6B and a larger beta under int4 (paper: 10)."""
    cfg = get_config("chatglm-6b")
    m = MemoryModel(cfg, hbm_bytes=32 * 2 ** 30, dtype_bytes=4)
    mq = MemoryModel(cfg, hbm_bytes=32 * 2 ** 30, dtype_bytes=4,
                     param_dtype_bytes=0.5)
    assert 5 <= m.vanilla_batch_size() <= 9
    assert m.vanilla_batch_size() < mq.vanilla_batch_size() <= 14


def test_memory_model_families():
    ssm = MemoryModel(get_config("mamba2-780m"))
    dense = MemoryModel(get_config("qwen2.5-14b"))
    # ssm per-request memory is constant in sequence length
    assert ssm.request_bytes(100) == ssm.request_bytes(10_000)
    assert dense.request_bytes(10_000) > dense.request_bytes(100)
    mla = MemoryModel(get_config("deepseek-v3-671b"))
    # MLA latent cache is far smaller per token than dense GQA KV
    assert mla.delta < dense.delta


# ------------------------------------------------------------ batcher ----
def test_batcher_groups_similar_requests():
    mem = MemoryModel(get_config("chatglm-6b"), hbm_bytes=32 * 2 ** 30)
    b = AdaptiveBatcher(mem, BatcherConfig(wma_threshold=50_000))
    for _ in range(8):
        b.insert(_req(10, 10), now=0.0)
    for _ in range(3):
        b.insert(_req(900, 900), now=0.0)
    sizes = sorted(bt.size for bt in b.queue)
    assert len(b.queue) == 2 and sizes == [3, 8]


def test_batcher_respects_memory_cap():
    mem = MemoryModel(get_config("chatglm-6b"), hbm_bytes=32 * 2 ** 30,
                      dtype_bytes=4)
    b = AdaptiveBatcher(mem, BatcherConfig(wma_threshold=1e18))
    n = 40
    for _ in range(n):
        b.insert(_req(1000, 1000), now=0.0)
    for bt in b.queue:
        assert mem.mem_of(bt) <= mem.theta


def test_batcher_beta_cap_glp():
    mem = MemoryModel(get_config("chatglm-6b"), hbm_bytes=32 * 2 ** 30)
    b = AdaptiveBatcher(mem, BatcherConfig(wma_threshold=1e18,
                                           max_batch_size=7))
    for _ in range(20):
        b.insert(_req(10, 10), now=0.0)
    assert all(bt.size <= 7 for bt in b.queue)


def test_oom_split():
    mem = MemoryModel(get_config("chatglm-6b"), hbm_bytes=32 * 2 ** 30)
    b = AdaptiveBatcher(mem)
    batch = Batch(requests=[_req(10, 10) for _ in range(9)])
    b1, b2 = b.handle_oom(batch, now=1.0)
    assert b1.size + b2.size == 9 and abs(b1.size - b2.size) <= 1
    assert not b1.insertable and not b2.insertable
    assert b1 in b.queue and b2 in b.queue


@given(st.lists(st.tuples(st.integers(1, 1000), st.integers(1, 1000)),
                min_size=1, max_size=30))
@settings(max_examples=50, deadline=None)
def test_batcher_never_violates_memory(pairs):
    mem = MemoryModel(get_config("chatglm-6b"), hbm_bytes=32 * 2 ** 30,
                      dtype_bytes=4)
    b = AdaptiveBatcher(mem, BatcherConfig(wma_threshold=1e18))
    for l, g in pairs:
        b.insert(_req(l, g), now=0.0)
    assert sum(bt.size for bt in b.queue) == len(pairs)
    for bt in b.queue:
        assert mem.mem_of(bt) <= mem.theta


# ---------------------------------------------------------- scheduler ----
def test_hrrn_prefers_high_response_ratio():
    est = {1: 100.0, 2: 1.0}
    sched = HRRNScheduler(lambda b: est[b.batch_id])
    b1 = Batch(requests=[_req(10, 10, t=0.0)], created_time=0.0, batch_id=1)
    b2 = Batch(requests=[_req(10, 10, t=5.0)], created_time=5.0, batch_id=2)
    # b2: queued 5s / 1s = 5; b1: queued 10s / 100s = 0.1
    assert sched.select([b1, b2], now=10.0) is b2


def test_hrrn_starvation_resistance():
    """A long batch eventually outranks short ones as it queues."""
    sched = HRRNScheduler(lambda b: 100.0 if b.batch_id == 1 else 1.0)
    b1 = Batch(requests=[_req(10, 10, t=0.0)], created_time=0.0, batch_id=1)
    b2 = Batch(requests=[_req(10, 10, t=9_999.0)], created_time=9_999.0,
               batch_id=2)
    assert sched.select([b1, b2], now=10_000.0) is b1


def test_fcfs():
    s = FCFSScheduler()
    b1 = Batch(created_time=1.0)
    b2 = Batch(created_time=0.5)
    assert s.select([b1, b2], now=2.0) is b2


# ----------------------------------------------------------- learners ----
def test_forest_fits_linear():
    rng = np.random.default_rng(0)
    x = rng.uniform(0, 10, (500, 3)).astype(np.float32)
    y = 3 * x[:, 0] - 2 * x[:, 1] + rng.normal(0, 0.1, 500)
    f = RandomForestRegressor(n_trees=10, max_depth=10).fit(x, y)
    pred = f.predict(x)
    rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
    assert rmse < 2.0


def test_knn_regression():
    x = np.array([[1.0], [2.0], [3.0], [10.0]], np.float32)
    y = np.array([1.0, 2.0, 3.0, 10.0], np.float32)
    k = KNNRegressor(k=2).fit(x, y)
    assert abs(float(k.predict(np.array([[2.1]]))[0]) - 2.0) < 1.0


def test_estimator_learns_cost_model():
    from repro.serving.cost_model import CostModel
    cfg = get_config("chatglm-6b")
    cost = CostModel(cfg)
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(300):
        beta, bl, bg = int(rng.integers(1, 32)), int(rng.integers(8, 1024)), \
            int(rng.integers(1, 1024))
        rows.append((beta, bl, bg, cost.batch_serving_time(beta, bl, bg)))
    est = ServingTimeEstimator().fit(rows[:250])
    rmse = est.rmse(rows[250:])
    mean_t = np.mean([r[3] for r in rows[250:]])
    assert rmse < 0.5 * mean_t


# ------------------------------------------------- continuous learning ----
@pytest.mark.slow
def test_predictor_continuous_learning_reduces_error():
    train = make_dataset(40, seed=0)
    test = make_dataset(40, seed=1)
    from repro.core.predictor import GenerationLengthPredictor, PredictorConfig
    p = GenerationLengthPredictor(
        PredictorConfig(retrain_period=0.0, n_trees=8, max_depth=8)).fit(train)
    before = p.rmse(test)
    # feed it the test distribution as served requests
    now = 0.0
    for r in test:
        r.predicted_gen_length = p.predict(r)
        now += 10.0
        p.observe(r, now)
    assert p.n_retrains > 0
    after = p.rmse(test)
    assert after <= before * 1.05


# ------------------------------------------------- hypothesis fallback ----
def test_fallback_shim_runs_explicit_examples_first():
    """The bare-env shim (repro.testing) honours ``@example`` like
    hypothesis: recorded cases run before the seeded draws."""
    from repro import testing
    seen = []

    @testing.settings(max_examples=2, deadline=None)
    @testing.given(testing.st.integers(0, 5))
    @testing.example(n=99)
    def prop(n):
        seen.append(n)

    prop()
    assert seen[0] == 99 and len(seen) == 3
    assert all(0 <= n <= 5 for n in seen[1:])
