"""Fused multi-step decode acceptance tests (ISSUE 2; DESIGN.md §9):

- ``decode_multi_paged(k)`` is bit-exact with ``k`` sequential
  ``decode_step_paged`` calls (pages, logits, emitted tokens) — fusion
  changes dispatch, not arithmetic
- dense ``decode_multi`` likewise matches sequential ``decode_step``
  (the BatchEngine inner loop rides the same fused path)
- the fused engine's generated tokens match the per-token (``fuse=False``)
  engine's, with strictly fewer host syncs
- property: fusion-window boundaries never skip a finish / grow / evict
  event (every window ends with progress <= target and positions within
  the allocated block tables)
- the sim-side HostSyncCost mirror: fused dispatch strictly beats
  per-token dispatch at any nonzero host-sync cost
"""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
except ImportError:
    from repro.testing import example, given, settings
    from repro.testing import strategies as st

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import model as M
from repro.serving.engine import (PagedContinuousEngine, _jitted,
                                  drive_paged)
from repro.workload.apps import make_dataset

from conftest import tiny_cfg

CFG = tiny_cfg()


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.PRNGKey(0))


def _reqs(n, max_gen=10, seed=0, predicted=True, short=True):
    reqs = make_dataset(2, seed=seed)[:n]
    for i, r in enumerate(reqs):
        if short:
            r.user_input = " ".join(r.user_input.split()[:6])
        r.gen_length = 3 + (i * 3) % max_gen
        r.predicted_gen_length = r.gen_length if predicted else None
    return reqs


# ---------------------------------------------------------------------------
# bit-exact equivalence, model level
# ---------------------------------------------------------------------------

def _paged_fixture(params, b=3, num_blocks=64, bt=8, max_blocks=12):
    rng = np.random.default_rng(0)
    pages = M.init_paged_cache(CFG, num_blocks, bt, dtype=jnp.float32)
    tables = rng.permutation(np.arange(1, num_blocks))[:b * max_blocks]
    tables = tables.reshape(b, max_blocks).astype(np.int32)
    positions = np.array([5, 9, 3], np.int32)[:b]
    logits0 = jnp.asarray(
        rng.normal(size=(b, CFG.padded_vocab)).astype(np.float32))
    return pages, jnp.asarray(tables), jnp.asarray(positions), logits0


def test_decode_multi_paged_bitexact_vs_sequential(params):
    """k fused steps == k sequential decode_step_paged calls, bit for bit
    (k deliberately not a power of two: correctness is per-step)."""
    k = 6
    jt = _jitted(CFG, jnp.float32)
    pages, tables, positions, logits = _paged_fixture(params)
    lg, pos = logits, positions
    pg = jax.tree.map(jnp.copy, pages)   # decode_paged donates its pages
    seq_toks = []
    for _ in range(k):
        tok = jnp.argmax(lg[:, :CFG.vocab_size], axis=-1).astype(jnp.int32)
        seq_toks.append(np.asarray(tok))
        lg, pg = jt["decode_paged"](
            params, pages=pg,
            batch={"tokens": tok, "positions": pos, "block_tables": tables})
        pos = pos + 1
    seq_toks = np.stack(seq_toks, axis=1)

    flg, fpg, fpos, ftoks = jt["decode_multi_paged"](
        params, pages=pages,
        batch={"logits": logits, "positions": positions,
               "block_tables": tables,
               "active": jnp.ones(positions.shape[0], bool)},
        num_steps=k)
    assert np.array_equal(np.asarray(ftoks), seq_toks)
    assert np.array_equal(np.asarray(flg), np.asarray(lg))
    assert np.array_equal(np.asarray(fpg["k"]), np.asarray(pg["k"]))
    assert np.array_equal(np.asarray(fpg["v"]), np.asarray(pg["v"]))
    assert np.array_equal(np.asarray(fpos), np.asarray(pos))


def test_decode_multi_paged_inactive_slots_frozen(params):
    """Inactive slots neither advance positions nor touch live pages
    (their writes land in the table they carry — the engine points idle
    tables at the null block)."""
    k = 4
    jt = _jitted(CFG, jnp.float32)
    pages, tables, positions, logits = _paged_fixture(params)
    active = jnp.asarray(np.array([True, False, True]))
    _, _, fpos, _ = jt["decode_multi_paged"](
        params, pages=pages,
        batch={"logits": logits, "positions": positions,
               "block_tables": tables, "active": active},
        num_steps=k)
    got = np.asarray(fpos)
    want = np.asarray(positions) + k * np.asarray(active).astype(np.int32)
    assert np.array_equal(got, want)


def test_decode_multi_dense_bitexact_vs_sequential(params):
    """Dense fused decode (the BatchEngine inner loop) matches sequential
    decode_step calls bit for bit, across a window split (5 = 4 + 1)."""
    jt = _jitted(CFG, jnp.float32)
    rng = np.random.default_rng(1)
    b, s = 2, 16
    tokens = rng.integers(1, CFG.vocab_size, size=(b, s))
    lengths = np.array([11, 16], np.int32)
    logits, cache = jt["prefill"](
        params, batch={"tokens": jnp.asarray(tokens),
                       "lengths": jnp.asarray(lengths)},
        cache_len=64)
    pos = jnp.asarray(lengths)
    lg = logits
    ch = jax.tree.map(jnp.copy, cache)   # decode donates its cache
    seq_toks = []
    for _ in range(5):
        tok = jnp.argmax(lg[:, :CFG.vocab_size], axis=-1).astype(jnp.int32)
        seq_toks.append(np.asarray(tok))
        lg, ch = jt["decode"](params, cache=ch,
                              batch={"tokens": tok, "positions": pos})
        pos = pos + 1
    seq_toks = np.stack(seq_toks, axis=1)

    flg, fch, fpos, t1 = jt["decode_multi"](
        params, cache=cache,
        batch={"logits": logits, "positions": jnp.asarray(lengths)},
        num_steps=4)
    flg, fch, fpos, t2 = jt["decode_multi"](
        params, cache=fch, batch={"logits": flg, "positions": fpos},
        num_steps=1)
    ftoks = np.concatenate([np.asarray(t1), np.asarray(t2)], axis=1)
    assert np.array_equal(ftoks, seq_toks)
    assert np.array_equal(np.asarray(flg), np.asarray(lg))


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def test_fused_engine_matches_per_token_engine(params):
    """Same requests, same params: fuse=True and fuse=False produce
    identical token streams, and fusion cuts host syncs per token."""
    out, syncs, steps = {}, {}, {}
    for fuse in (False, True):
        eng = PagedContinuousEngine(CFG, params=params, max_concurrency=4,
                                    num_blocks=48, block_tokens=8,
                                    max_len=128, max_gen=16, fuse=fuse)
        reqs = _reqs(4, seed=2)        # fresh ids per run; compare by index
        stats = drive_paged(eng, reqs)
        assert stats["served"] == len(reqs)
        out[fuse] = [eng.generated[r.req_id] for r in reqs]
        syncs[fuse] = stats["host_syncs"]
        steps[fuse] = stats["steps"]
    assert out[True] == out[False]
    assert steps[True] == steps[False], "fusion must not change step count"
    assert syncs[True] < syncs[False], (syncs, "fusion must amortize syncs")


def test_batch_engine_single_slice_and_sync_count(params):
    """BatchEngine satellite: the fused loop reads back O(log bg) windows
    instead of bg per-token syncs."""
    from repro.core.types import Batch
    from repro.serving.engine import BatchEngine
    reqs = _reqs(3, seed=4, max_gen=12)
    eng = BatchEngine(CFG, params=params, max_gen=12)
    res = eng.serve_batch(Batch(requests=reqs))
    bg = res.iterations
    assert eng.host_syncs == bin(bg).count("1"), \
        "one readback per power-of-two window"
    for r in reqs:
        assert len(res.generated[r.req_id]) == min(r.gen_length, 12)


# ---------------------------------------------------------------------------
# property: windows never skip engine events
# ---------------------------------------------------------------------------

_PROP_ENGINE = {}


def _prop_engine():
    """One engine reused across examples (drained between runs) so the
    shared jit cache compiles once for the whole property sweep.
    No pytest fixture: @given-wrapped tests take drawn args only."""
    if "eng" not in _PROP_ENGINE:
        _PROP_ENGINE["eng"] = PagedContinuousEngine(
            CFG, params=M.init_params(CFG, jax.random.PRNGKey(0)),
            max_concurrency=4, num_blocks=12,
            block_tokens=8, max_len=64, max_gen=16)
    return _PROP_ENGINE["eng"]


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=5),
       st.lists(st.tuples(st.integers(min_value=1, max_value=12),
                          st.integers(min_value=1, max_value=12)),
                min_size=5, max_size=5),
       st.integers(min_value=0, max_value=10_000))
@example(n=1, gens=[(1, 1)] * 5, seed=0)
def test_fusion_windows_never_skip_events(n, gens, seed):
    """Drive random (target, prediction) workloads through the fused
    engine, checking after every window that (a) no request decoded past
    its target, (b) no position outran its allocated block table, and
    (c) every request finished with exactly its target tokens — i.e. every
    finish/grow/evict event fell on a window boundary."""
    from collections import deque
    eng = _prop_engine()
    reqs = _reqs(n, seed=seed % 7, short=True)
    for r, (g, pred) in zip(reqs, gens):
        r.gen_length = g
        r.predicted_gen_length = pred      # over- and under-shoot freely
    pending = deque(reqs)
    done, guard = 0, 0
    while (pending or eng.num_active) and guard < 400:
        for _ in range(eng.join_many(pending)):
            pending.popleft()
        finished, evicted, k = eng.step_window()
        done += len(finished)
        for r in reversed(evicted):
            pending.appendleft(r)
        for slot, a in enumerate(eng.active):
            if a is None:
                continue
            assert len(a["generated"]) <= a["target"], \
                "window decoded past a finish event"
            cap = len(eng.allocator.tables[slot]) * eng.bt
            assert int(eng.pos_host[slot]) <= cap, \
                "window crossed a block boundary without a grow"
        guard += max(k, 1)
    assert done == len(reqs), "fused serve left requests unfinished"
    for r in reqs:
        assert len(eng.generated[r.req_id]) == min(r.gen_length, 16)
    assert eng.allocator.used_blocks == 1     # pool fully reclaimed


# ---------------------------------------------------------------------------
# sim mirror
# ---------------------------------------------------------------------------

def test_sim_host_sync_cost_fused_beats_per_token():
    """HostSyncCost (sim/runner.py): any nonzero per-iteration host cost
    makes fused dispatch strictly faster at cluster scale, and zero cost
    leaves the original numbers untouched."""
    from repro.sim.runner import run_strategy
    from repro.workload.generator import poisson_workload
    cfg = get_config("chatglm-6b")
    wl = poisson_workload(8.0, 20.0, seed=0)
    base = run_strategy("magnus", wl, cfg, seed=0)
    again = run_strategy("magnus", wl, cfg, seed=0, host_sync_s=0.0)
    assert again.summary() == base.summary()
    fused = run_strategy("magnus", wl, cfg, seed=0, host_sync_s=0.05,
                         dispatch="fused")
    per_tok = run_strategy("magnus", wl, cfg, seed=0, host_sync_s=0.05,
                           dispatch="per-token")
    assert fused.avg_response_time < per_tok.avg_response_time
    assert fused.token_throughput >= per_tok.token_throughput
