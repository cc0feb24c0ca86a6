"""Speculative decoding conformance suite (DESIGN.md §16).

The §16 contract, asserted under ``REPRO_SANITIZE=1`` for the whole
module (the shadow allocator audits every draft-pool write too):

- **speculation never changes greedy output**: a spec-on engine's
  streams are bit-identical to the ``fuse=False`` per-token oracle AND
  to the spec-off fused engine — for self-draft (everything accepted),
  for a genuinely different draft model (proposals rejected), across
  radix hit/miss mixes with mid-block COW tails, and for every
  ``draft_k`` in {1, 2, 4, 8};
- verification is ONE batched target dispatch per window and the host
  reads back a single packed array: syncs stay one per window;
- rejected-token rollback is pure block-table truncation — it never
  frees or mutates a block another holder still references (COW rules
  apply to rollback), which the hypothesis property test drives over
  random accept/reject patterns;
- the draft pool rides the engine's existing admission / grow / evict
  valves and drains to zero with the target pool (``assert_drained``).
"""
import copy
import dataclasses
import os

import pytest

from repro.core.types import Request
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.paged_cache import BlockAllocator
from repro.testing import given, settings, strategies as st
from repro.workload.apps import make_shared_prefix_dataset

from conftest import tiny_draft_cfg, tiny_engine_cfg

CFG = tiny_engine_cfg()
DRAFT = tiny_draft_cfg()
MAX_GEN = 10
BT = 4


@pytest.fixture(autouse=True, scope="module")
def _sanitize():
    old = os.environ.get("REPRO_SANITIZE")
    os.environ["REPRO_SANITIZE"] = "1"
    yield
    if old is None:
        os.environ.pop("REPRO_SANITIZE", None)
    else:
        os.environ["REPRO_SANITIZE"] = old


def _engine(num_blocks=96, *, n=4, **kw):
    return PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=num_blocks, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, **kw)


_REQ_CACHE = {}


def _reqs(n, seed=0):
    key = (n, seed)
    if key not in _REQ_CACHE:
        _REQ_CACHE[key] = [
            Request(app=f"a{i % 3}", task="t",
                    instruction=f"spec instruction {seed} {i} words",
                    user_input=f"user input number {i} more text",
                    length=14, gen_length=3 + (i * 3) % MAX_GEN,
                    predicted_gen_length=1)
            for i in range(n)]
    return copy.deepcopy(_REQ_CACHE[key])


_REF_CACHE = {}


def _reference_streams(n, seed=0):
    """The per-token oracle: fuse=False, spec off, roomy pool."""
    key = (n, seed)
    if key not in _REF_CACHE:
        eng = _engine(n=n, fuse=False)
        stats = drive_paged(eng, _reqs(n, seed=seed))
        assert stats["served"] == n
        eng.assert_drained()
        _REF_CACHE[key] = dict(eng.generated)
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# the §16 invariant: speculation never changes greedy output
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_selfdraft_bitexact_across_draft_k(k):
    """Self-draft at every tested window size matches BOTH references:
    the per-token loop and the spec-off fused window."""
    ref = _reference_streams(4)
    fused = _engine()
    drive_paged(fused, _reqs(4))
    fused.assert_drained()
    assert dict(fused.generated) == ref   # fused vs per-token baseline
    eng = _engine(spec_decode=True, draft_k=k)
    stats = drive_paged(eng, _reqs(4))
    eng.assert_drained()
    assert stats["served"] == 4
    for rid, toks in ref.items():
        assert eng.generated[rid] == toks, f"req {rid} diverged at k={k}"
    # self-draft: every proposal is the target's own greedy token
    assert stats["acceptance_rate"] == 1.0
    assert stats["accepted_per_dispatch"] > 1.0


def test_real_draft_model_bitexact_under_rejection():
    """A draft with different weights mispredicts (acceptance < 1) —
    verification must still reproduce the target stream bit-exactly."""
    ref = _reference_streams(4, seed=3)
    eng = _engine(spec_decode=True, draft_k=4, draft_cfg=DRAFT)
    stats = drive_paged(eng, _reqs(4, seed=3))
    eng.assert_drained()
    assert stats["served"] == 4
    for rid, toks in ref.items():
        assert eng.generated[rid] == toks
    assert stats["acceptance_rate"] < 1.0
    # even with every proposal rejected the window emits >= 1 token
    assert stats["accepted_per_dispatch"] >= 1.0


def test_radix_mixes_and_cow_tails_bitexact():
    """Radix hit/miss mixes with mid-block shared tails: the spec
    engine's verify path crosses prefill-seeded carries, COW clones and
    published prefixes, and still matches the spec-off radix engine."""
    reqs = make_shared_prefix_dataset(12, seed=5)
    for r in reqs:
        r.gen_length = min(r.gen_length, MAX_GEN)
    ref = _engine(n=4, prefix_cache=True)
    drive_paged(ref, copy.deepcopy(reqs))
    ref.assert_drained()
    eng = _engine(n=4, prefix_cache=True, spec_decode=True, draft_k=4)
    stats = drive_paged(eng, copy.deepcopy(reqs))
    eng.assert_drained()
    assert stats["served"] == len(reqs)
    assert dict(eng.generated) == dict(ref.generated)


def test_step_interleaving_matches_window():
    """step() (a max_steps=1 window) under speculation clamps emission
    to one token and still reproduces the reference streams."""
    ref = _reference_streams(3, seed=7)
    eng = _engine(n=3, spec_decode=True, draft_k=4)
    eng.join_many(_reqs(3, seed=7))
    for _ in range(200):
        eng.step()
        if eng.num_active == 0:
            break
    eng.assert_drained()
    assert dict(eng.generated) == ref


# ---------------------------------------------------------------------------
# window accounting: one sync per window, counters add up
# ---------------------------------------------------------------------------

def test_one_sync_per_spec_window():
    eng = _engine(spec_decode=True, draft_k=4, warmup=False)
    eng.join_many(_reqs(4))
    syncs0 = eng.host_syncs
    finished, evicted, k = eng.step_window()
    assert eng.host_syncs - syncs0 == 1     # ONE packed readback
    assert evicted == [] and k >= 1
    assert eng.spec_windows == 1
    assert eng.spec_slot_windows == 4
    drive_paged(eng, [])
    eng.assert_drained()


def test_spec_counters_and_prefill_split():
    """Draft admission prefills are counted separately — the TARGET
    wave discipline (one prefill dispatch per wave) is untouched."""
    eng = _engine(spec_decode=True, draft_k=4)
    stats = drive_paged(eng, _reqs(4))
    eng.assert_drained()
    assert eng.prefill_dispatches == 1          # one admission wave
    assert eng.draft_prefill_tokens == eng.prefill_tokens
    assert stats["spec_emitted"] == sum(
        len(t) for t in eng.generated.values())
    assert stats["spec_accepted"] == (stats["spec_emitted"]
                                      - eng.spec_slot_windows)


# ---------------------------------------------------------------------------
# rollback = truncation: unit + property (never frees/mutates shared)
# ---------------------------------------------------------------------------

def test_truncate_unit():
    alloc = BlockAllocator(num_blocks=8, block_tokens=2)
    table = list(alloc.allocate(0, 8))             # 4 blocks
    released = alloc.truncate(0, 2)
    assert released == table[2:]
    assert list(alloc.tables[0]) == table[:2]
    assert set(released) <= set(alloc.free)
    assert alloc.truncate(0, 2) == []              # idempotent
    assert alloc.truncate(99, 0) == []             # missing seq: no-op
    with pytest.raises(ValueError):
        alloc.truncate(0, -1)
    alloc.free_seq(0)
    assert alloc.used_blocks == 0


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=4, max_value=12),
       st.integers(min_value=0, max_value=12),
       st.lists(st.integers(min_value=0, max_value=12),
                min_size=1, max_size=6))
def test_truncate_never_frees_or_mutates_shared(n_blocks, shared_n, keeps):
    """Random accept/reject rollback patterns: truncation of a seq whose
    tail is still held by a radix-like sharer releases only THIS seq's
    references — the shared blocks stay allocated for the other holder,
    and total refcounts are exactly conserved."""
    shared_n = min(shared_n, n_blocks)
    alloc = BlockAllocator(num_blocks=16, block_tokens=2)
    table = list(alloc.allocate(0, n_blocks * 2))
    if shared_n:
        alloc.share(1, table[:shared_n])           # the "radix holder"
    for keep in keeps:
        # the engine floors rollback at the accepted stream, which always
        # covers the published/shared span — mirror that contract here
        keep = min(max(keep, shared_n), n_blocks)
        released = alloc.truncate(0, keep)
        assert released == table[keep:]
        kept = table[:keep]
        for b in table[:shared_n]:
            # the sharer's blocks are never freed out from under it
            assert alloc.refcount.get(b, 0) >= 1
        # regrow to the full table size: fresh blocks append, the kept
        # prefix is untouched (same physical ids => no mutation)
        table = list(alloc.allocate(0, n_blocks * 2))
        assert table[:keep] == kept and len(table) == n_blocks
    alloc.free_seq(0)
    if shared_n:
        for b in table[:shared_n]:
            assert alloc.refcount.get(b, 0) == 1   # holder survives
        alloc.free_seq(1)
    assert alloc.used_blocks == 0


# ---------------------------------------------------------------------------
# draft guard + draft pool lifecycle
# ---------------------------------------------------------------------------

def test_poisoned_draft_quarantines_not_the_request():
    """NaN draft logits ice the slot's DRAFT permanently; the request
    keeps serving one verified token per window, bit-exactly."""
    ref = _reference_streams(2, seed=9)
    eng = _engine(n=2, spec_decode=True, draft_k=4, nan_guard=True)
    eng.join_many(_reqs(2, seed=9))
    eng.step_window()
    live = next(s for s, a in enumerate(eng.active) if a is not None)
    eng.draft_logits = eng.draft_logits.at[live].set(float("nan"))
    drive_paged(eng, [])
    eng.assert_drained()
    assert eng.draft_quarantined == 1
    assert eng.quarantined == 0                    # request survived
    assert dict(eng.generated) == ref


def test_draft_pool_drains_with_target_pool():
    """assert_drained covers the draft band: a leaked draft seq (or a
    draft block surviving finish) fails the drain check."""
    eng = _engine(spec_decode=True, draft_k=2)
    drive_paged(eng, _reqs(4))
    eng.assert_drained()
    stray = [s for s in eng.allocator.tables
             if s <= eng._DRAFT_SEQ_BASE and eng.allocator.tables[s]]
    assert stray == []
    # and the check actually bites: a planted draft-band seq trips it
    eng.allocator.allocate(eng._draft_seq(0), 1)
    with pytest.raises(Exception):
        eng.assert_drained()
    eng.allocator.free_seq(eng._draft_seq(0))


def test_spec_rejects_unfused_and_mismatched_vocab():
    with pytest.raises(ValueError):
        _engine(spec_decode=True, fuse=False)
    with pytest.raises(ValueError):
        _engine(spec_decode=True, draft_cfg=dataclasses.replace(
            DRAFT, vocab_size=CFG.vocab_size // 2))


# ---------------------------------------------------------------------------
# sim mirror: accepted-tokens-per-dispatch pricing
# ---------------------------------------------------------------------------

def test_sim_spec_dispatch_pricing():
    """HostSyncCost (sim/runner.py) with dispatch="spec": the expected
    accepted prefix is geometric in the acceptance rate (floor 1.0,
    ceiling draft_k+1), the per-emitted-token cost falls monotonically
    with acceptance, and a high-acceptance cheap draft beats the fused
    engine's per-token cost — decode is memory-bound, so one verify
    dispatch covering draft_k+1 positions rereads params/KV once."""
    from repro.configs import get_config
    from repro.serving.cost_model import CostModel, TPU_V5E
    from repro.sim.runner import HostSyncCost

    base = CostModel(get_config("chatglm-6b"), TPU_V5E)
    selfdraft = HostSyncCost(base, 0.01, "spec", acceptance=1.0, draft_k=4)
    reject = HostSyncCost(base, 0.01, "spec", acceptance=0.0, draft_k=4)
    mid = HostSyncCost(base, 0.01, "spec", acceptance=0.8, draft_k=4)
    assert selfdraft.accepted_per_dispatch() == 5.0
    assert reject.accepted_per_dispatch() == 1.0
    assert 1.0 < mid.accepted_per_dispatch() < 5.0
    # monotone: higher acceptance => cheaper per emitted token
    assert (selfdraft.decode_iter_time(8, 256)
            < mid.decode_iter_time(8, 256)
            < reject.decode_iter_time(8, 256))
    fused = HostSyncCost(base, 0.01, "fused")
    assert selfdraft.decode_iter_time(8, 256) \
        < fused.decode_iter_time(8, 256)
    # the sync schedule follows the emitted-token amortization
    assert selfdraft._syncs(20) == 4 and reject._syncs(20) == 20
    with pytest.raises(ValueError):
        HostSyncCost(base, 0.01, "spec", acceptance=1.5)
    with pytest.raises(ValueError):
        HostSyncCost(base, 0.01, "warp")
