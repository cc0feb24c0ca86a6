"""Chaos harness: the §14 degradation contract under scripted faults.

Every test replays a deterministic :class:`FaultInjector` plan through
the paged engine and asserts the same contract the ``chaos`` benchmark
floors pin:

- no hang: the driver finishes inside its step budget;
- no crash: faults surface as typed sheds / typed exceptions, never as
  stack traces out of the serve loop;
- no strand: after the plan's restore the allocator drains to the null
  block (``assert_drained``);
- bit-exact survivors: every *finished* stream equals the fault-free
  reference run token-for-token — quarantined and evicted requests
  restart from the prompt, and replay-scripted generation must
  reconverge exactly;
- bounded: retries, deadline misses, and sheds are counted, and
  ``served + shed`` accounts for every request.
"""
import copy

import pytest

from repro.serving.engine import (EngineFull, PagedContinuousEngine,
                                  PoolExhausted, drive_paged)
from repro.serving.faults import (FAULT_SEQ, FaultEvent, FaultInjector,
                                  Shed)
from repro.serving.paged_cache import BlockAllocator, MispredictionEWMA
from repro.testing import given, settings, strategies as st
from repro.workload.apps import make_dataset

from conftest import tiny_engine_cfg

CFG = tiny_engine_cfg()
MAX_GEN = 10
BT = 4


_REQ_CACHE = {}


def _reqs(n, max_gen=MAX_GEN, seed=0):
    """One canonical request list per (n, seed): req_ids are minted at
    construction, and the reference-stream comparison keys on them — so
    every run (reference and fault) must deepcopy the SAME base list."""
    key = (n, max_gen, seed)
    if key not in _REQ_CACHE:
        reqs = make_dataset(2, seed=seed)[:n]
        for i, r in enumerate(reqs):
            r.user_input = " ".join(r.user_input.split()[:6])
            r.gen_length = 3 + (i * 3) % max_gen
            r.predicted_gen_length = r.gen_length
        _REQ_CACHE[key] = reqs
    return copy.deepcopy(_REQ_CACHE[key])


def _engine(num_blocks=48, *, faults=None, n=4, **kw):
    return PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=num_blocks, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, faults=faults, **kw)


_REF_CACHE = {}


def _reference_streams(n, seed=0):
    """Fault-free generated streams keyed by req_id (module-cached:
    req_ids are assigned at dataset construction and survive deepcopy,
    so every fault run compares against the same ids)."""
    key = (n, seed)
    if key not in _REF_CACHE:
        eng = _engine(n=n)
        st_ = drive_paged(eng, copy.deepcopy(_reqs(n, seed=seed)))
        assert st_["served"] == n
        eng.assert_drained()
        _REF_CACHE[key] = dict(eng.generated)
    return _REF_CACHE[key]


def _assert_contract(eng, stats, inj, n, seed=0):
    """The degradation contract, shared by every storm test."""
    inj.release(eng.allocator)
    assert not stats["unserved"], "hang: driver exited with a live queue"
    assert stats["served"] + len(stats["shed"]) == n, \
        "unaccounted requests: neither served nor typed-shed"
    ref = _reference_streams(n, seed=seed)
    for rid, toks in eng.generated.items():
        assert toks == ref[rid], f"survivor {rid} diverged from reference"
    eng.assert_drained()
    assert FAULT_SEQ not in eng.allocator.tables or \
        not eng.allocator.tables[FAULT_SEQ]


# ---------------------------------------------------------------------------
# scripted storms (the acceptance-criteria plans)
# ---------------------------------------------------------------------------

def test_allocator_exhaustion_storm_serves_everything():
    """Pool shrink mid-serve: evictions + retries, then the restore lets
    every request finish — bit-exact, drained, nothing shed."""
    n = 4
    inj = FaultInjector([
        FaultEvent(window=1, kind="pool_shrink", blocks=10),
        FaultEvent(window=4, kind="pool_restore"),
    ])
    eng = _engine(num_blocks=20, faults=inj, n=n)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert ("pool_shrink" in [k for _, k in inj.fired]
            and "pool_restore" in [k for _, k in inj.fired])
    assert stats["served"] == n and not stats["shed"]
    _assert_contract(eng, stats, inj, n)


def test_underprediction_storm_escalates_and_finishes():
    """×4 under-prediction on every admission: the eviction storm must
    damp (EWMA headroom + retry-budget escalation), not repeat forever."""
    n = 4
    inj = FaultInjector([
        FaultEvent(window=0, kind="predict_skew", factor=0.25),
    ])
    eng = _engine(num_blocks=24, faults=inj, n=n, retry_budget=2)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert inj.corrupted_predictions > 0
    assert stats["served"] == n and not stats["shed"]
    # the feedback loop must have seen the under-reservation
    assert eng.mispredict.samples > 0
    assert max(eng.mispredict.factor(app)
               for app in eng.mispredict.ratio) > 1.0
    # bounded: a damped storm cannot thrash hundreds of times
    assert stats["retries_max"] <= eng.retry_budget + 2
    _assert_contract(eng, stats, inj, n)


def test_poisoned_logits_quarantine_is_surgical():
    """NaN poisoning of one slot: exactly that slot is quarantined and
    re-served; every stream (victim included) matches the reference."""
    n = 4
    inj = FaultInjector([
        FaultEvent(window=2, kind="poison_logits", slot=0),
    ])
    eng = _engine(faults=inj, n=n)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert inj.poisoned == 1
    assert eng.quarantined == 1 and stats["quarantined"] == 1
    assert stats["served"] == n                 # the victim was re-served
    _assert_contract(eng, stats, inj, n)


def test_poisoned_draft_storm_keeps_verified_streams():
    """§14 × §16: a poisoned DRAFT logits row under speculation ices the
    slot's draft (cold draft), never the request — no target quarantine,
    every stream matches the spec-off fault-free reference, and the
    draft pool still drains."""
    n = 4
    inj = FaultInjector([
        FaultEvent(window=2, kind="poison_draft_logits", slot=0),
    ])
    eng = _engine(faults=inj, n=n, spec_decode=True, draft_k=4,
                  nan_guard=True)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert inj.draft_poisoned == 1
    assert eng.draft_quarantined == 1
    assert eng.quarantined == 0, \
        "a draft fault must never quarantine the verified target stream"
    assert stats["served"] == n and not stats["shed"]
    _assert_contract(eng, stats, inj, n)


def test_poisoned_draft_is_noop_without_speculation():
    """The same plan against a spec-off engine is a recorded no-op: the
    injector guards on the draft band existing."""
    n = 2
    inj = FaultInjector([
        FaultEvent(window=1, kind="poison_draft_logits"),
    ])
    eng = _engine(faults=inj, n=n, nan_guard=True)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert ("poison_draft_logits" in [k for _, k in inj.fired]
            and inj.draft_poisoned == 0)
    assert stats["served"] == n and not stats["shed"]
    _assert_contract(eng, stats, inj, n)


def test_deadline_storm_sheds_expired_requests():
    """Stalled windows burn the scheduler clock past tight TTLs: expired
    requests are shed with reason ``deadline`` (not requeued), counted,
    and their blocks freed."""
    n = 4
    inj = FaultInjector([
        FaultEvent(window=1, kind="stall", ticks=50),
    ])
    eng = _engine(faults=inj, n=n, default_ttl=8)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert eng.stall_ticks == 50
    assert stats["deadline_misses"] > 0
    assert all(s.reason == "deadline" for s in stats["shed"])
    assert len(stats["shed"]) == stats["deadline_misses"]
    _assert_contract(eng, stats, inj, n)


def test_radix_corruption_is_blocked_by_shadow(monkeypatch):
    """A rogue write into a cache-held radix block goes through the PR 6
    shadow path: with REPRO_SANITIZE=1 it is blocked and counted, and
    serving continues unaffected."""
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    n = 4
    inj = FaultInjector([
        FaultEvent(window=1, kind="radix_corrupt"),
    ])
    alloc = BlockAllocator(num_blocks=48, block_tokens=BT)
    eng = PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=48, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, faults=inj, allocator=alloc,
        prefix_cache=True)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    assert inj.radix_corruptions_blocked == 1
    assert inj.radix_probes_unchecked == 0
    assert stats["served"] == n and not stats["shed"]
    _assert_contract(eng, stats, inj, n)


# ---------------------------------------------------------------------------
# typed exception (satellite: no more attribute smuggling)
# ---------------------------------------------------------------------------

def test_engine_full_has_typed_evicted_field():
    assert EngineFull().evicted == ()
    assert EngineFull("msg", evicted=()).evicted == ()
    e = PoolExhausted("boom")
    assert isinstance(e, MemoryError) and isinstance(e, EngineFull)
    assert e.evicted == () and e.culprit is None


def _foreign_squeeze(n):
    """Engine whose free pool a foreign sequence (seq 999 on the shared
    allocator) swallows after admission: the first decode-time growth
    has no victim worth evicting and must raise PoolExhausted."""
    alloc = BlockAllocator(num_blocks=16, block_tokens=BT)
    eng = PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=16, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, allocator=alloc)
    reqs = _reqs(n)
    for r in reqs:
        r.gen_length = MAX_GEN
        r.predicted_gen_length = 1          # force decode-time growth
    return eng, alloc, reqs


def test_pool_exhausted_carries_culprit_and_leaves_engine_drainable():
    eng, alloc, reqs = _foreign_squeeze(1)
    assert eng.join_many(copy.deepcopy(reqs)) == 1
    alloc.allocate(999, len(alloc.free) * BT)
    with pytest.raises(PoolExhausted) as ei:
        for _ in range(2 * MAX_GEN):
            eng.step_window()
    e = ei.value
    assert isinstance(e, MemoryError)
    assert e.culprit is not None and e.culprit.req_id == reqs[0].req_id
    assert e.evicted == ()                  # no same-window evictions
    # nothing stranded: the culprit's slot was freed on the raise
    assert eng.num_active == 0
    alloc.free_seq(999)
    eng.assert_drained()


def test_drive_paged_sheds_pool_exhausted_culprit_as_oom():
    """The driver's catch site: a PoolExhausted window becomes a typed
    ``oom`` shed (plus requeued evictions), never a crash or a hang."""
    eng, alloc, reqs = _foreign_squeeze(1)
    alloc.allocate(999, (len(alloc.free) - 4) * BT)   # room to admit one
    stats = drive_paged(eng, copy.deepcopy(reqs), max_steps=200)
    assert stats["served"] == 0
    assert [s.reason for s in stats["shed"]] == ["oom"]
    assert stats["shed"][0].req.req_id == reqs[0].req_id
    assert not stats["unserved"]
    alloc.free_seq(999)
    eng.assert_drained()


def test_shed_reason_is_validated():
    with pytest.raises(ValueError):
        Shed(req=None, reason="because")
    with pytest.raises(ValueError):
        FaultEvent(window=0, kind="meteor_strike")


# ---------------------------------------------------------------------------
# requeue-through-radix (satellite small fix)
# ---------------------------------------------------------------------------

def test_requeued_request_prefills_only_its_suffix():
    """An evicted-then-requeued request re-enters admission through the
    radix hit path: its published blocks are still cached, so the
    readmission prefills only the uncached tail."""
    eng = PagedContinuousEngine(
        CFG, max_concurrency=2, num_blocks=48, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, prefix_cache=True)
    req = _reqs(1)[0]
    slot = eng.join(req)
    first = eng.prefill_tokens
    evicted = eng._evict(slot)
    assert evicted.req_id == req.req_id
    eng.join(req)
    second = eng.prefill_tokens - first
    assert eng.requeue_prefix_hits == 1
    assert second < first, \
        f"readmission re-prefilled {second} of {first} prompt tokens"
    eng._evict(0 if eng.active[0] is not None else 1)
    eng.assert_drained()


# ---------------------------------------------------------------------------
# property: random fault schedules never break the contract
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5),
                          st.sampled_from(["pool_shrink", "stall",
                                           "poison_logits",
                                           "predict_skew"])),
                min_size=1, max_size=4),
       st.sampled_from([0.25, 0.5, 2.0]))
def test_random_fault_schedule_keeps_contract(events, factor):
    n = 4
    plan = [FaultEvent(window=w, kind=k,
                       blocks=8 if k == "pool_shrink" else 0,
                       factor=factor if k == "predict_skew" else 1.0,
                       ticks=3 if k == "stall" else 0)
            for w, k in events]
    plan.append(FaultEvent(window=8, kind="pool_restore"))
    inj = FaultInjector(plan)
    eng = _engine(num_blocks=24, faults=inj, n=n)
    stats = drive_paged(eng, copy.deepcopy(_reqs(n)))
    _assert_contract(eng, stats, inj, n)
    # with no deadline and no retry cap, escalation must serve everything
    assert stats["served"] == n
