"""Host-memory KV swap tier (DESIGN.md §15) acceptance tests.

The §15 contract, asserted under ``REPRO_SANITIZE=1`` for the whole
module (the shadow allocator tracks cross-tier residency):

- suspension is lossless: a swap-out/swap-in round trip restores pages,
  positions, and the logits row bit-exactly, and the resumed stream
  continues with ZERO re-prefilled tokens;
- pool-pressure storms suspend victims instead of destroying them, and
  every survivor matches the fault-free reference token-for-token;
- random interleavings of swap-out / swap-in / evict / COW / finish
  never corrupt KV, and at drain both memory tiers are empty;
- a suspended request whose deadline lapses sheds with the typed reason
  ``swapped_timeout``; ``swap_stall`` and ``host_pressure`` faults defer
  or squeeze the tier without breaking the §14 degradation contract.
"""
import copy
import os

import numpy as np
import pytest

from repro.analysis.sanitizer import (SWAP_HOLDER, ShadowAllocator,
                                      SharedWriteError, SwappedBlockError)
from repro.core.types import Request, ShedReason
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.faults import FaultEvent, FaultInjector
from repro.serving.paged_cache import BlockAllocator, HostSwapTier
from repro.testing import given, settings, strategies as st
from repro.workload.apps import make_shared_prefix_dataset

from conftest import tiny_engine_cfg

CFG = tiny_engine_cfg()
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


def _engine(num_blocks=24, *, n=4, swap_blocks=64, **kw):
    return PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=num_blocks, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, swap_blocks=swap_blocks, **kw)


_REQ_CACHE = {}


def _reqs(n, seed=0):
    """Distinct-instruction requests (no radix sharing => real pool
    pressure), canonical per (n, seed) so reference comparisons key on
    stable req_ids."""
    key = (n, seed)
    if key not in _REQ_CACHE:
        rs = [Request(app=f"a{i % 3}", task="t",
                      instruction=f"distinct instruction {seed} {i} words",
                      user_input=f"user input number {i} more text",
                      length=14, gen_length=3 + (i * 3) % MAX_GEN,
                      predicted_gen_length=1)
              for i in range(n)]
        _REQ_CACHE[key] = rs
    return copy.deepcopy(_REQ_CACHE[key])


_REF_CACHE = {}


def _reference_streams(n, seed=0):
    """Fault-free streams from a roomy no-pressure engine."""
    key = (n, seed)
    if key not in _REF_CACHE:
        eng = _engine(num_blocks=96, n=n, swap_blocks=0)
        stats = drive_paged(eng, _reqs(n, seed=seed))
        assert stats["served"] == n
        eng.assert_drained()
        _REF_CACHE[key] = dict(eng.generated)
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# tier unit: round trip is bit-exact, dedup counts, drain is clean
# ---------------------------------------------------------------------------

def test_tier_roundtrip_bitexact():
    alloc = BlockAllocator(num_blocks=8, block_tokens=2)
    table = alloc.allocate(0, 8)                   # 4 blocks
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((2, 1, len(table), 2, 2, 4),
                               dtype=np.float32)
    tier = HostSwapTier(16)
    fresh = tier.fresh_blocks(table)
    assert fresh == list(table)
    alloc.free_seq(0)
    tier.swap_out(7, table, fresh, vals, alloc)
    shared, slots = tier.split_resident(7)
    assert shared == [] and len(slots) == len(table)
    back = tier.read(slots)
    np.testing.assert_array_equal(back, vals)      # bit-exact, not close
    tier.drop(7, alloc)
    assert tier.empty and not tier.device_holds()


def test_tier_dedups_shared_blocks():
    """Two images over the same still-live blocks swap the pages ONCE;
    the tier's device holds certify them immutable until both drop."""
    alloc = BlockAllocator(num_blocks=8, block_tokens=2)
    table = alloc.allocate(0, 4)                   # 2 shared blocks
    alloc.share(1, list(table))
    vals = np.arange(2 * len(table) * 2 * 2,
                     dtype=np.float32).reshape(2, 1, len(table), 2, 2, 1)
    tier = HostSwapTier(16)
    alloc.free_seq(0)
    tier.swap_out("img0", table, tier.fresh_blocks(table), vals, alloc)
    assert sorted(tier.device_holds()) == sorted(table)
    used0 = tier.used_slots
    alloc.free_seq(1)
    fresh = tier.fresh_blocks(table)
    assert fresh == [], "already-resident blocks must not re-swap"
    tier.swap_out("img1", table, fresh, vals[:, :, :0], alloc)
    assert tier.used_slots == used0, "dedup: second image adds no slot"
    shared, slots = tier.split_resident("img1")
    assert shared == list(table) and slots == []
    tier.drop("img0", alloc)
    assert not tier.empty                          # img1 still pins slots
    tier.drop("img1", alloc)
    assert tier.empty and not tier.device_holds()
    assert len(alloc.free_blocks()) == alloc.num_blocks


# ---------------------------------------------------------------------------
# engine: forced suspension round trip
# ---------------------------------------------------------------------------

def test_forced_swap_roundtrip_resumes_bitexact():
    """Mid-generation suspension and auto-resume: the stream continues
    exactly where it stopped, with zero re-prefilled tokens."""
    n = 2
    eng = _engine(num_blocks=48, n=n)
    reqs = _reqs(n)
    assert eng.join_many(copy.deepcopy(reqs)) == n
    eng.step_window()                              # some real progress
    pages_before = {k: np.asarray(v) for k, v in eng.pages.items()}
    assert eng._swap_out(0)
    assert eng.num_suspended == 1 and eng.active[0] is None
    stats = drive_paged(eng, [])
    assert stats["swap_outs"] == 1 and stats["swap_ins"] == 1
    assert stats["reprefilled_swapped_tokens"] == 0
    assert stats["served"] == n and not stats["shed"]
    ref = _reference_streams(n)
    for r in reqs:
        assert eng.generated[r.req_id] == ref[r.req_id]
    eng.assert_drained()
    del pages_before


def test_swap_mid_speculation_resumes_bitexact():
    """§15 × §16: suspending a slot mid-speculation drops its draft KV
    (never swapped — it is recomputable), and resume re-prefills the
    DRAFT pool only: the target stream continues with zero re-prefilled
    tokens and stays bit-exact with the spec-off reference."""
    n = 2
    eng = _engine(num_blocks=48, n=n, spec_decode=True, draft_k=4)
    reqs = _reqs(n)
    assert eng.join_many(copy.deepcopy(reqs)) == n
    eng.step_window()                              # mid-speculation state
    live = next(s for s, a in enumerate(eng.active) if a is not None)
    assert eng._swap_out(live)
    assert eng.num_suspended == 1
    # the suspended slot's draft band is released at suspension time
    assert eng.allocator.tables.get(eng._draft_seq(live), []) == []
    stats = drive_paged(eng, [])
    assert stats["swap_outs"] == 1 and stats["swap_ins"] == 1
    assert stats["reprefilled_swapped_tokens"] == 0, \
        "the TARGET stream must never re-prefill across a suspension"
    assert stats["draft_reprefill_tokens"] > 0, \
        "resume must rebuild the draft KV from the verified stream"
    # a spec window emits up to draft_k+1 tokens, so the short request
    # can finish inside the manual step_window above — count streams,
    # not the drive's serve tally
    assert len(eng.generated) == n and not stats["shed"]
    ref = _reference_streams(n)
    for r in reqs:
        assert eng.generated[r.req_id] == ref[r.req_id]
    eng.assert_drained()


def test_swap_out_refuses_when_tier_full():
    eng = _engine(num_blocks=48, n=2, swap_blocks=1)
    assert eng.join_many(_reqs(2)) == 2
    eng.step_window()
    assert not eng._swap_out(0), \
        "a 1-slot tier cannot hold a multi-block image"
    assert eng.num_suspended == 0 and eng.active[0] is not None
    drive_paged(eng, [])
    eng.assert_drained()


# ---------------------------------------------------------------------------
# scripted storm: pressure suspends instead of destroying
# ---------------------------------------------------------------------------

def test_pool_shrink_storm_swaps_and_survives():
    """The acceptance-criteria storm: a mid-serve pool shrink under
    ×-underprediction forces live suspensions; after the restore every
    request finishes bit-exact with ZERO re-prefilled swapped tokens and
    both tiers drain."""
    n = 8
    inj = FaultInjector([
        FaultEvent(window=2, kind="pool_shrink", blocks=12),
        FaultEvent(window=9, kind="pool_restore"),
    ])
    eng = _engine(num_blocks=24, n=4, faults=inj)
    stats = drive_paged(eng, _reqs(n))
    inj.release(eng.allocator)
    assert stats["swap_outs"] > 0 and stats["swap_ins"] > 0, \
        "the storm must exercise the swap valve, not just evictions"
    assert stats["reprefilled_swapped_tokens"] == 0, \
        "preemption must never re-prefill a swapped request"
    assert stats["served"] + len(stats["shed"]) == n
    assert not stats["unserved"]
    ref = _reference_streams(n)
    for rid, toks in eng.generated.items():
        assert toks == ref[rid], f"survivor {rid} diverged from reference"
    eng.assert_drained()


def test_swap_victims_preferred_over_destruction():
    """With a working tier, the storm above destroys nothing: every
    preemption is a suspension (evictions stay zero)."""
    n = 8
    inj = FaultInjector([
        FaultEvent(window=2, kind="pool_shrink", blocks=12),
        FaultEvent(window=9, kind="pool_restore"),
    ])
    eng = _engine(num_blocks=24, n=4, faults=inj)
    stats = drive_paged(eng, _reqs(n))
    inj.release(eng.allocator)
    assert stats["swap_outs"] > 0
    assert stats["evictions"] == 0, \
        "victims must suspend (tier valve) before anything is destroyed"
    eng.assert_drained()


# ---------------------------------------------------------------------------
# typed shed: swapped_timeout
# ---------------------------------------------------------------------------

def test_suspended_deadline_sheds_swapped_timeout():
    """A suspended image whose deadline lapses while resume is stalled
    sheds with the typed reason ``swapped_timeout`` (a ShedReason
    member), counted as a deadline miss, and the tier drains."""
    n = 2
    inj = FaultInjector([
        # budget 100: every resume attempt is refused until the deadline
        FaultEvent(window=1, kind="swap_stall", ticks=100),
        FaultEvent(window=3, kind="stall", ticks=50),
    ])
    eng = _engine(num_blocks=48, n=n, faults=inj, default_ttl=8)
    assert eng.join_many(_reqs(n)) == n
    eng.step_window()                              # window 1: arms the stall
    assert eng._swap_out(0)
    misses0 = eng.deadline_misses
    stats = drive_paged(eng, [])
    assert inj.swap_stalls > 0, "resume attempts must hit the stall"
    reasons = {s.reason for s in stats["shed"]}
    assert ShedReason.SWAPPED_TIMEOUT.value in reasons
    assert eng.deadline_misses > misses0
    assert eng.num_suspended == 0 and eng.swap.empty
    eng.assert_drained()


# ---------------------------------------------------------------------------
# fault kinds: swap_stall defers resume; host_pressure squeezes the tier
# ---------------------------------------------------------------------------

def test_swap_stall_defers_resume_then_recovers():
    n = 2
    inj = FaultInjector([FaultEvent(window=0, kind="swap_stall", ticks=3)])
    eng = _engine(num_blocks=48, n=n, faults=inj)
    assert eng.join_many(_reqs(n)) == n
    eng.step_window()
    assert eng._swap_out(0)
    stats = drive_paged(eng, [])
    assert inj.swap_stalls == 3, "each refused attempt burns one tick"
    assert stats["served"] == n and stats["swap_ins"] == 1
    ref = _reference_streams(n)
    for r in _reqs(n):
        assert eng.generated[r.req_id] == ref[r.req_id]
    eng.assert_drained()


def test_host_pressure_shrinks_and_restores_tier():
    n = 4
    inj = FaultInjector([
        FaultEvent(window=1, kind="host_pressure", blocks=60),
        FaultEvent(window=6, kind="host_pressure", blocks=0),
    ])
    eng = _engine(num_blocks=48, n=n, faults=inj)
    stats = drive_paged(eng, _reqs(n))
    assert inj.host_pressure_events == 2
    assert eng.swap.capacity == 64, "restore must lift the squeeze"
    assert stats["served"] == n
    eng.assert_drained()


def test_squeezed_tier_cannot_hold_new_images():
    eng = _engine(num_blocks=48, n=2)
    eng.swap.shrink(63)
    assert not eng.swap.can_hold(2)
    eng.swap.restore()
    assert eng.swap.can_hold(2)


# ---------------------------------------------------------------------------
# sanitizer: cross-tier residency
# ---------------------------------------------------------------------------

def test_write_into_swap_held_block_raises():
    s = ShadowAllocator()
    s.on_allocate(0, [3])
    s.on_retain([3], SWAP_HOLDER)
    with pytest.raises(SwappedBlockError):
        s.check_write(0, [3])
    # subclasses SharedWriteError so existing handlers keep catching it
    with pytest.raises(SharedWriteError):
        s.check_write(0, [3])
    s.on_release([3], SWAP_HOLDER)
    s.check_write(0, [3])                          # hold gone: write is fine


def test_shadow_tracks_image_residency():
    s = ShadowAllocator()
    s.on_swap_out(42)
    assert 42 in s.swapped
    s.on_swap_in(42)
    assert not s.swapped


# ---------------------------------------------------------------------------
# property: random interleavings never corrupt KV
# ---------------------------------------------------------------------------

_PROP_BASE = None


def _prop_reqs():
    """Shared-prefix workload (radix chains + COW tails) for the
    interleaving property; cached so req_ids stay stable."""
    global _PROP_BASE
    if _PROP_BASE is None:
        rs = make_shared_prefix_dataset(6, n_apps=2, instr_words=10,
                                        input_words=4, gen_length=6, seed=3)
        for i, r in enumerate(rs):
            r.gen_length = 2 + (i * 3) % 6
            r.predicted_gen_length = r.gen_length
        _PROP_BASE = rs
    return copy.deepcopy(_PROP_BASE)


_PROP_REF = {}


def _prop_reference():
    if not _PROP_REF:
        eng = PagedContinuousEngine(
            CFG, max_concurrency=4, num_blocks=96, block_tokens=BT,
            max_len=64, max_gen=8, prefix_cache=True, swap_blocks=0)
        stats = drive_paged(eng, _prop_reqs())
        assert stats["served"] == 6
        eng.assert_drained()
        _PROP_REF.update(eng.generated)
    return _PROP_REF


@settings(max_examples=5, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3),
                          st.sampled_from(["swap", "evict", "resume",
                                           "step"])),
                min_size=3, max_size=12))
def test_random_interleavings_keep_streams_bitexact(ops):
    """Arbitrary interleavings of swap-out / swap-in / evict / COW /
    finish (COW and finishes arise from the shared-prefix workload and
    stepping): page contents stay bit-exact, nothing re-prefills after a
    suspension, and at drain both tiers are empty with the shadow
    residency registry drained."""
    reqs = _prop_reqs()
    pending = list(reqs)
    eng = PagedContinuousEngine(
        CFG, max_concurrency=4, num_blocks=96, block_tokens=BT,
        max_len=64, max_gen=8, prefix_cache=True, swap_blocks=64)

    def admit():
        while pending:
            if eng.join_many([pending[0]]) != 1:
                break
            pending.pop(0)

    admit()
    for arg, op in ops:
        if op == "swap":
            live = [i for i, a in enumerate(eng.active) if a is not None]
            if live:
                eng._swap_out(live[arg % len(live)])
        elif op == "evict":
            live = [i for i, a in enumerate(eng.active) if a is not None]
            if live:
                pending.append(eng._evict(live[arg % len(live)]))
        elif op == "resume":
            eng._resume_swapped()
        else:
            eng.step_window()
        admit()
    for _ in range(400):
        if not pending and not eng.num_active and not eng.num_suspended:
            break
        admit()
        eng.step_window()
    else:
        raise AssertionError("interleaving wedged the engine")
    assert eng.reprefilled_swapped_tokens == 0
    ref = _prop_reference()
    for r in reqs:
        assert eng.generated[r.req_id] == ref[r.req_id], \
            f"request {r.req_id} diverged after interleaved preemptions"
    assert eng.swap.empty and not eng.swap.device_holds()
    shadow = eng.allocator._shadow
    assert shadow is not None and not shadow.swapped
    eng.assert_drained()
