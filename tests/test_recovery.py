"""Kill-and-recover chaos harness: the §17 crash-safety contract.

A scripted ``crash`` fault hard-stops the engine at a seam (mid-wave,
mid-window, mid-swap, mid-publish); the crashed process's checkpoint
directory — last snapshot + write-ahead journal tail — is all that
survives.  Recovery must then:

- finish every journaled request with token streams bit-exact vs an
  uncrashed reference run;
- re-prefill ZERO target tokens for snapshot-covered requests (the
  §15 zero-re-prefill argument, applied across process death);
- drain both tiers (``assert_drained``) with the §13 shadow rebuilt
  from the snapshot agreeing with the restored allocator
  (``load_engine`` runs ``check_allocator`` unconditionally);
- self-check: streams the crashed process already journaled as
  finished re-derive identically (``journal_mismatches == 0``).

Plus round-trip units for the snapshot container (checksum), the radix
tree (refcounts, COW partial tails, LRU order), the swap tier
(by_block dedup slots), the journal (torn-tail tolerance, typed
corruption), ``ShedReason.JOURNAL_EXPIRED``, the hardened train
checkpoint restore, and the sim's ``recovery_time`` pricing mirror.
"""
import copy
import json
import os
import zlib

import numpy as np
import pytest

from repro.core.types import SHED_REASONS, Request
from repro.serving import snapshot as snaplib
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.faults import (EngineCrash, FaultEvent, FaultInjector,
                                  SEAMS)
from repro.serving.paged_cache import (BlockAllocator, HostSwapTier,
                                       RadixPrefixCache)
from repro.testing import given, settings, strategies as st
from repro.workload.apps import make_dataset

from conftest import tiny_engine_cfg

CFG = tiny_engine_cfg()
MAX_GEN = 10
BT = 4
N = 6


_REQ_CACHE = {}


def _reqs(n=N, seed=0, underpredict=False):
    """One canonical request list per (n, seed) — req_ids are minted at
    construction and the reference comparison keys on them, so every
    run deepcopies the SAME base list (the test_chaos idiom).  With
    ``underpredict`` every request predicts 1 token (the test_swap
    idiom: Algorithm-1 overcommits, so pool pressure — and hence swap
    traffic — actually materializes)."""
    key = (n, seed, underpredict)
    if key not in _REQ_CACHE:
        reqs = make_dataset(2, seed=seed)[:n]
        for i, r in enumerate(reqs):
            r.user_input = " ".join(r.user_input.split()[:6])
            r.gen_length = 3 + (i * 3) % MAX_GEN
            r.predicted_gen_length = 1 if underpredict else r.gen_length
        _REQ_CACHE[key] = reqs
    return copy.deepcopy(_REQ_CACHE[key])


def _engine(faults=None, num_blocks=48, n=4, **kw):
    return PagedContinuousEngine(
        CFG, max_concurrency=n, num_blocks=num_blocks, block_tokens=BT,
        max_len=64, max_gen=MAX_GEN, faults=faults, **kw)


_REF_CACHE = {}


def _reference_streams(seed=0, underpredict=False, **engine_kw):
    key = (seed, underpredict, tuple(sorted(engine_kw.items())))
    if key not in _REF_CACHE:
        eng = _engine(**engine_kw)
        stats = drive_paged(eng, _reqs(seed=seed, underpredict=underpredict))
        assert stats["served"] == N, stats
        eng.assert_drained()
        _REF_CACHE[key] = dict(eng.generated)
    return _REF_CACHE[key]


def _crash_and_recover(tmp_path, seam, window, *, seed=0, underpredict=False,
                       snapshot_every=2, extra_events=(), **engine_kw):
    """Run to the scripted crash, recover from the checkpoint dir, and
    assert the full §17 contract against the uncrashed reference.
    ``extra_events`` lets a test add pressure faults (e.g. pool_shrink
    to force swap traffic) to the crashed run only — the reference run
    stays fault-free, which is exactly the §15/§17 bit-exactness claim.
    Returns (recovered_engine, report) for extra per-test assertions;
    returns None if the seam was never crossed (the crash didn't fire)."""
    ref = _reference_streams(seed=seed, underpredict=underpredict,
                             **engine_kw)
    ckpt = str(tmp_path / f"ckpt-{seam}-{window}")
    inj = FaultInjector([*extra_events,
                         FaultEvent(window=window, kind="crash", seam=seam)])
    eng = _engine(faults=inj, **engine_kw)
    mgr = snaplib.RecoveryManager(ckpt, snapshot_every=snapshot_every)
    crashed = False
    try:
        stats = drive_paged(eng, _reqs(seed=seed, underpredict=underpredict),
                            recovery=mgr)
    except EngineCrash as e:
        crashed = True
        assert e.seam == seam
    mgr.close()
    if not crashed:
        # seam never crossed (e.g. no pool pressure => no swap): the
        # run must simply have completed normally and bit-exact
        inj.release(eng.allocator)
        assert stats["served"] == N
        assert dict(eng.generated) == ref
        eng.assert_drained()
        return None
    eng2, report = snaplib.recover(
        lambda: _engine(**engine_kw), ckpt, snapshot_every=snapshot_every)
    assert report["journaled"] == N
    assert report["recovered"] == N, report
    for rid, toks in ref.items():
        assert eng2.generated.get(rid) == toks, \
            f"seam={seam} w={window}: stream {rid} diverged after recovery"
    assert report["replayed_reprefill_tokens"] == 0, \
        "snapshot-covered request re-prefilled target tokens"
    assert report["journal_mismatches"] == 0
    eng2.assert_drained()
    return eng2, report


# ---------------------------------------------------------------------------
# the kill-and-recover acceptance seams
# ---------------------------------------------------------------------------

def test_crash_mid_wave(tmp_path):
    """Crash between reservation and prefill dispatch: the WAL already
    holds the admits, so recovery replays the whole wave."""
    assert _crash_and_recover(tmp_path, "wave", 0) is not None


def test_crash_mid_window_early_and_late(tmp_path):
    """Mid-window crashes before AND after the first snapshot landed:
    the early one recovers from journal-only replay, the late one from
    snapshot + journal tail with restored in-flight decode state."""
    assert _crash_and_recover(tmp_path, "window", 1) is not None
    out = _crash_and_recover(tmp_path, "window", 5)
    assert out is not None
    _, report = out
    assert report["snapshot_used"] is not None, \
        "window-5 crash with snapshot_every=2 must restore from a snapshot"
    assert report["journal_confirmed"] >= 1, \
        "some stream finished pre-crash and must re-derive bit-exact"


def test_crash_mid_publish(tmp_path):
    """Crash inside the deferred radix publish flush: queued spans are
    an optimization, not durable state — recovery (radix tree restored
    from the snapshot) still serves everything bit-exact."""
    assert _crash_and_recover(tmp_path, "publish", 1,
                              prefix_cache=True) is not None


def test_crash_mid_swap(tmp_path):
    """Crash after the tier committed to a suspension but before the
    image readback: nothing of the half-swap survives, and the restored
    swap tier's books round-trip (dedup slots included)."""
    out = _crash_and_recover(
        tmp_path, "swap", 2, seed=1, underpredict=True,
        num_blocks=24, swap_blocks=16,
        extra_events=(FaultEvent(window=2, kind="pool_shrink", blocks=12),))
    assert out is not None
    eng2, _ = out
    assert eng2.swap is not None and eng2.swap.empty


@given(seam=st.sampled_from(SEAMS), window=st.integers(0, 6))
@settings(max_examples=6, deadline=None)
def test_crash_random_seam_property(seam, window):
    """Hypothesis sweep: ANY (seam, window) either never fires (the run
    completes normally, bit-exact) or recovers bit-exact with zero
    replayed re-prefill and both tiers drained."""
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        _crash_and_recover(pathlib.Path(d), seam, window, seed=1,
                           num_blocks=20, swap_blocks=16,
                           prefix_cache=True)


def test_recovery_under_sanitizer_rebuilds_shadow(tmp_path):
    """With REPRO_SANITIZE on for the factory engine, load_engine
    rebuilds the ShadowAllocator from the snapshot; check_allocator
    (always run) cross-checks it against the restored books."""
    os.environ["REPRO_SANITIZE"] = "1"
    try:
        out = _crash_and_recover(tmp_path, "window", 5, prefix_cache=True)
        assert out is not None
        eng2, _ = out
        assert eng2.allocator._shadow is not None, \
            "sanitizing restore must carry a rebuilt shadow"
    finally:
        os.environ.pop("REPRO_SANITIZE", None)


# ---------------------------------------------------------------------------
# snapshot container round-trip units
# ---------------------------------------------------------------------------

def test_snapshot_checksum_rejects_corruption(tmp_path):
    path = str(tmp_path / "snap.npz")
    meta = {"version": 1, "who": "unit"}
    arrays = {"a": np.arange(12, dtype=np.int32).reshape(3, 4),
              "b": np.linspace(0, 1, 5, dtype=np.float32)}
    snaplib.write_snapshot(path, meta, arrays)
    m2, a2 = snaplib.read_snapshot(path)
    assert m2["who"] == "unit"
    np.testing.assert_array_equal(a2["a"], arrays["a"])
    # corrupt one stored array but keep the OLD checksum: rewriting the
    # zip (rather than flipping raw bytes) keeps the container readable
    # so the typed checksum error — not a zip error — must fire
    with np.load(path) as data:
        members = {k: data[k] for k in data.files}
    members["['a']"] = members["['a']"] + 1
    np.savez(path[:-4], **members)
    with pytest.raises(snaplib.SnapshotChecksumError):
        snaplib.read_snapshot(path)


def test_snapshot_geometry_mismatch_is_typed(tmp_path):
    """A snapshot from a different pool geometry refuses to restore."""
    path = str(tmp_path / "geo.npz")
    eng = _engine()
    eng.snapshot(path)
    other = _engine(num_blocks=32)
    with pytest.raises(snaplib.SnapshotMismatchError):
        other.restore(path)


def test_bfloat16_arrays_round_trip(tmp_path):
    import ml_dtypes
    path = str(tmp_path / "bf16.npz")
    arr = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
    snaplib.write_snapshot(path, {}, {"kv": arr})
    _, arrays = snaplib.read_snapshot(path)
    assert arrays["kv"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(arrays["kv"], arr)


# ---------------------------------------------------------------------------
# radix / swap-tier round-trip units
# ---------------------------------------------------------------------------

def _walk(cache):
    out = {}
    for node in cache.nodes():
        out[tuple(node.tokens)] = (node.block, node.pins, node.last_used,
                                   tuple(sorted(node.children)),
                                   tuple(sorted(node.partials)))
    return out


def test_radix_round_trip_preserves_structure_and_lru():
    """Serialize/deserialize keeps every node (full AND partial-tail),
    pins, per-node LRU stamps, the tree clock, and — because restore is
    structural — the allocator's refcounts are untouched."""
    alloc = BlockAllocator(32, BT)
    cache = RadixPrefixCache(alloc)
    t1 = alloc.allocate(0, 3 * BT)
    cache.insert(list(range(10)), t1)         # 2 full + 1 partial tail
    t2 = alloc.allocate(1, 2 * BT)
    cache.insert(list(range(8)), t2)          # shares the full prefix
    m = cache.match(list(range(10)))
    cache.pin(m.node)
    ref_before = dict(alloc.refcount)
    shape_before = _walk(cache)
    clock_before = cache._clock

    data, index = snaplib.snapshot_radix(cache)
    assert index[id(m.node)] >= 0
    restored = RadixPrefixCache(alloc)
    objs = snaplib.restore_radix(restored, data)
    assert _walk(restored) == shape_before
    assert restored._clock == clock_before
    assert alloc.refcount == ref_before, \
        "structural restore must not touch refcounts"
    assert sorted(restored.retained_blocks()) \
        == sorted(cache.retained_blocks())
    # the pinned path survives: the same node is pinned in the rebuild
    ridx = data["nodes"][index[id(m.node)]]
    assert objs[index[id(m.node)]].pins == m.node.pins == 1
    assert tuple(ridx["tokens"]) == tuple(m.node.tokens)
    cache.unpin(m.node)
    restored.unpin(objs[index[id(m.node)]])


def test_swap_tier_round_trip_preserves_dedup_slots():
    """Tier books (free-list order, slot_ref, by_block dedup map, FIFO
    resume order) and the used host pages round-trip exactly."""
    tier = HostSwapTier(8)
    alloc = BlockAllocator(16, BT)
    t1 = list(alloc.allocate(0, 2 * BT))
    alloc.share(1, [t1[0]])                    # seq 1 shares t1's head
    t2 = list(alloc.allocate(1, 2 * BT))
    vals = np.arange(2 * 2 * 2 * BT * 2 * 4, dtype=np.float32) \
        .reshape(2, 2, 2, BT, 2, 4)
    fresh1 = tier.fresh_blocks(t1)
    alloc.free_seq(0)
    tier.swap_out(7, t1, fresh1, vals, alloc)
    fresh2 = tier.fresh_blocks(t2)             # t1[0] already host-resident
    alloc.free_seq(1)
    tier.swap_out(9, t2, fresh2, vals[:, :, :len(fresh2)], alloc)
    assert tier.deduped_blocks >= 1

    meta, store = snaplib.snapshot_swap_tier(tier)
    clone = HostSwapTier(8)
    snaplib.restore_swap_tier(clone, meta, store)
    assert clone.free == tier.free
    assert clone.slot_ref == tier.slot_ref
    assert clone.by_block == tier.by_block
    assert list(clone.maps) == list(tier.maps)      # FIFO resume order
    assert clone.deduped_blocks == tier.deduped_blocks
    for rid in tier.maps:
        np.testing.assert_array_equal(clone.read(tier.maps[rid]),
                                      tier.read(tier.maps[rid]))
    with pytest.raises(snaplib.SnapshotMismatchError):
        snaplib.restore_swap_tier(HostSwapTier(4), meta, store)


# ---------------------------------------------------------------------------
# journal units
# ---------------------------------------------------------------------------

def test_journal_tolerates_torn_tail_only(tmp_path):
    path = str(tmp_path / "journal.wal")
    j = snaplib.AdmissionJournal(path)
    j.append("admit", rid=1)
    j.append("finish", rid=1, tokens=[5, 6])
    j.sync()
    j.close()
    with open(path, "a") as fh:
        fh.write('deadbeef {"kind": "admit", "rid"')   # torn mid-write
    records, torn = snaplib.AdmissionJournal.read(path)
    assert [r["kind"] for r in records] == ["admit", "finish"]
    assert torn == 1
    with pytest.raises(snaplib.JournalTornError):
        snaplib.AdmissionJournal.read(path, allow_torn=False)


def test_journal_midfile_corruption_is_fatal(tmp_path):
    path = str(tmp_path / "journal.wal")
    j = snaplib.AdmissionJournal(path)
    for rid in range(3):
        j.append("admit", rid=rid)
    j.close()
    lines = open(path).read().splitlines()
    payload = json.dumps({"kind": "admit", "rid": 99}, sort_keys=True)
    lines[1] = f"{zlib.crc32(b'not the payload'):08x} {payload}"
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    with pytest.raises(snaplib.JournalCorruptError):
        snaplib.AdmissionJournal.read(path)     # even with allow_torn


# ---------------------------------------------------------------------------
# JOURNAL_EXPIRED: TTLs elapse across crash downtime
# ---------------------------------------------------------------------------

def test_downtime_expires_journaled_requests(tmp_path):
    """TTL'd requests whose deadline elapsed while the process was dead
    are typed ``journal_expired`` sheds, not replays — and the reason
    is a first-class ShedReason the sim Metrics accept."""
    from repro.sim.events import Metrics

    assert "journal_expired" in SHED_REASONS
    m = Metrics()
    m.record_shed("journal_expired")
    assert m.shed_reasons["journal_expired"] == 1
    with pytest.raises(ValueError):
        m.record_shed("journal_imploded")

    ckpt = str(tmp_path / "ckpt-ttl")
    reqs = _reqs(seed=2)
    for r in reqs:
        r.ttl_steps = 40
    inj = FaultInjector([FaultEvent(window=1, kind="crash", seam="window")])
    eng = _engine(faults=inj)
    mgr = snaplib.RecoveryManager(ckpt, snapshot_every=2)
    with pytest.raises(EngineCrash):
        drive_paged(eng, copy.deepcopy(reqs), recovery=mgr)
    mgr.close()
    eng2, report = snaplib.recover(lambda: _engine(), ckpt,
                                   downtime_ticks=10_000)
    assert report["expired"] > 0
    reasons = {s.reason for s in eng2.shed_log}
    assert reasons <= {"journal_expired"}, reasons
    assert report["expired"] + len(eng2.generated) == report["journaled"]
    eng2.assert_drained()


# ---------------------------------------------------------------------------
# hardened train-checkpoint restore (shared flatten helper)
# ---------------------------------------------------------------------------

def test_checkpoint_restore_validates_template(tmp_path):
    from repro.train import checkpoint as ckpt

    tree = {"w": np.ones((2, 3), np.float32), "b": np.zeros(3, np.float32)}
    path = str(tmp_path / "model")
    ckpt.save(path, tree, step=7)
    restored, step = ckpt.restore(path, tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.restore(path, {"w": np.ones((2, 3), np.float32)})   # missing b
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.restore(path, {"w": np.ones((3, 2), np.float32),    # shape
                            "b": tree["b"]})
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.restore(path, {"w": np.ones((2, 3), np.int32),      # dtype
                            "b": tree["b"]})
    # the engine snapshot rides the same flatten convention
    assert set(ckpt.flatten_tree({"x": np.zeros(1)})) == {"['x']"}


# ---------------------------------------------------------------------------
# sim pricing mirror
# ---------------------------------------------------------------------------

def test_sim_recovery_time_pricing():
    """recovery_time = one host-link pool transfer + deterministic
    journal replay; monotone in both, and restore of a swap-sized image
    prices exactly like the §15 transfer it reuses."""
    from repro.configs import get_config
    from repro.serving.cost_model import CostModel, TPU_V5E
    from repro.sim.runner import HostSyncCost

    base = CostModel(get_config("chatglm-6b"), TPU_V5E)
    c = HostSyncCost(base, 0.01, "fused")
    assert c.recovery_time(8, 16) == c.swap_transfer_time(8, 16)
    assert c.recovery_time(8, 16, journal_records=1000) \
        > c.recovery_time(8, 16, journal_records=10) \
        > c.recovery_time(8, 16)
    assert c.recovery_time(64, 16) > c.recovery_time(8, 16)
    # replay parsing is deliberately cheap next to moving the pool
    assert c.recovery_time(64, 16, journal_records=100) \
        < 2 * c.recovery_time(64, 16)
