"""Prefix-cached paged serving acceptance tests (DESIGN.md §10-§11):

- ref-counted allocator: share/retain/release lifecycle, conservation
  under random admit/grow/share/publish/finish/evict sequences
  (property test), shared blocks survive owner eviction,
  ``can_allocate_new`` has no probe-seq-id collision
- RadixPrefixCache: insert/match/pin/leaf-LRU-evict semantics
- prefix-aware prefill attention: Pallas-interpret kernel vs the
  gather oracle, and both suffix paths vs a *full* prefill — greedy
  tokens identical, logits equal to f32 rounding
- engine: prefix cache on/off produces identical token streams
  (including partial-tail copy-on-write matches), hits reserve
  suffix-only blocks (strictly higher concurrency at equal Θ), a warmed
  engine serves hit + miss waves with zero mid-serve compiles
- PagedMemoryModel: prefix_sharing charges each distinct template once
  and shared heads once at LCP granularity

COW-specific property tests and cross-app radix sharing live in
tests/test_radix_cow.py.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from repro.testing import given, settings
    from repro.testing import strategies as st

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import model as M
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.paged_cache import (BlockAllocator, NULL_SEQ,
                                       RadixPrefixCache, make_paged_memory)
from repro.workload.apps import make_dataset, make_shared_prefix_dataset

CFG = get_config("smollm-135m").reduced()
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, KEY)


# ---------------------------------------------------------------------------
# allocator: ref-counted sharing
# ---------------------------------------------------------------------------

def test_share_and_release_lifecycle():
    a = BlockAllocator(num_blocks=8, block_tokens=4)
    owner = a.allocate(1, 8)                    # 2 blocks, refcount 1 each
    cache_blocks = list(owner)
    a.retain(cache_blocks)                      # the prefix cache's ref
    a.share(2, cache_blocks)                    # a sharing request
    assert a.refcount[owner[0]] == 3
    a.free_seq(1)                               # owner eviction
    assert a.used_blocks == 2, "shared blocks survive owner eviction"
    a.free_seq(2)
    assert a.used_blocks == 2, "cache ref still holds the pages"
    a.release(cache_blocks)
    assert a.used_blocks == 0 and len(a.free) == 8


def test_share_requires_live_blocks_and_empty_table():
    a = BlockAllocator(num_blocks=4, block_tokens=4)
    t = a.allocate(1, 4)
    a.allocate(2, 4)
    with pytest.raises(ValueError):
        a.share(2, t)             # table exists: prefix must come first
    a.free_seq(1)
    with pytest.raises(ValueError):
        a.retain(t)               # t's block is free now
    with pytest.raises(ValueError):
        a.release(t)              # double free


def test_can_allocate_new_no_probe_collision():
    """The old probe used seq_id -2; a live seq -2 made the answer wrong.
    ``can_allocate_new`` asks about a *fresh* table unconditionally."""
    a = BlockAllocator(num_blocks=4, block_tokens=16)
    a.allocate(-2, 33)            # 3 blocks held by a (hostile) live seq
    assert a.can_allocate(-2, 64)          # seq -2 itself could grow to 4
    assert not a.can_allocate_new(32)      # but a NEW request needs 2 > 1
    assert a.can_allocate_new(16)


@given(st.lists(st.tuples(st.integers(0, 4), st.integers(1, 9),
                          st.integers(1, 120)),
                min_size=1, max_size=60))
@settings(max_examples=100, deadline=None)
def test_allocator_refcount_invariants(ops):
    """Random admit/grow/radix-publish/share/finish/evict: free +
    unique-live == num_blocks, refcounts == holder counts (tables +
    radix nodes), never negative, no double-free, shared blocks survive
    owner eviction."""
    a = BlockAllocator(num_blocks=32, block_tokens=4)
    cache = RadixPrefixCache(a)
    for op, seq, tokens in ops:
        if op == 0:                       # admit / grow
            if a.can_allocate(seq, tokens):
                a.allocate(seq, tokens)
        elif op == 1:                     # finish / evict
            a.free_seq(seq)
        elif op == 2:                     # publish seq's leading span
            table = a.tables.get(seq, [])
            span = min(len(table) * a.block_tokens, tokens)
            if span:
                # deterministic per-seq content stand-in: same seq
                # re-publishes the same chain (idempotent inserts)
                ids = [seq * 1000 + i for i in range(span)]
                cache.insert(ids, table)
        elif op == 3:                     # share a matched prefix
            ids = [seq * 1000 + i for i in range(tokens)]
            m = cache.match(ids, peek=True)
            new_seq = 100 + seq
            full = m.tokens // a.block_tokens
            if full and not a.tables.get(new_seq) \
                    and a.can_allocate_new(tokens):
                a.share(new_seq, m.blocks[:full])
                a.allocate(new_seq, full * a.block_tokens + tokens)
        else:                             # cache pressure: evict LRU
            cache.evict_until(min(tokens, 8))
        # ---- invariants, after every op ----
        holders: dict = {}
        for t in a.tables.values():
            for b in t:
                holders[b] = holders.get(b, 0) + 1
        for node in cache.nodes():
            holders[node.block] = holders.get(node.block, 0) + 1
        assert holders == a.refcount, "refcount != holder count"
        assert all(n > 0 for n in a.refcount.values())
        assert set(a.free).isdisjoint(a.refcount)
        assert len(a.free) + len(a.refcount) == a.num_blocks
    # teardown: everything releasable, pool fully reclaimed
    for seq in list(a.tables):
        a.free_seq(seq)
    cache.evict_until(10 ** 9)
    assert len(a.free) == a.num_blocks and not a.refcount


# ---------------------------------------------------------------------------
# RadixPrefixCache
# ---------------------------------------------------------------------------

def test_radix_insert_match_pin_lru():
    a = BlockAllocator(num_blocks=16, block_tokens=4)
    cache = RadixPrefixCache(a)
    ids1 = list(range(10, 18))                    # 2 full blocks
    ids2 = list(range(20, 28))
    t1 = list(a.allocate(1, 8))
    t2 = list(a.allocate(2, 8))
    assert cache.insert(ids1, t1) == 2
    assert cache.insert(ids2, t2) == 2
    assert cache.insert(ids1, t1) == 0            # idempotent
    a.free_seq(1)
    a.free_seq(2)
    assert a.used_blocks == 4                     # cache refs keep pages
    m1 = cache.match(ids1)                        # bumps chain 1's LRU
    assert m1.tokens == 8 and m1.blocks == t1
    assert cache.hits == 1 and cache.misses == 0
    assert cache.match([99] * 8).node is None
    assert cache.misses == 1
    cache.pin(m1.node)
    assert cache.evict_until(14)                  # must evict chain 2
    assert cache.match(ids2, peek=True).tokens == 0
    assert cache.match(ids1, peek=True).tokens == 8
    assert not cache.evict_until(16), "pinned path is not evictable"
    cache.unpin(m1.node)
    assert cache.evict_until(16)
    assert a.used_blocks == 0


def test_radix_partial_and_cross_chain_match():
    """Block-boundary publishing: every node on a chain is a valid match
    endpoint, mid-block divergence matches the longest common prefix
    into full blocks and partial leaves alike."""
    a = BlockAllocator(num_blocks=16, block_tokens=4)
    cache = RadixPrefixCache(a)
    ids = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]        # 2 full + 2-token tail
    t = list(a.allocate(1, 10))
    assert cache.insert(ids, t) == 3              # 2 full nodes + partial
    exact = cache.match(ids)
    assert exact.tokens == 10 and exact.blocks == t
    assert cache.match([1, 2, 3, 4]).tokens == 4, "interior node matches"
    head = cache.match([1, 2, 3, 4, 5, 99, 0, 0])
    assert head.tokens == 5, "LCP into a full block is shareable"
    assert head.blocks == t[:2]
    tail = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 99])
    assert tail.tokens == 9 and tail.blocks == t  # LCP into partial leaf
    # partial tails always end mid-block: the sharer must copy-on-write
    assert tail.tokens % a.block_tokens != 0
    assert tail.full_blocks(a.block_tokens) == 2


# ---------------------------------------------------------------------------
# prefix-aware prefill attention: kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bt,hq,hkv,d,s,plens,slens",
                         [(8, 4, 2, 32, 16, (16, 8, 0), (16, 5, 12)),
                          (16, 4, 4, 64, 24, (32, 16, 16), (24, 24, 1)),
                          (8, 8, 1, 32, 8, (24, 0), (8, 3))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_prefill_kernel_matches_oracle(bt, hq, hkv, d, s, plens,
                                              slens, dtype):
    from repro.kernels.decode_attention.kernel import (
        paged_prefix_prefill_attention_kernel)
    from repro.kernels.decode_attention.ref import (
        paged_prefix_prefill_attention_ref)
    b = len(plens)
    mb = max(max(-(-p // bt) for p in plens), 1)
    nb = b * mb + 1
    q = jax.random.normal(KEY, (b, s, hq, d), dtype)
    ks = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d), dtype)
    vs = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(KEY, 3), (nb, hkv, bt, d), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 4), (nb, hkv, bt, d), dtype)
    tables = np.zeros((b, mb), np.int32)
    nxt = 1
    for i, p in enumerate(plens):
        for j in range(-(-p // bt)):
            tables[i, j] = nxt
            nxt += 1
    out = paged_prefix_prefill_attention_kernel(
        q, ks, vs, kp, vp, jnp.asarray(tables),
        jnp.asarray(plens, jnp.int32), jnp.asarray(slens, jnp.int32),
        interpret=True)
    ref = paged_prefix_prefill_attention_ref(
        q, ks, vs, kp, vp, jnp.asarray(tables),
        jnp.asarray(plens, jnp.int32), jnp.asarray(slens, jnp.int32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    for i, sn in enumerate(slens):      # rows past suffix_len are garbage
        err = jnp.max(jnp.abs(out[i, :sn].astype(jnp.float32)
                              - ref[i, :sn].astype(jnp.float32)))
        assert float(err) < tol, (i, float(err))


def test_prefix_prefill_kernel_masks_foreign_pages():
    """Poisoning blocks outside a request's table, its own positions past
    prefix_len, and suffix positions past suffix_len must not change its
    output — the isolation property shared pages depend on."""
    from repro.kernels.decode_attention.kernel import (
        paged_prefix_prefill_attention_kernel)
    bt, hq, hkv, d, s = 8, 4, 2, 32, 8
    plens, slens = (12, 20), (8, 5)
    b, mb, nb = 2, 3, 7
    q = jax.random.normal(KEY, (b, s, hq, d))
    ks = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    vs = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 3), (nb, hkv, bt, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 4), (nb, hkv, bt, d))
    tables = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    args = (jnp.asarray(plens, jnp.int32), jnp.asarray(slens, jnp.int32))
    out1 = paged_prefix_prefill_attention_kernel(q, ks, vs, kp, vp, tables,
                                                 *args, interpret=True)
    # poison: null block 0, request 0's tail (12 % 8 = 4 into block 2),
    # and request 1's pages as seen from request 0
    kp2 = kp.at[0].set(1e4).at[2, :, 4:].set(-1e4).at[3].set(1e4)
    vp2 = vp.at[0].set(1e4).at[2, :, 4:].set(-1e4).at[3].set(1e4)
    out2 = paged_prefix_prefill_attention_kernel(q, ks, vs, kp2, vp2, tables,
                                                 *args, interpret=True)
    assert jnp.allclose(out1[0], out2[0], atol=1e-5)


# ---------------------------------------------------------------------------
# suffix prefill vs full prefill (model level)
# ---------------------------------------------------------------------------

def _suffix_vs_full(params, use_kernel: bool):
    """Prefill request B's suffix against pages published from request
    A's full prefill; compare with B's own full prefill."""
    bt, num_blocks, max_blocks = 8, 32, 8
    rng = np.random.default_rng(0)
    instr = rng.integers(3, CFG.vocab_size, size=16).tolist()  # 2 blocks
    ids_a = instr + rng.integers(3, CFG.vocab_size, size=11).tolist()
    ids_b = instr + rng.integers(3, CFG.vocab_size, size=7).tolist()

    def pad(ids, to):
        out = np.zeros((1, to), np.int64)
        out[0, :len(ids)] = ids
        return out

    pages = M.init_paged_cache(CFG, num_blocks, bt, dtype=jnp.float32)
    _, cache_a = M.prefill(
        params, CFG, {"tokens": jnp.asarray(pad(ids_a, 32)),
                      "lengths": jnp.asarray([len(ids_a)], np.int32)},
        act_dtype=jnp.float32)
    table_a = list(range(1, 1 + -(-len(ids_a) // bt)))
    pages = M.write_prefill_pages_batched(pages, cache_a["kv"], [table_a],
                                          null_block=0, pad_to=max_blocks)
    logits_full, _ = M.prefill(
        params, CFG, {"tokens": jnp.asarray(pad(ids_b, 32)),
                      "lengths": jnp.asarray([len(ids_b)], np.int32)},
        act_dtype=jnp.float32)
    suffix = ids_b[16:]
    rows = np.zeros((1, max_blocks), np.int32)
    rows[0, :4] = table_a[:2] + [10, 11]     # shared prefix + private
    batch = {"tokens": jnp.asarray(pad(suffix, 16)),
             "lengths": jnp.asarray([len(suffix)], np.int32),
             "prefix_lens": jnp.asarray([16], np.int32),
             "block_tables": jnp.asarray(rows)}
    if use_kernel:
        from repro.kernels.decode_attention import ops
        from repro.kernels.decode_attention.kernel import (
            paged_prefix_prefill_attention_kernel)
        orig = ops.paged_prefix_prefill_attention_impl
        ops.paged_prefix_prefill_attention_impl = (
            lambda *a, **k: paged_prefix_prefill_attention_kernel(
                *a, interpret=True))
        try:
            from repro.models import transformer as T
            logits_sfx, _ = T.prefill_suffix(
                params, CFG, pages, batch["tokens"], batch["lengths"],
                batch["prefix_lens"], batch["block_tables"],
                act_dtype=jnp.float32)
        finally:
            ops.paged_prefix_prefill_attention_impl = orig
    else:
        logits_sfx, _ = M.prefill_suffix(params, CFG, pages, batch,
                                         act_dtype=jnp.float32)
    return logits_full, logits_sfx


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["dense-oracle", "pallas-interpret"])
def test_suffix_prefill_matches_full_prefill(params, use_kernel):
    """The §10 correctness claim, both backends: prefilling only the
    user-input suffix against published prefix pages reproduces the full
    prefill — greedy next token identical (the serving invariant), logits
    equal to f32 rounding."""
    logits_full, logits_sfx = _suffix_vs_full(params, use_kernel)
    v = CFG.vocab_size
    assert int(jnp.argmax(logits_full[0, :v])) == \
        int(jnp.argmax(logits_sfx[0, :v]))
    err = float(jnp.max(jnp.abs(logits_full - logits_sfx)))
    assert err < 1e-4, err


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _shared_reqs(n, seed=0, gen=6):
    # 14 instruction words + BOS = 15 tokens: ends mid-block at
    # block_tokens=4, so hits share a partial tail (copy-on-write)
    reqs = make_shared_prefix_dataset(n, n_apps=2, instr_words=14,
                                      input_words=5, gen_length=gen,
                                      seed=seed)
    for i, r in enumerate(reqs):
        r.gen_length = 2 + (i * 3) % gen
        r.predicted_gen_length = r.gen_length
    return reqs


def test_engine_prefix_cache_token_streams_identical(params):
    """Cache on vs off: identical greedy token streams (suffix prefill
    changes where prompt KV comes from, never what is generated), with
    real hits on the cached templates.  The 16-token instructions end
    mid-block at block_tokens=4, so the hits exercise the partial-tail
    copy-on-write path too."""
    out = {}
    for pc in (False, True):
        eng = PagedContinuousEngine(CFG, params=params, max_concurrency=3,
                                    num_blocks=64, block_tokens=4,
                                    max_len=64, max_gen=8, prefix_cache=pc)
        reqs = _shared_reqs(6, seed=3)
        stats = drive_paged(eng, reqs)
        assert stats["served"] == len(reqs)
        out[pc] = [eng.generated[r.req_id] for r in reqs]
        if pc:
            assert eng.prefix_cache.hits >= 2, "templates never re-used"
            assert eng.cow_copies >= 1, "partial tails never cloned"
            cached = {n.block for n in eng.prefix_cache.nodes()}
            assert len(cached) == eng.prefix_cache.num_nodes, \
                "each radix node owns a distinct physical block"
            assert eng.allocator.used_blocks == 1 + len(cached)
        else:
            assert eng.cow_copies == 0
            assert eng.allocator.used_blocks == 1
    assert out[True] == out[False]


def test_engine_admits_more_at_equal_theta_on_hits(params):
    """A published prefix makes hits reserve suffix + gen blocks only:
    strictly higher admitted concurrency than the no-cache engine at the
    same physical pool size."""
    reqs = make_shared_prefix_dataset(6, n_apps=1, instr_words=31,
                                      input_words=4, gen_length=4, seed=0)
    warm = make_shared_prefix_dataset(1, n_apps=1, instr_words=31,
                                      input_words=4, gen_length=2, seed=0)
    admitted = {}
    for pc in (False, True):
        eng = PagedContinuousEngine(CFG, params=params, max_concurrency=8,
                                    num_blocks=25, block_tokens=8,
                                    max_len=64, max_gen=8, prefix_cache=pc)
        assert eng.join_many(warm) == 1          # publishes on the pc side
        while eng.num_active:
            eng.step_window()
        admitted[pc] = eng.join_many(list(reqs))
    # prompt 36 tokens + gen 4 -> 5 blocks/request without sharing, but
    # only 1 new block on a hit (32 prefix tokens cached)
    assert admitted[True] > admitted[False], admitted
    assert admitted[True] == len(reqs)


def test_engine_shared_pages_survive_owner_eviction(params):
    """Evicting the request that published a prefix must not free the
    shared pages other live requests are reading."""
    reqs = make_shared_prefix_dataset(2, n_apps=1, instr_words=15,
                                      input_words=4, gen_length=8, seed=1)
    eng = PagedContinuousEngine(CFG, params=params, max_concurrency=2,
                                num_blocks=32, block_tokens=4,
                                max_len=64, max_gen=8, prefix_cache=True)
    eng.join(reqs[0])                     # publishes 4 full prefix blocks
    eng.join(reqs[1])                     # instruction hit: shares them
    share_ids = eng._shareable_ids(reqs[0], eng._prompt_ids(reqs[0]))
    m = eng.prefix_cache.match(share_ids, peek=True)
    # §12 publishes the whole prompt span: req 0's own span matches its 4
    # full instruction blocks PLUS its private input's partial leaf; the
    # sharer (different input) holds references on the full blocks only
    blocks = list(m.blocks[:m.full_blocks(eng.bt)])
    assert len(blocks) == 4
    assert all(eng.allocator.refcount[b] == 3 for b in blocks)
    eng._evict(0)                         # owner evicted
    assert all(eng.allocator.refcount[b] == 2 for b in blocks), \
        "owner eviction must not strip the sharer's pages"
    done = 0
    while eng.num_active:
        finished, _, _ = eng.step_window()
        done += len(finished)
    assert done == 1
    assert all(eng.allocator.refcount[b] == 1 for b in blocks), \
        "cache keeps its reference after all sharers finish"


def test_warmed_prefix_engine_zero_midserve_compiles(params):
    """The §10 recompile guarantee: a warmed engine serves miss waves
    (full prefill + publish) and hit waves (suffix prefill) with zero
    mid-serve XLA compiles."""
    from repro.testing import count_compiles
    eng = PagedContinuousEngine(CFG, params=params, max_concurrency=4,
                                num_blocks=96, block_tokens=4,
                                max_len=64, max_gen=8, warmup=True,
                                prefix_cache=True)
    # first serve compiles the remaining eager update paths, once
    stats = drive_paged(eng, _shared_reqs(6, seed=5))
    assert stats["served"] == 6
    with count_compiles() as c:
        stats = drive_paged(eng, _shared_reqs(6, seed=7))
    assert stats["served"] == 6
    assert eng.prefix_cache.hits > 0, "second serve must exercise hits"
    assert c["n"] == 0, f"{c['n']} XLA compiles during a warmed serve"


# ---------------------------------------------------------------------------
# batcher accounting
# ---------------------------------------------------------------------------

def test_paged_memory_prefix_sharing_charges_template_once():
    import dataclasses

    from repro.core.types import Batch
    cfg = get_config("chatglm-6b")
    paged = make_paged_memory(cfg, hbm_bytes=32 * 2 ** 30, dtype_bytes=4)
    shared = dataclasses.replace(paged, prefix_sharing=True)
    reqs = make_shared_prefix_dataset(8, n_apps=1, instr_words=63,
                                      input_words=8, gen_length=16)
    batch = Batch(requests=reqs)
    base_bytes = paged.mem_of(batch)
    shared_bytes = shared.mem_of(batch)
    assert shared_bytes < base_bytes
    # 8 requests x 64-token template -> 7 copies saved (rounded to blocks)
    saved = 7 * paged.request_bytes(64)
    assert base_bytes - shared_bytes == saved
    # distinct templates share nothing
    mixed = Batch(requests=make_shared_prefix_dataset(
        4, n_apps=4, instr_words=63, input_words=8, gen_length=16))
    assert shared.mem_of(mixed) == paged.mem_of(mixed)


def test_null_seq_constant_shared():
    from repro.serving.engine import PagedContinuousEngine as E
    assert E._NULL_SEQ == NULL_SEQ


def test_magnus_paged_prefix_sharing_wires_one_cache():
    from repro.core.magnus import MagnusConfig, MagnusService
    from repro.core.wma import MemoryModel
    cfg = get_config("chatglm-6b")
    base = MemoryModel(cfg, hbm_bytes=32 * 2 ** 30, dtype_bytes=4)
    svc = MagnusService(base, MagnusConfig(strategy="magnus-paged",
                                           prefix_sharing=True))
    assert svc.memory.prefix_sharing
    assert svc.prefix_cache is not None
    assert svc.prefix_cache.allocator is svc.allocator
    off = MagnusService(base, MagnusConfig(strategy="magnus-paged"))
    assert off.prefix_cache is None and not off.memory.prefix_sharing
