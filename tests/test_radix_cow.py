"""Radix-tree prefix sharing + copy-on-write acceptance tests
(DESIGN.md §11):

- property: `cow_if_not_appendable` NEVER leaves a sequence about to
  append into a block with refcount > 1 — shared blocks are cloned, the
  original keeps its other holders untouched, and pool conservation
  holds after every operation
- radix sharing: three templates sharing a 2-block head reuse exactly
  those physical blocks across apps (the cross-app LCP case the
  content-keyed exact-match cache could not serve)
- model level: suffix prefill from a *mid-block* offset against a
  copy-on-write clone reproduces the full prefill (argmax-exact), and
  the offset-aware suffix scatter never touches the copied prefix slots
- PagedMemoryModel: LCP-trie footprints charge a shared head once
  across distinct templates
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
from repro.core.types import Request
from repro.models import model as M
from repro.serving.engine import PagedContinuousEngine, drive_paged
from repro.serving.paged_cache import (BlockAllocator, RadixPrefixCache,
                                       make_paged_memory)

CFG = get_config("smollm-135m").reduced()
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, KEY)


# ---------------------------------------------------------------------------
# COW property: a writable block is never shared
# ---------------------------------------------------------------------------

def _ids(seq, n):
    """Deterministic per-seq token content (same seq -> same chain)."""
    return [seq * 1000 + i for i in range(n)]


@given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 6),
                          st.integers(1, 40)),
                min_size=1, max_size=60))
@settings(max_examples=100, deadline=None)
def test_cow_never_mutates_shared_block(ops):
    """Random publish / share-and-append / append / evict sequences:
    before any append the sequence calls ``cow_if_not_appendable`` and
    the block it then writes ALWAYS has refcount 1; when a clone
    happened, the source block kept every other holder's reference and
    was not mutated (its tree/table membership is unchanged)."""
    a = BlockAllocator(num_blocks=24, block_tokens=4)
    cache = RadixPrefixCache(a)
    for op, seq, tokens in ops:
        if op == 0:                      # admit + publish (full + partial)
            if not a.tables.get(seq) and a.can_allocate_new(8):
                t = a.allocate(seq, 8)
                cache.insert(_ids(seq, 6), t)     # 1 full node + partial
        elif op == 1:                    # share a match, then append into it
            m = cache.match(_ids(seq, 6), peek=True)
            ns = 50 + seq
            if m.node is not None and not a.tables.get(ns) \
                    and a.can_allocate_new(8):
                a.share(ns, m.blocks)
                if m.tokens % a.block_tokens:
                    idx = len(m.blocks) - 1
                    shared = a.tables[ns][idx]
                    held_before = a.refcount[shared]
                    pair = a.cow_if_not_appendable(ns, idx)
                    assert pair is not None, \
                        "a cache-resident partial tail is always shared"
                    src, dst = pair
                    assert src == shared and dst != src
                    # the original kept its other holders, untouched
                    assert a.refcount[src] == held_before - 1
                    assert any(n.block == src for n in cache.nodes())
                    # the append target is now exclusively owned (a
                    # block-aligned match appends into a fresh block
                    # instead — nothing shared is ever written)
                    assert a.refcount[a.tables[ns][idx]] == 1
                a.allocate(ns, 8)
        elif op == 2:                    # decode-append into own last block
            t = a.tables.get(seq)
            if t:
                idx = len(t) - 1
                if a.refcount[t[idx]] == 1 or a.free:
                    pair = a.cow_if_not_appendable(seq, idx)
                    assert a.refcount[t[idx]] == 1, \
                        "append target still shared after COW"
                    if pair is not None:
                        assert a.refcount.get(pair[0], 0) >= 1, \
                            "COW source lost its other holders"
        else:                            # churn: finish / cache pressure
            if a.tables.get(seq):
                a.free_seq(seq)
            cache.evict_until(min(tokens, 6))
        # conservation after every op
        assert len(a.free) + len(a.refcount) == a.num_blocks
        assert all(n > 0 for n in a.refcount.values())
    for seq in list(a.tables):
        a.free_seq(seq)
    cache.evict_until(10 ** 9)
    assert len(a.free) == a.num_blocks and not a.refcount


def test_cow_requires_free_block():
    """Cloning needs a free block: a full pool raises (callers evict
    first); one free block suffices."""
    a = BlockAllocator(num_blocks=2, block_tokens=4)
    t = a.allocate(0, 8)
    a.retain([t[1]])
    with pytest.raises(MemoryError):
        a.cow_if_not_appendable(0, 1)
    b = BlockAllocator(num_blocks=3, block_tokens=4)
    tb = b.allocate(0, 8)
    b.retain([tb[1]])
    pair = b.cow_if_not_appendable(0, 1)  # 1 free block -> clone succeeds
    assert pair is not None and b.refcount[b.tables[0][1]] == 1


# ---------------------------------------------------------------------------
# cross-app radix sharing (engine level)
# ---------------------------------------------------------------------------

_HEAD = "alpha beta gamma delta epsilon zeta eta"   # +BOS = 8 toks = 2 blocks


def _head_req(i, tail, input_words="foo bar baz"):
    instr = f"{_HEAD} {tail}"
    n_in = len(input_words.split())
    return Request(app=f"app{i}", task=f"app{i}", instruction=instr,
                   user_input=input_words,
                   length=len(instr.split()) + 1 + n_in,
                   user_input_length=n_in, gen_length=4,
                   predicted_gen_length=4)


def test_three_templates_share_exactly_the_head_blocks(params):
    """Three apps whose instructions share a 2-block head: the radix
    walk reuses exactly those two physical blocks in every table, while
    the diverging tails stay private — the cross-app case that was a
    guaranteed miss for the content-keyed exact-match cache."""
    reqs = [_head_req(0, "one two three"),
            _head_req(1, "four five six"),
            _head_req(2, "seven eight nine")]
    eng = PagedContinuousEngine(CFG, params=params, max_concurrency=4,
                                num_blocks=64, block_tokens=4,
                                max_len=64, max_gen=8, prefix_cache=True)
    slots = [eng.join(r) for r in reqs]
    assert eng.prefix_cache.hits == 2 and eng.prefix_cache.misses == 1
    tables = [eng.allocator.tables[s] for s in slots]
    head = tables[0][:2]
    assert tables[1][:2] == head and tables[2][:2] == head, \
        "the 2-block shared head must be the same physical pages"
    # 3 tables + 1 cache reference each
    assert all(eng.allocator.refcount[b] == 4 for b in head)
    # private tails are disjoint across the three requests
    tails = [set(t[2:]) for t in tables]
    assert not (tails[0] & tails[1] or tails[0] & tails[2]
                or tails[1] & tails[2])
    while eng.num_active:
        eng.step_window()
    assert all(len(g) == 4 for g in eng.generated.values())
    # after all finish, only the cache's references remain
    assert all(eng.allocator.refcount[b] == 1 for b in head)
    eng.assert_drained()   # cache-retained blocks are legitimate survivors


def test_head_only_hits_match_streams_and_save_prefill(params):
    """Shared-head workload served with and without the radix cache:
    identical token streams, strictly fewer prefill tokens with the
    cache on (the acceptance criterion PR 3's exact-match cache could
    not meet — every request here is a distinct template)."""
    reqs = [_head_req(i, tail) for i, tail in enumerate(
        ("one two three", "four five six", "seven eight nine",
         "ten eleven twelve"))]
    out, toks = {}, {}
    for pc in (False, True):
        eng = PagedContinuousEngine(CFG, params=params, max_concurrency=2,
                                    num_blocks=64, block_tokens=4,
                                    max_len=64, max_gen=8, prefix_cache=pc)
        stats = drive_paged(eng, list(reqs))
        assert stats["served"] == len(reqs)
        out[pc] = [eng.generated[r.req_id] for r in reqs]
        toks[pc] = eng.prefill_tokens
        eng.assert_drained()
        if pc:
            assert eng.prefix_cache.hits >= 2
    assert out[True] == out[False]
    assert toks[True] < toks[False], toks


# ---------------------------------------------------------------------------
# mid-block suffix prefill against a COW clone (model level)
# ---------------------------------------------------------------------------

def test_midblock_suffix_prefill_matches_full_prefill(params):
    """Request B shares 12 of request A's tokens — 1.5 blocks at
    block_tokens=8.  B clones the half-shared block (copy_pages), runs
    the suffix prefill from offset 12, and scatters its suffix KV at the
    mid-block offset.  Greedy next token must equal B's own full
    prefill; the clone's copied prefix slots must survive the scatter."""
    bt, num_blocks, max_blocks = 8, 32, 8
    rng = np.random.default_rng(0)
    shared = rng.integers(3, CFG.vocab_size, size=12).tolist()
    ids_a = shared + rng.integers(3, CFG.vocab_size, size=9).tolist()
    ids_b = shared + rng.integers(3, CFG.vocab_size, size=5).tolist()

    def pad(ids, to):
        out = np.zeros((1, to), np.int64)
        out[0, :len(ids)] = ids
        return out

    pages = M.init_paged_cache(CFG, num_blocks, bt, dtype=jnp.float32)
    _, cache_a = M.prefill(
        params, CFG, {"tokens": jnp.asarray(pad(ids_a, 32)),
                      "lengths": jnp.asarray([len(ids_a)], np.int32)},
        act_dtype=jnp.float32)
    table_a = [1, 2, 3]
    pages = M.write_prefill_pages_batched(pages, cache_a["kv"], [table_a],
                                          null_block=0, pad_to=max_blocks)
    logits_full, _ = M.prefill(
        params, CFG, {"tokens": jnp.asarray(pad(ids_b, 32)),
                      "lengths": jnp.asarray([len(ids_b)], np.int32)},
        act_dtype=jnp.float32)
    # copy-on-write: B's table shares block 1 fully, clones block 2
    clone = 10
    pages = M.copy_pages(pages, jnp.asarray([2], jnp.int32),
                         jnp.asarray([clone], jnp.int32))
    rows = np.zeros((1, max_blocks), np.int32)
    rows[0, :3] = [1, clone, 11]
    rows_j = jnp.asarray(rows)
    suffix = ids_b[12:]
    plens = jnp.asarray([12], np.int32)
    slens = jnp.asarray([len(suffix)], np.int32)
    logits_sfx, kv = M.prefill_suffix(
        params, CFG, pages,
        {"tokens": jnp.asarray(pad(suffix, 8)),
         "lengths": slens, "prefix_lens": plens,
         "block_tables": rows_j}, act_dtype=jnp.float32)
    v = CFG.vocab_size
    assert int(jnp.argmax(logits_full[0, :v])) == \
        int(jnp.argmax(logits_sfx[0, :v]))
    err = float(jnp.max(jnp.abs(logits_full - logits_sfx)))
    assert err < 1e-4, err
    # the mid-block scatter writes slots 4.. of the clone and leaves the
    # copied prefix KV (slots 0-3) bit-identical
    before = pages["k"][:, clone, :, :4]
    pages2 = M.write_suffix_pages_batched(pages, kv, rows_j, plens, slens,
                                          null_block=0)
    assert bool(jnp.all(pages2["k"][:, clone, :, :4] == before))
    assert not bool(jnp.all(pages2["k"][:, clone, :, 4:5] ==
                            pages["k"][:, clone, :, 4:5])), \
        "suffix KV must actually land in the clone's tail slots"


# ---------------------------------------------------------------------------
# LCP footprint accounting
# ---------------------------------------------------------------------------

def test_paged_memory_charges_shared_head_once():
    """Two distinct templates sharing a 2-block head: the LCP trie
    charges the head once — less than two independent chains, more than
    one fully shared chain."""
    import dataclasses
    from repro.core.types import Batch
    cfg = get_config("chatglm-6b")
    paged = make_paged_memory(cfg, hbm_bytes=32 * 2 ** 30, dtype_bytes=4)
    shared = dataclasses.replace(paged, prefix_sharing=True)
    bt = paged.block_tokens
    head = " ".join(f"h{i}" for i in range(2 * bt))        # 2 full blocks
    reqs = []
    for i, tail in enumerate(("x " * bt, "y " * bt)):
        instr = f"{head} {tail.strip()}"
        n = len(instr.split()) + 1
        reqs.append(Request(app=f"a{i}", task=f"a{i}", instruction=instr,
                            user_input="u v w", length=n + 3,
                            user_input_length=3, gen_length=16,
                            predicted_gen_length=16))
    batch = Batch(requests=reqs)
    base = paged.mem_of(batch)
    lcp = shared.mem_of(batch)
    # head (2*bt tokens, +BOS pushes the span: compute the exact saving)
    span = [shared.shared_prefix_tokens(r) for r in reqs]
    assert all(s > 0 for s in span)
    # the second chain re-charges only its tail blocks beyond the shared
    # head; with BOS the head occupies the first 2 blocks of both chains
    saved = base - lcp
    assert saved == shared.request_bytes(2 * bt), \
        (saved, shared.request_bytes(2 * bt))
