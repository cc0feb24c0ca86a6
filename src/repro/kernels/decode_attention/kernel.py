"""Pallas TPU decode attention (flash-decode): one query token per request
against a long KV cache, tiled over KV blocks with online-softmax partial
merges in VMEM scratch.

Grid: (B, Hkv, num_k_blocks) — K innermost so the f32 accumulators persist.
All G grouped query heads of one KV head are processed together as a
[G, D] x [D, block_k] MXU matmul.  Per-request ``lengths`` mask invalid
(padded / not-yet-written) cache slots; KV blocks entirely beyond a
request's length are skipped with ``pl.when`` — on real hardware those HBM
reads are exactly the WMA the Magnus batcher minimizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_k: int, scale: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [G, D]
        k = k_ref[0].astype(jnp.float32)                    # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * alpha + p.sum(axis=1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _kernel_i8(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               acc_ref, m_ref, l_ref, *, block_k: int, scale: float):
    """int8-cache variant: K/V arrive as int8 + per-(token,head) scales;
    dequantization happens in VMEM right before the MXU pass, so HBM
    traffic is halved vs bf16 (the kernel-level form of the §Perf
    cache_int8 lever)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [G, D]
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * alpha + p.sum(axis=1)
        m_ref[:, 0] = m_new
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][:, None]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _paged_kernel(steps_ref, sched_ref, len_ref, q_ref, *refs,
                  pages_per_step: int, max_blocks: int, scale: float):
    """One grid step per live chunk of one row (see
    :func:`paged_decode_attention_kernel`).  ``refs``: the chunk's
    ``pages_per_step`` K pages, as many V pages, the output, then the f32
    accumulator, running max and running sum of every query head."""
    pp = pages_per_step
    k_refs, v_refs = refs[:pp], refs[pp:2 * pp]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pp:]
    hkv, bt, d = k_refs[0].shape
    g = q_ref.shape[1]
    at = pl.program_id(0) * (pp + 2)
    row, c = sched_ref[at + pp], sched_ref[at + pp + 1]
    limit = jnp.minimum(len_ref[row], max_blocks * bt)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a chunk's slots past the row's last live page hold a page copied for
    # an earlier step: masked out of the scores and zeroed in V
    first = c * pp * bt
    valid = first + jax.lax.broadcasted_iota(jnp.int32, (g, pp * bt), 1) \
        < limit
    v_ok = first + jax.lax.broadcasted_iota(jnp.int32, (pp * bt, d), 0) \
        < limit
    cdt = jnp.promote_types(q_ref.dtype, k_refs[0].dtype)
    for h in range(hkv):
        k = jnp.concatenate([r[h] for r in k_refs], axis=0).astype(cdt)
        s = jax.lax.dot_general(q_ref[h].astype(cdt), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, NEG_INF)              # [G, P*bt]
        m_prev, l_prev = m_ref[h], l_ref[h]                   # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        m_ref[h] = m_new
        v = jnp.concatenate([r[h] for r in v_refs], axis=0)
        v = jnp.where(v_ok, v.astype(jnp.float32), 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv

    @pl.when(first + pp * bt >= limit)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _prefix_prefill_kernel(tables_ref, plen_ref, slen_ref, q_ref, ks_ref,
                           vs_ref, kp_ref, vp_ref, o_ref, acc_ref, m_ref,
                           l_ref, *, block_tokens: int, g: int, scale: float):
    """Prefix-aware suffix-prefill attention: grid (B, Hkv, MB + 1).

    Steps ``ji < MB`` stream the request's cached *prefix* pages, gathered
    physically through the scalar-prefetched ``tables_ref`` exactly like
    the paged decode kernel; the final step processes the new *suffix*
    K/V.  All suffix queries of one (batch, kv-head) pair ride together
    as a ``[S*G, D]`` MXU tile with online-softmax accumulators in VMEM —
    every prefix position is valid for every suffix query (strictly
    earlier in the timeline), causality only bites within the suffix."""
    bi = pl.program_id(0)
    ji = pl.program_id(2)
    nj = pl.num_programs(2)
    mb = nj - 1                       # prefix steps; last step = suffix

    @pl.when(ji == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    plen = plen_ref[bi]
    slen = slen_ref[bi]
    k_start = ji * block_tokens

    def _update(s):
        m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_prev * alpha + p.sum(axis=1)
        m_ref[:, 0] = m_new
        return p, alpha

    @pl.when((ji < mb) & (k_start < plen))
    def _prefix_block():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # [S*G, D]
        k = kp_ref[0, 0].astype(jnp.float32)                # [bt, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < plen, s, NEG_INF)
        p, alpha = _update(s)
        pv = jax.lax.dot_general(p, vp_ref[0, 0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ji == mb)
    def _suffix_block():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # [S*G, D]
        k = ks_ref[0, 0].astype(jnp.float32)                # [S, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        k_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((k_idx <= q_idx) & (k_idx < slen), s, NEG_INF)
        p, alpha = _update(s)
        pv = jax.lax.dot_general(p, vs_ref[0, 0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(ji == nj - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_prefix_prefill_attention_kernel(
        q: jax.Array, k_suf: jax.Array, v_suf: jax.Array,
        k_pages: jax.Array, v_pages: jax.Array, block_tables: jax.Array,
        prefix_lens: jax.Array, suffix_lens: jax.Array, *,
        interpret: bool = False) -> jax.Array:
    """q, k_suf, v_suf: [B, S, H*, D] suffix tensors (rope'd at absolute
    positions); pages: [num_blocks, Hkv, block_tokens, D];
    block_tables: [B, MB] physical ids of each request's prefix pages
    (pad entries must be valid ids — masked but still indexed);
    prefix_lens/suffix_lens: [B] -> [B, S, Hq, D]."""
    b, s, hq, d = q.shape
    _, hkv, bt, _ = k_pages.shape
    mb = block_tables.shape[1]
    g = hq // hkv

    qt = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, s * g, d)
    kt = k_suf.transpose(0, 2, 1, 3)                        # [B, Hkv, S, D]
    vt = v_suf.transpose(0, 2, 1, 3)
    grid = (b, hkv, mb + 1)

    # Variable-prefix DMA clamp (DESIGN.md §12): grid steps past a row's
    # own prefix (``ji * bt >= prefix_lens[bi]`` — every step for a miss
    # row with prefix_len 0) are compute-masked by ``pl.when``, but their
    # BlockSpecs would still stream whatever page the pad table entry
    # names.  Clamping the gather index to the row's LAST valid prefix
    # block makes all dead steps re-reference one already-resident page
    # (revisited blocks are not re-DMA'd), so a mixed admission wave pays
    # prefix bandwidth proportional to each row's ACTUAL cached prefix,
    # not to the padded table width.
    def _page_index(ji, tables, pl_, bi):
        last = jnp.maximum((pl_[bi] + bt - 1) // bt - 1, 0)
        return tables[bi, jnp.minimum(jnp.minimum(ji, last),
                                      tables.shape[1] - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, s * g, d),
                         lambda bi, hi, ji, tables, pl_, sl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda bi, hi, ji, tables, pl_, sl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda bi, hi, ji, tables, pl_, sl: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bt, d),
                         lambda bi, hi, ji, tables, pl_, sl:
                         (_page_index(ji, tables, pl_, bi), hi, 0, 0)),
            pl.BlockSpec((1, 1, bt, d),
                         lambda bi, hi, ji, tables, pl_, sl:
                         (_page_index(ji, tables, pl_, bi), hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s * g, d),
                               lambda bi, hi, ji, tables, pl_, sl:
                               (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s * g, d), jnp.float32),
            pltpu.VMEM((s * g, 1), jnp.float32),
            pltpu.VMEM((s * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefix_prefill_kernel, block_tokens=bt, g=g,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, s * g, d), q.dtype),
        interpret=interpret,
        name="paged_prefix_prefill_attention",
    )(block_tables.astype(jnp.int32), prefix_lens.astype(jnp.int32),
      suffix_lens.astype(jnp.int32), qt, kt, vt, k_pages, v_pages)
    return out.reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, hq, d)


def _schedule_kernel(len_ref, tables_ref, steps_ref, sched_ref, held_ref, *,
                     bt: int, pp: int, max_blocks: int):
    """Scalar-core walk of every row's live chunks, rows in order: for each
    step, the block id in each of its ``pp`` page slots, then its row and
    its chunk of that row.  A slot whose page of the step lies past its
    row's last live page keeps the block it holds (``held_ref``), so the
    pipeline, which copies a slot's block only when its id changes, copies
    no page past a length."""
    for i in range(pp):
        held_ref[i] = tables_ref[0]

    def row(r, w):
        live = jnp.clip((len_ref[r] + bt - 1) // bt, 1, max_blocks)

        def chunk(c, w):
            at = w * (pp + 2)
            for i in range(pp):
                @pl.when(c * pp + i < live)
                def _():
                    held_ref[i] = tables_ref[r * max_blocks + c * pp + i]
                sched_ref[at + i] = held_ref[i]
            sched_ref[at + pp] = r
            sched_ref[at + pp + 1] = c
            return w + 1

        return jax.lax.fori_loop(0, (live + pp - 1) // pp, chunk, w)

    steps_ref[0] = jax.lax.fori_loop(0, len_ref.shape[0], row, 0)


def _decode_schedule(block_tables: jax.Array, lengths: jax.Array, bt: int,
                     pp: int, interpret: bool):
    """``(steps, sched)`` of the paged decode kernel (see
    :func:`_schedule_kernel`); entries of ``sched`` past ``steps`` steps
    are not written."""
    b, mb = block_tables.shape
    steps_max = b * -(-mb // pp)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_schedule_kernel, bt=bt, pp=pp, max_blocks=mb),
        in_specs=[smem, smem],
        out_specs=[smem, smem],
        out_shape=[jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((steps_max * (pp + 2),), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((pp,), jnp.int32)],
        interpret=interpret,
        name="paged_decode_schedule",
    )(lengths, block_tables.reshape(-1))


#: VMEM one step's K pages may take in the paged decode kernel; V takes as
#: much again, and the pipeline double-buffers both
DECODE_CHUNK_BYTES = 64 * 1024


def decode_pages_per_step(page_bytes: int, max_blocks: int) -> int:
    """Pages of one row the paged decode kernel copies and computes per
    step: the largest power of two whose K pages (pages x ``page_bytes``,
    one page being ``Hkv x block_tokens x D x itemsize``) fit
    :data:`DECODE_CHUNK_BYTES`, at most ``max_blocks`` and at least 1."""
    p = 1
    while 2 * p * page_bytes <= DECODE_CHUNK_BYTES:
        p *= 2
    return max(1, min(p, max_blocks))


def paged_decode_attention_kernel(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; pages: [num_blocks, Hkv, block_tokens, D];
    block_tables: [B, max_blocks] physical block ids (pad entries must be
    valid ids; they are never copied); lengths: [B] -> [B, Hq, D].

    The grid walks each row's live pages, ``ceil(length / block_tokens)``
    of them, in chunks of ``P`` = :func:`decode_pages_per_step` pages, one
    grid step a chunk, rows one after another; a row with a single live
    page (an idle slot) costs one step.  Each of a step's ``P`` page slots
    is a BlockSpec whose block is one page's ``[Hkv, block_tokens, D]``
    slab, all KV heads in one DMA, gathered through the scalar-prefetched
    schedule (:func:`_decode_schedule`); the pipeline double-buffers them,
    so the next step's pages are in flight while this step computes.
    Pages past a row's length are never copied: a slot with no live page
    in a step keeps its block, which the pipeline does not copy again.
    Every query head is computed against the step's ``P x block_tokens``
    tokens with an f32 online softmax; positions past the length inside
    the last page are masked.  The kernel's cost follows the live pages,
    not ``B x max_blocks``.  (The pages are BlockSpec blocks, not manual
    DMAs of a ``pl.ANY`` pool: Mosaic refuses a DMA slice of a pool whose
    head dim is below the 128-lane tiling, such as D = 64.)"""
    b, hq, d = q.shape
    _, hkv, bt, _ = k_pages.shape
    mb = block_tables.shape[1]
    g = hq // hkv
    pp = decode_pages_per_step(
        hkv * bt * d * jnp.dtype(k_pages.dtype).itemsize, mb)
    lengths = lengths.astype(jnp.int32)
    steps, sched = _decode_schedule(block_tables.astype(jnp.int32), lengths,
                                    bt, pp, interpret)

    def page_spec(i):             # the block in the step's i-th slot
        return pl.BlockSpec(
            (None, hkv, bt, d),
            lambda w, n, sc, ln: (
                sc[jnp.minimum(w, n[0] - 1) * (pp + 2) + i], 0, 0, 0))

    row_spec = pl.BlockSpec(
        (None, hkv, g, d),
        lambda w, n, sc, ln: (
            sc[jnp.minimum(w, n[0] - 1) * (pp + 2) + pp], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(steps[0],),
        in_specs=[row_spec] + [page_spec(i) for i in range(pp)] * 2,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, pages_per_step=pp, max_blocks=mb,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        # a row's chunks run in order into its accumulators
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(steps, sched, lengths, q.reshape(b, hkv, g, d),
      *[k_pages] * pp, *[v_pages] * pp)
    return out.reshape(b, hq, d)


def decode_attention_int8_kernel(q: jax.Array, k_cache: jax.Array,
                                 v_cache: jax.Array, k_scale: jax.Array,
                                 v_scale: jax.Array, lengths: jax.Array, *,
                                 block_k: int = 512,
                                 interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; caches: int8 [B, S, Hkv, D]; scales: [B, S, Hkv];
    lengths: [B] -> [B, Hq, D]."""
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    block_k = min(block_k, max(s, 8))
    pad_k = (-s) % block_k
    if pad_k:
        pad4 = ((0, 0), (0, pad_k), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad4)
        v_cache = jnp.pad(v_cache, pad4)
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k), (0, 0)))
    s_p = s + pad_k

    qt = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_p, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_p, d)
    kst = k_scale.transpose(0, 2, 1).reshape(b * hkv, s_p)
    vst = v_scale.transpose(0, 2, 1).reshape(b * hkv, s_p)

    grid = (b, hkv, s_p // block_k)
    out = pl.pallas_call(
        functools.partial(_kernel_i8, block_k=block_k, scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ki: (bi,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda bi, hi, ki: (bi * hkv + hi, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bi, hi, ki: (bi * hkv + hi, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bi, hi, ki: (bi * hkv + hi, ki, 0)),
            pl.BlockSpec((1, block_k),
                         lambda bi, hi, ki: (bi * hkv + hi, ki)),
            pl.BlockSpec((1, block_k),
                         lambda bi, hi, ki: (bi * hkv + hi, ki)),
        ],
        out_specs=pl.BlockSpec((1, g, d),
                               lambda bi, hi, ki: (bi * hkv + hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention_int8",
    )(lengths.astype(jnp.int32), qt, kt, vt,
      kst.astype(jnp.float32), vst.astype(jnp.float32))
    return out.reshape(b, hkv, g, d).reshape(b, hq, d)


def decode_attention_kernel(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, lengths: jax.Array, *,
                            block_k: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; caches: [B, S, Hkv, D]; lengths: [B] -> [B, Hq, D]."""
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    block_k = min(block_k, max(s, 8))
    pad_k = (-s) % block_k
    if pad_k:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    s_p = s + pad_k

    qt = q.reshape(b, hkv, g, d).transpose(0, 1, 2, 3).reshape(b * hkv, g, d)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_p, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s_p, d)

    grid = (b, hkv, s_p // block_k)
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=d ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ki: (bi,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda bi, hi, ki: (bi * hkv + hi, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bi, hi, ki: (bi * hkv + hi, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bi, hi, ki: (bi * hkv + hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d),
                               lambda bi, hi, ki: (bi * hkv + hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, hkv, g, d).reshape(b, hq, d)
