"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.kernels.ssd_scan.ref import ssd_scan_ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 5e-2 if dtype == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("s,hq,hkv,d", [(128, 4, 4, 64), (256, 4, 2, 64),
                                        (192, 6, 2, 32), (256, 8, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["causal", "window", "full"])
def test_flash_attention(s, hq, hkv, d, dtype, mode):
    b = 2
    q = jax.random.normal(KEY, (b, s, hq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d), dtype)
    kw = {"causal": mode != "full",
          "window": 64 if mode == "window" else None}
    out = flash_attention_kernel(q, k, v, block_q=64, block_k=64,
                                 interpret=True, **kw)
    ref = flash_attention_ref(q, k, v, **kw)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < _tol(dtype), (mode, float(err))


@pytest.mark.parametrize("s,hq,hkv,d", [(256, 4, 4, 64), (640, 8, 2, 64),
                                        (512, 4, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(s, hq, hkv, d, dtype):
    b = 3
    q = jax.random.normal(KEY, (b, hq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d), dtype)
    lengths = jnp.array([s, 13, s // 2])
    out = decode_attention_kernel(q, k, v, lengths, block_k=128,
                                  interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < _tol(dtype), float(err)


def test_decode_attention_masks_waiting_tokens():
    """Invalid (waiting/pad) cache slots must not leak into the output —
    the kernel-level statement of the paper's WMA masking."""
    b, s, h, d = 2, 128, 2, 32
    q = jax.random.normal(KEY, (b, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, h, d))
    lengths = jnp.array([40, 64])
    out1 = decode_attention_kernel(q, k, v, lengths, block_k=32,
                                   interpret=True)
    # poison the invalid region; result must not change
    k2 = k.at[0, 40:].set(1e4)
    v2 = v.at[0, 40:].set(-1e4)
    out2 = decode_attention_kernel(q, k2, v2, lengths, block_k=32,
                                   interpret=True)
    assert jnp.allclose(out1, out2, atol=1e-5)


@pytest.mark.parametrize("s,h,p,n,chunk", [(128, 2, 32, 16, 32),
                                           (256, 3, 32, 16, 64),
                                           (192, 2, 64, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan(s, h, p, n, chunk, dtype):
    b = 2
    x = jax.random.normal(KEY, (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)))
    bb = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, n), dtype)
    cc = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, n), dtype)
    y, st = ssd_scan_kernel(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    yr, str_ = ssd_scan_ref(x, dt, a, bb, cc)
    assert float(jnp.max(jnp.abs(y - yr))) < 5e-3
    assert float(jnp.max(jnp.abs(st - str_))) < 5e-3


def test_jnp_chunked_ssd_matches_recurrence():
    """The model's production jnp SSD path against the naive recurrence."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 256, 3, 32, 16
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)))
    bb = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, n))
    cc = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, n))
    y, st = ssd_chunked(x, dt, a, bb, cc, chunk=64)
    yr, str_ = ssd_scan_ref(x, dt, a, bb, cc)
    assert float(jnp.max(jnp.abs(y - yr))) < 5e-3
    assert float(jnp.max(jnp.abs(st - str_))) < 5e-3


def test_blockwise_attention_matches_exact():
    from repro.models.attention import gqa_prefill_attention
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    q = jax.random.normal(KEY, (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    out = gqa_prefill_attention(q, k, v, causal=True, chunk=64)
    ref = flash_attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4


@pytest.mark.parametrize("s,hq,hkv,d", [(256, 4, 2, 32), (320, 8, 2, 64)])
def test_decode_attention_int8(s, hq, hkv, d):
    """int8-cache kernel variant vs the fp oracle (quantization tolerance)."""
    from repro.kernels.decode_attention.kernel import (
        decode_attention_int8_kernel)
    b = 2
    q = jax.random.normal(KEY, (b, hq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    lengths = jnp.array([s, s // 3])

    def q8(t):
        sc = jnp.maximum(jnp.max(jnp.abs(t), -1) / 127., 1e-8)
        return jnp.round(t / sc[..., None]).astype(jnp.int8), sc

    kq, ks = q8(k)
    vq, vs = q8(v)
    out = decode_attention_int8_kernel(q, kq, vq, ks, vs, lengths,
                                       block_k=64, interpret=True)
    ref = decode_attention_ref(q, k, v, lengths)
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05


# ---------------- block-table paged decode attention ----------------

def _paged_setup(b, nb, bt, hq, hkv, d, mb, lengths, dtype=jnp.float32):
    """Random pool + disjoint per-request tables covering ``lengths``."""
    q = jax.random.normal(KEY, (b, hq, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (nb, hkv, bt, d), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (nb, hkv, bt, d), dtype)
    tables = jnp.zeros((b, mb), jnp.int32)
    nxt = 1                      # block 0 plays the shared null/pad block
    for i, ln in enumerate(lengths):
        for j in range(-(-ln // bt)):
            tables = tables.at[i, j].set(nxt)
            nxt += 1
    assert nxt <= nb
    return q, kp, vp, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("bt,hq,hkv,d,lengths",
                         [(16, 4, 4, 64, (48, 17, 5)),      # non-multiples
                          (16, 4, 2, 64, (64, 33, 16)),
                          (8, 8, 1, 32, (40, 23, 9)),
                          (32, 6, 2, 64, (96, 1, 50))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(bt, hq, hkv, d, lengths, dtype):
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_kernel)
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    b = len(lengths)
    mb = max(-(-ln // bt) for ln in lengths)
    nb = sum(-(-ln // bt) for ln in lengths) + 1
    q, kp, vp, tables, lens = _paged_setup(b, nb, bt, hq, hkv, d, mb,
                                           lengths, dtype)
    out = paged_decode_attention_kernel(q, kp, vp, tables, lens,
                                        interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < (5e-2 if dtype == jnp.bfloat16 else 1e-3), float(err)


def test_paged_matches_dense_decode_attention():
    """Identity block tables over a contiguous pool == the dense kernel's
    answer: paging changes layout, not math."""
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)
    b, s, hq, hkv, d, bt = 2, 64, 4, 2, 32, 16
    q = jax.random.normal(KEY, (b, hq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    lengths = jnp.array([50, 29])
    # request i's pages are the contiguous slices of its own dense cache
    kp = k.reshape(b * (s // bt), bt, hkv, d).swapaxes(1, 2)
    vp = v.reshape(b * (s // bt), bt, hkv, d).swapaxes(1, 2)
    tables = jnp.arange(b * (s // bt), dtype=jnp.int32).reshape(b, s // bt)
    ref_dense = decode_attention_ref(q, k, v, lengths)
    ref_paged = paged_decode_attention_ref(q, kp, vp, tables, lengths)
    assert float(jnp.max(jnp.abs(ref_dense - ref_paged))) < 1e-6


def test_paged_decode_attention_masks_foreign_pages():
    """Poisoning (a) positions past a request's length inside its last
    block, (b) the null block, and (c) a page that sits in the request's
    table past its length, in the same chunk as its live pages, must not
    change its output: the isolation property the shared pool depends on.
    The poison is NaN where a page past the length could be read."""
    from repro.kernels.decode_attention.kernel import (
        decode_pages_per_step, paged_decode_attention_kernel)
    bt, hq, hkv, d = 16, 4, 2, 32
    lengths = (23, 40)
    b, mb = 2, 3
    nb = 7
    q, kp, vp, tables, lens = _paged_setup(b, nb, bt, hq, hkv, d, mb, lengths)
    # request 0 holds blocks 1, 2 and, past its length, block 6
    tables = tables.at[0, 2].set(6)
    assert decode_pages_per_step(hkv * bt * d * 4, mb) == mb   # one chunk
    out1 = paged_decode_attention_kernel(q, kp, vp, tables, lens,
                                         interpret=True)
    # poison: block 0 (null), request 0's tail (23 % 16 = 7 into block 2)
    # and its table's block 6, which lies past its length
    nan = jnp.float32(jnp.nan)
    kp2 = kp.at[0].set(1e4).at[2, :, 7:].set(nan).at[6].set(nan)
    vp2 = vp.at[0].set(1e4).at[2, :, 7:].set(nan).at[6].set(nan)
    out2 = paged_decode_attention_kernel(q, kp2, vp2, tables, lens,
                                         interpret=True)
    assert bool(jnp.all(jnp.isfinite(out2[0])))
    assert jnp.allclose(out1[0], out2[0], atol=1e-5)


# (block_tokens, Hq, Hkv, D, max_blocks, lengths, dtype, pages a step):
# lengths end on a chunk boundary, one past it and one short of it; None is
# an idle slot (length 1 on the null table); rows differ by several
# chunks; max_blocks is not a multiple of the pages a step
CHUNKED = [
    (16, 8, 4, 64, 10, (64, 65, 63, None, 160, 130), jnp.float32, 4),
    (32, 8, 4, 64, 7, (128, 129, None, 127, 224, 33), jnp.float32, 2),
    (16, 8, 4, 64, 19, (128, 129, 127, None, 304, 40), jnp.bfloat16, 8),
    (8, 6, 2, 32, 37, (None, 256, 257, 255, 296, 9), jnp.float32, 32),
]


@pytest.mark.parametrize("bt,hq,hkv,d,mb,lengths,dtype,pp", CHUNKED)
def test_paged_decode_attention_chunks(bt, hq, hkv, d, mb, lengths, dtype,
                                       pp):
    """Rows of several chunks against the gather oracle, pages scattered
    over the pool in no order."""
    import numpy as np
    from repro.kernels.decode_attention.kernel import (
        decode_pages_per_step, paged_decode_attention_kernel)
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    assert decode_pages_per_step(
        hkv * bt * d * jnp.dtype(dtype).itemsize, mb) == pp
    b = len(lengths)
    lens = [1 if n is None else n for n in lengths]
    pages = [0 if n is None else -(-n // bt) for n in lengths]
    nb = sum(pages) + 1
    ids = np.random.default_rng(3).permutation(np.arange(1, nb))
    tables = np.zeros((b, mb), np.int32)       # block 0: the null block
    used = 0
    for i, n in enumerate(pages):
        tables[i, :n] = ids[used:used + n]
        used += n
    q = jax.random.normal(KEY, (b, hq, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (nb, hkv, bt, d),
                           dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (nb, hkv, bt, d),
                           dtype)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention_kernel(q, kp, vp, tables, lens,
                                        interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lens)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < (5e-2 if dtype == jnp.bfloat16 else 1e-3), float(err)


@pytest.mark.parametrize("seed", range(4))
def test_paged_decode_schedule_copies_live_pages_once(seed):
    """The kernel's steps cover each row's live chunks in order.  A page
    slot names the live page its step needs, and otherwise the block it
    already holds, so the pipeline, which copies a slot's block when its
    id changes, copies each live page once and no page past a length:
    every copy after the first step's is a live page of its step."""
    import numpy as np
    from repro.kernels.decode_attention.kernel import _decode_schedule
    rng = np.random.default_rng(seed)
    b, mb, bt, pp = 12, 13, 16, 4
    lens = rng.integers(1, mb * bt + 1, b)
    lens[rng.integers(0, b, 3)] = 1                      # idle slots
    tables = rng.permutation(np.arange(1, b * mb + 1)).reshape(b, mb)
    steps, sched = _decode_schedule(jnp.asarray(tables, jnp.int32),
                                    jnp.asarray(lens, jnp.int32), bt, pp,
                                    interpret=True)
    steps = int(np.asarray(steps)[0])
    sched = np.asarray(sched).reshape(-1, pp + 2)[:steps]
    live = -(-lens // bt)
    want = [(r, c) for r in range(b) for c in range(-(-live[r] // pp))]
    assert [tuple(x) for x in sched[:, pp:]] == want
    slots = sched[:, :pp]                                # block ids
    for w, (r, c) in enumerate(want):
        for i in range(pp):
            if c * pp + i < live[r]:
                assert slots[w, i] == tables[r, c * pp + i]
            elif w:
                assert slots[w, i] == slots[w - 1, i]
    copies = pp + int((slots[1:] != slots[:-1]).sum())
    assert copies == live.sum() + pp - min(live[0], pp)


# (name, page bytes, max_blocks): smollm-135m and chatglm-6b pages in
# bf16 at 16 tokens, the cells' tables (96 blocks) and short ones
PAGES = [("smollm-135m", 3 * 16 * 64 * 2, 96),
         ("smollm-135m-short", 3 * 16 * 64 * 2, 3),
         ("chatglm-6b", 32 * 16 * 128 * 2, 96),
         ("tiny-f32", 2 * 8 * 32 * 4, 1000)]


@pytest.mark.parametrize("name,page_bytes,mb", PAGES,
                         ids=[p[0] for p in PAGES])
def test_decode_pages_per_step(name, page_bytes, mb):
    """Pages a step: a function of the shapes alone, at least 1, at most
    the table, within the VMEM share unless one page exceeds it, and a
    power of two unless the table caps it."""
    from repro.kernels.decode_attention.kernel import (
        DECODE_CHUNK_BYTES, decode_pages_per_step)
    pp = decode_pages_per_step(page_bytes, mb)
    assert pp == decode_pages_per_step(page_bytes, mb)
    assert 1 <= pp <= mb
    assert pp * page_bytes <= DECODE_CHUNK_BYTES or pp == 1
    assert pp == mb or (pp & (pp - 1)) == 0
    if pp < mb:       # the largest such power of two
        assert 2 * pp * page_bytes > DECODE_CHUNK_BYTES
