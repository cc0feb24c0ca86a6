"""Real JAX serving engines (run the actual model; CPU-sized configs).

- :class:`BatchEngine` — the paper's §II-D padded batch procedure: pad all
  requests to the batch length, prefill, then decode until *every* request
  has finished (early finishers keep generating invalid tokens = request
  waiting).  Reports measured WMA so tests can check Eqs. (2)-(4) against
  reality.
- :class:`ContinuousEngine` — conservative continuous batching (CCB):
  slot-based active set; a joining request's prefill pauses the instance.
- :class:`PagedContinuousEngine` — continuous batching over a shared
  physical block pool (`serving.paged_cache.BlockAllocator`): admission
  reserves blocks for the *predicted* generation length only, decode
  grows per-request block tables block-by-block, and a failed grow
  evicts-and-requeues instead of splitting the batch (DESIGN.md §8).

Decode runs in **fused multi-step windows** (DESIGN.md §9): a jitted
``lax.scan`` performs ``k`` decode iterations entirely on device — on-
device argmax feeds each step's token into the next, the
``[B, padded_vocab]`` logits never leave the device, and the generated
tokens come back as one ``[B, k]`` buffer per window.  The window length
is the host-computed distance to the next engine event (a finish or a
block-table grow), rounded down to a power of two so the jit cache holds
O(log G_max) entries.  Host syncs per generated token drop from O(1) to
O(1/k); every engine counts them in ``host_syncs``.

Generation is *length-scripted replay*: logits are computed by the real
model (compute is real), but EOS fires at the request's ground-truth
generation length — standard for serving-system benchmarking and required
for controlled comparisons (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque
from typing import Collection, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitizer as _san
from repro.analysis.sanitizer import count_sync, hot_path
from repro.configs.base import ModelConfig
from repro.core.types import Batch, Request
from repro.core.wma import batch_wma
from repro.kernels.decode_attention.kernel import decode_pages_per_step
from repro.models import model as M
from repro.models.transformer import cast_params
from repro.serving.faults import FaultInjector, Shed
from repro.serving.paged_cache import (BlockAllocator, HostSwapTier,
                                       MispredictionEWMA, NULL_SEQ,
                                       PrefixMatch, RadixPrefixCache)
from repro.serving.trace import span
from repro.workload.tokenizer import encode


class EngineFull(RuntimeError):
    """Admission refused: no free slot / not enough free KV blocks.
    Callers must keep the request queued and retry after a step().

    ``evicted`` is a typed field (default ``()``): admission itself never
    evicts, but the attribute exists on every instance so catch sites can
    requeue ``e.evicted`` without hasattr probing (DESIGN.md §14)."""

    def __init__(self, msg: str = "", *,
                 evicted: Tuple[Request, ...] = ()):
        super().__init__(msg)
        self.evicted: Tuple[Request, ...] = tuple(evicted)


class PoolExhausted(MemoryError, EngineFull):
    """Decode-time growth cannot proceed: the pool is too small for the
    growing request, its table overflowed ``max_len + max_gen``, or a
    foreign sequence on a shared allocator holds the blocks.

    Typed replacement for the ad-hoc ``e.evicted = evicted`` attribute
    smuggling: ``evicted`` carries the requests evicted earlier in the
    same failed ``step_window`` (callers must requeue them), ``culprit``
    the request whose growth raised — already freed from its slot, so
    the engine itself stays serviceable and drainable after the raise.
    Subclasses :class:`MemoryError` so pre-§14 ``except MemoryError``
    call sites keep working."""

    def __init__(self, msg: str = "", *,
                 evicted: Tuple[Request, ...] = (),
                 culprit: Optional[Request] = None):
        EngineFull.__init__(self, msg, evicted=evicted)
        self.culprit = culprit


_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048)


def _bucket(n: int, buckets=_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    # beyond the table: next power of two, so pad shapes (and the jit
    # cache) stay O(log n) even for max_len > buckets[-1]
    return _pow2_ceil(n)


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1)."""
    return 1 << (max(n, 1).bit_length() - 1)


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    n = max(n, 1)
    return 1 << (n - 1).bit_length() if n & (n - 1) else n


def _restore_slot(tables, positions, active, logits, slot, row, pos,
                  logits_row):
    """§15 resume: restore a suspended slot's four engine arrays in ONE
    dispatch (vs four eager per-array updates — resume latency is the
    swap tier's sale price).  ``slot`` is a traced np.int32 so a single
    compile serves every slot.  All four arrays are donated: callers
    rebind them all."""
    return (tables.at[slot].set(row),
            positions.at[slot].set(pos),
            active.at[slot].set(True),
            logits.at[slot].set(logits_row))


def _named_partial(fn, **bound):
    """``functools.partial`` that keeps ``fn``'s name: the jitted program
    is then ``jit_<fn>`` in the profiler and the compiled HLO (a bare
    partial compiles as ``jit__unknown``)."""
    p = functools.partial(fn, **bound)
    p.__name__ = fn.__name__
    return p


@functools.lru_cache(maxsize=None)
def _jitted(cfg: ModelConfig, dtype):
    """One jitted entry-point set per (config, dtype), shared by every
    engine instance: re-creating an engine must not re-compile (the
    recompile-audit tier counts on this), and benchmark comparisons
    between engines stay warm-cache on both sides.

    ``prefill_wave`` is the paged engines' single admission entry point
    (DESIGN.md §12): COW clones + variable-prefix prefill + suffix-KV
    scatter + slot-state update in ONE dispatch, with the page pools and
    the per-slot engine arrays donated — admission never copies the pool
    and never reads anything back."""
    return {
        "prefill": jax.jit(
            _named_partial(M.prefill, cfg=cfg, act_dtype=dtype),
            static_argnames=("cache_len",)),
        # every decode entry point donates its KV buffer: each step writes
        # one token's KV back into the same cache/pool, so without donation
        # XLA keeps two full copies live across the dispatch (and hotlint
        # HL003 flags the rebind-without-donate call sites)
        "decode": jax.jit(
            _named_partial(M.decode_step, cfg=cfg, act_dtype=dtype),
            donate_argnames=("cache",)),
        "decode_multi": jax.jit(
            _named_partial(M.decode_multi, cfg=cfg, act_dtype=dtype),
            static_argnames=("num_steps",), donate_argnames=("cache",)),
        "decode_paged": jax.jit(
            _named_partial(M.decode_step_paged, cfg=cfg, act_dtype=dtype),
            donate_argnames=("pages",)),
        "decode_multi_paged": jax.jit(
            _named_partial(M.decode_multi_paged, cfg=cfg,
                           act_dtype=dtype),
            static_argnames=("num_steps",), donate_argnames=("pages",)),
        "prefill_wave": jax.jit(
            _named_partial(M.prefill_wave, cfg=cfg, act_dtype=dtype),
            donate_argnames=("pages", "state")),
        # grow-path COW clones (decode side): donated so the in-place
        # page copy never duplicates the pool — §12's full-span
        # publishing makes every request clone its published tail at
        # its first grow, so this runs once per request, not rarely
        "copy_pages": jax.jit(M.copy_pages, donate_argnames=("pages",)),
        # §15 host swap tier: gather stacks a suspension's pages for ONE
        # device→host readback (pages NOT donated — the pool lives on);
        # scatter writes a resume's host pages back, donated like
        # copy_pages so the pool is never duplicated mid-serve
        "gather_pages": jax.jit(M.gather_pages),
        "scatter_pages": jax.jit(M.scatter_pages,
                                 donate_argnames=("pages",)),
        # §15 resume: one fused dispatch restores a suspended slot's
        # four engine arrays (donated — the caller rebinds them all)
        "restore_slot": jax.jit(
            _restore_slot,
            donate_argnames=("tables", "positions", "active", "logits")),
        # §16 speculative decoding: the draft's fused k+1-step proposal
        # scan (fetched from the DRAFT config's entry-point set) and the
        # target's one-dispatch verification of the whole window.  Both
        # donate their own pool only — positions/logits are carried
        # state the engine rebinds, matching decode_multi_paged
        "draft_window": jax.jit(
            _named_partial(M.draft_window, cfg=cfg, act_dtype=dtype),
            static_argnames=("num_steps", "target_vocab"),
            donate_argnames=("pages",)),
        "verify_window": jax.jit(
            _named_partial(M.verify_window, cfg=cfg, act_dtype=dtype),
            donate_argnames=("pages",)),
    }


@dataclasses.dataclass
class ServeResult:
    iterations: int
    batch_size: int
    batch_length: int
    wall_time: float
    wma: int
    total_tokens: int
    valid_tokens: int
    generated: Dict[int, List[int]]   # req_id -> generated token ids
    decode_time: float = 0.0          # decode loop only (prefill excluded)


class BatchEngine:
    """Padded batch serving with the real model (vanilla / Magnus runtime)."""

    def __init__(self, cfg: ModelConfig, params=None, *, seed: int = 0,
                 max_gen: int = 64, dtype=jnp.float32):
        self.cfg = cfg
        self.max_gen = max_gen
        self.dtype = dtype
        self.params = params if params is not None else M.init_params(
            cfg, jax.random.PRNGKey(seed))
        jt = _jitted(cfg, dtype)
        self._prefill = jt["prefill"]
        self._decode_multi = jt["decode_multi"]
        self.host_syncs = 0

    def _tokens(self, reqs: List[Request], pad_to: int) -> np.ndarray:
        out = np.zeros((len(reqs), pad_to), np.int64)
        for i, r in enumerate(reqs):
            ids = encode(f"{r.instruction} {r.user_input}",
                         self.cfg.vocab_size)[:pad_to]
            out[i, :len(ids)] = ids
        return out

    @hot_path
    def serve_batch(self, batch: Batch) -> ServeResult:
        reqs = batch.requests
        t0 = time.perf_counter()
        bl = _bucket(max(r.length for r in reqs))
        lengths = np.array([min(r.length, bl) for r in reqs], np.int32)
        gen_targets = np.array([min(r.gen_length, self.max_gen)
                                for r in reqs], np.int32)
        bg = int(gen_targets.max())
        cache_len = _bucket(bl + bg + (self.cfg.num_patches
                                       if self.cfg.family == "vlm" else 0))
        tokens = self._tokens(reqs, bl)
        batch_in = {"tokens": jnp.asarray(tokens),
                    "lengths": jnp.asarray(lengths)}
        if self.cfg.family == "vlm":
            batch_in["patches"] = jnp.zeros(
                (len(reqs), self.cfg.num_patches, self.cfg.d_model), self.dtype)
        if self.cfg.family == "audio":
            batch_in["frames"] = jnp.zeros(
                (len(reqs), self.cfg.encoder_seq, self.cfg.d_model), self.dtype)
        logits, cache = self._prefill(self.params, batch=batch_in,
                                      cache_len=cache_len)
        positions = jnp.asarray(lengths)
        # gen_targets are known up front, so the whole decode loop fuses
        # into power-of-two on-device windows; the padded-vocab logits are
        # sliced exactly once, inside the fused argmax. Decode until the
        # slowest request finishes (request waiting!).
        # hotlint: sync(uncounted: decode_time barrier, not a readback)
        jax.block_until_ready(logits)   # decode_time excludes the prefill
        t_dec = time.perf_counter()
        chunks: List[np.ndarray] = []
        remaining = bg
        while remaining > 0:
            k = _pow2_floor(remaining)
            logits, cache, positions, toks = self._decode_multi(
                self.params, cache=cache,
                batch={"logits": logits, "positions": positions},
                num_steps=k)
            # hotlint: sync(window token readback — one sync per window)
            chunks.append(np.asarray(toks))
            self.host_syncs += count_sync()
            remaining -= k
        toks = (np.concatenate(chunks, axis=1) if chunks
                else np.zeros((len(reqs), 0), np.int32))
        decode_time = time.perf_counter() - t_dec
        generated = {r.req_id: toks[i, :int(gen_targets[i])].tolist()
                     for i, r in enumerate(reqs)}
        wall = time.perf_counter() - t0
        wma = batch_wma([int(l) for l in lengths],
                        [int(g) for g in gen_targets])
        return ServeResult(
            iterations=int(bg), batch_size=len(reqs), batch_length=bl,
            wall_time=wall, wma=wma,
            total_tokens=len(reqs) * int(bg),
            valid_tokens=int(gen_targets.sum()), generated=generated,
            decode_time=decode_time)


class ContinuousEngine:
    """Conservative continuous batching with the real model: fixed slots;
    joins prefill alone (single-request batch) while decoding pauses."""

    def __init__(self, cfg: ModelConfig, params=None, *, seed: int = 0,
                 slots: int = 4, max_len: int = 256, max_gen: int = 64,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.max_gen = max_gen
        self.dtype = dtype
        self.params = params if params is not None else M.init_params(
            cfg, jax.random.PRNGKey(seed))
        jt = _jitted(cfg, dtype)
        self._prefill = jt["prefill"]
        self._decode = jt["decode"]
        self.cache = M.init_cache(cfg, slots, max_len + max_gen,
                                  dtype=jnp.float32 if dtype == jnp.float32
                                  else jnp.bfloat16)
        self.active: List[Optional[dict]] = [None] * slots
        self.logits = jnp.zeros((slots, cfg.padded_vocab), dtype)
        self.positions = np.zeros(slots, np.int32)
        self.host_syncs = 0

    # device-resident attrs: hotlint taints reads of these in hot regions
    # (positions is a HOST mirror here, deliberately absent)
    _DEVICE_STATE = ("cache", "logits")

    def _merge_cache_slot(self, slot: int, single_cache) -> None:
        """Copy a single-request prefill cache into slot ``slot``."""
        def merge(dst, src):
            return dst.at[:, slot:slot + 1].set(
                src[:, :, :dst.shape[2]].astype(dst.dtype)
                if src.shape[2] >= dst.shape[2] else
                jnp.pad(src, [(0, 0), (0, 0), (0, dst.shape[2] - src.shape[2])]
                        + [(0, 0)] * (src.ndim - 3)).astype(dst.dtype))
        self.cache = jax.tree.map(merge, self.cache, single_cache)

    @property
    def has_capacity(self) -> bool:
        return None in self.active

    @hot_path
    def join(self, req: Request) -> int:
        if not self.has_capacity:
            raise EngineFull(
                f"all {self.slots} slots occupied; queue req "
                f"{req.req_id} and retry after step()")
        slot = self.active.index(None)
        ids = encode(f"{req.instruction} {req.user_input}",
                     self.cfg.vocab_size)[:self.max_len]
        pad = _bucket(len(ids))
        tokens = np.zeros((1, pad), np.int64)
        tokens[0, :len(ids)] = ids
        batch_in = {"tokens": jnp.asarray(tokens),
                    "lengths": jnp.asarray([len(ids)], np.int32)}
        if self.cfg.family == "vlm":
            batch_in["patches"] = jnp.zeros(
                (1, self.cfg.num_patches, self.cfg.d_model), self.dtype)
        if self.cfg.family == "audio":
            batch_in["frames"] = jnp.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model), self.dtype)
        logits, single_cache = self._prefill(
            self.params, batch=batch_in,
            cache_len=self.max_len + self.max_gen)
        self._merge_cache_slot(slot, single_cache)
        self.logits = self.logits.at[slot].set(logits[0].astype(self.dtype))
        self.positions[slot] = len(ids)
        self.active[slot] = {"req": req, "generated": [],
                             "target": min(req.gen_length, self.max_gen)}
        return slot

    @hot_path
    def step(self) -> List[Request]:
        """One decode iteration over all active slots; returns finished."""
        if not any(self.active):
            return []
        next_tok = jnp.argmax(self.logits[:, :self.cfg.vocab_size],
                              axis=-1).astype(jnp.int32)
        self.logits, self.cache = self._decode(
            self.params, cache=self.cache,
            batch={"tokens": next_tok,
                   "positions": jnp.asarray(self.positions)})
        self.logits = self.logits.astype(self.dtype)
        self.positions = self.positions + 1
        # read the tokens back only after the decode dispatch is in
        # flight: the sync overlaps device compute instead of serializing
        # hotlint: sync(per-step token readback, overlapped with decode)
        tok_host = np.asarray(next_tok)
        self.host_syncs += count_sync()
        for slot, a in enumerate(self.active):
            if a is not None:
                a["generated"].append(int(tok_host[slot]))
        finished = []
        for slot, a in enumerate(self.active):
            if a is not None and len(a["generated"]) >= a["target"]:
                finished.append(a["req"])
                self.active[slot] = None
                self.positions[slot] = 0
        return finished


class PagedContinuousEngine:
    """Continuous batching over a shared physical block pool.

    KV lives in per-layer pools ``[L, num_blocks, Hkv, block_tokens, D]``;
    each active request owns a block table (allocator seq_id = its slot).
    Admission reserves ``L(p) + G'(p)`` tokens of blocks — the *predicted*
    generation length, not G_max — so concurrency at a given Θ is bounded
    by actual footprints, not the dense engines' ``(L_max + G_max)`` slot
    reservation.  When a request outlives its prediction, decode grows its
    table one block at a time; if the pool is exhausted, the least-progress
    other request is evicted (blocks freed, request returned for requeue —
    recompute-on-readmit preemption, not the padded engines' batch split).

    Block tables and positions are **device-resident** ``jnp`` arrays
    updated functionally (``.at[].set``): the decode dispatch never
    re-uploads host state, and there is no aliasing hazard to defend
    against with copies.  Host-side mirrors (``pos_host`` plus the
    allocator's tables) carry the scheduling arithmetic — they are derived
    deterministically from admissions and window lengths, never read back
    from the device.

    Decode runs in fused windows (``step_window``): ``k`` is the minimum
    over active slots of steps-to-finish and steps-to-block-boundary, so
    every grow/evict/finish still happens on the host *between* windows —
    eviction and least-progress victim semantics are unchanged from the
    per-token loop.  ``fuse=False`` pins ``k = 1`` (the per-token baseline
    the BENCH_engine trajectory compares against).

    A reserved *null block* backs every inactive/pad table entry so masked
    gathers and idle-slot writes can never touch a live request's pages.

    With ``prefix_cache`` enabled (DESIGN.md §11), admission walks a
    **token-id radix tree** of published prefix blocks: the longest
    cached block-aligned prefix across *all* apps is shared (ref-
    counted) and only the tokens past the divergence point run through
    the model, at position offset ``match.tokens``.  A match ending
    mid-block shares the partial tail read-only and **copy-on-writes**
    it — fresh block, device page copy, table-entry swap — before the
    suffix prefill appends into it; the same clone step guards the
    decode grow path when a published partial tail would be appended to
    (``cow_copies`` counts both).  Every admission *publishes* its
    shareable span at every block boundary, so a head-only hit's
    private tail becomes an exact hit for the next same-template
    request.  Finish/evict drop per-request references; shared pages
    free only when radix leaf-LRU eviction reclaims them under pool
    pressure *and* no live table references them.

    Admission itself is a **single-dispatch variable-prefix wave**
    (DESIGN.md §12): hits and misses ride one jitted ``prefill_wave``
    call per suffix-length bucket — a miss is just ``prefix_len = 0``
    against a width-1 null gather table — and the call folds the COW
    page copies, the suffix-KV scatter and the per-slot state update
    into the same dispatch over donated buffers.  The wave is ordered
    **radix-aware**: requests matching a chain published earlier in the
    same wave admit one dispatch *generation* later, after the chain's
    KV is written, converting same-wave duplicate templates from N full
    prefills into one full + (N-1) suffix prefills.  The shareable span
    covers the whole prompt (instruction AND user input, §12), so
    byte-identical retries hit end-to-end and prefill one token; radix
    tree inserts are deferred off the admission hot path and flushed
    between waves (``_flush_publishes``), keeping a pure-miss cache-on
    wave as fast as cache-off.
    """

    def __init__(self, cfg: ModelConfig, params=None, *, seed: int = 0,
                 max_concurrency: int = 8, num_blocks: int = 64,
                 block_tokens: int = 16, max_len: int = 256,
                 max_gen: int = 64, dtype=jnp.float32,
                 allocator: Optional[BlockAllocator] = None,
                 fuse: bool = True, warmup: bool = False,
                 prefix_cache=False,
                 faults: Optional[FaultInjector] = None,
                 retry_budget: int = 3,
                 default_ttl: Optional[int] = None,
                 mispredict: Optional[MispredictionEWMA] = None,
                 nan_guard: Optional[bool] = None,
                 swap_blocks: int = 0,
                 spec_decode: bool = False, draft_k: int = 4,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_params=None, draft_seed: int = 1):
        ok, why = M.supports_paged(cfg)
        if not ok:
            raise NotImplementedError(f"{cfg.name}: {why}")
        self.cfg = cfg
        self.max_len = max_len
        self.max_gen = max_gen
        self.dtype = dtype
        self.fuse = fuse
        self.allocator = allocator if allocator is not None else \
            BlockAllocator(num_blocks, block_tokens)
        if isinstance(prefix_cache, RadixPrefixCache):
            if prefix_cache.allocator is not self.allocator:
                raise ValueError("prefix_cache must share the engine's "
                                 "BlockAllocator (one physical pool)")
            self.prefix_cache: Optional[RadixPrefixCache] = prefix_cache
        else:
            self.prefix_cache = (RadixPrefixCache(self.allocator)
                                 if prefix_cache else None)
        self.bt = self.allocator.block_tokens
        self.slots = max_concurrency
        # §16: a speculative window writes up to draft_k lookahead KV
        # positions past the accepted stream before rollback truncates
        # them — per-slot tables must cover the transient overshoot
        self.max_blocks = -(-(max_len + max_gen
                              + (draft_k if spec_decode else 0)) // self.bt)
        # the null block: every pad/idle table entry points here
        self.null_block = self.allocator.allocate(self._NULL_SEQ, 1)[0]
        # weights live in the serving dtype: the jitted steps' at-use
        # cast is then a no-op instead of a full-weight convert per call
        self.params = cast_params(params if params is not None
                                  else M.init_params(
                                      cfg, jax.random.PRNGKey(seed)), dtype)
        jt = _jitted(cfg, dtype)
        self._prefill_wave = jt["prefill_wave"]
        self._copy_pages = jt["copy_pages"]
        self._decode_multi = jt["decode_multi_paged"]
        self._gather_pages = jt["gather_pages"]
        self._scatter_pages = jt["scatter_pages"]
        self._restore_slot = jt["restore_slot"]
        self.pages = M.init_paged_cache(
            cfg, self.allocator.num_blocks, self.bt,
            dtype=jnp.float32 if dtype == jnp.float32 else jnp.bfloat16)
        kp = self.pages["k"]
        self._pages_per_step = decode_pages_per_step(
            math.prod(kp.shape[2:]) * kp.dtype.itemsize, self.max_blocks)
        b = self.slots
        self.active: List[Optional[dict]] = [None] * b
        self._null_row = jnp.full((self.max_blocks,), self.null_block,
                                  jnp.int32)
        self.tables = jnp.tile(self._null_row[None, :], (b, 1))
        self.positions = jnp.zeros(b, jnp.int32)
        self.active_mask = jnp.zeros(b, dtype=bool)
        self.pos_host = np.zeros(b, np.int32)
        self.logits = jnp.zeros((b, cfg.padded_vocab), dtype)
        self.evictions = 0
        self.host_syncs = 0
        self.decode_steps = 0
        self.prefill_tokens = 0   # tokens actually run through a prefill
        self.prefill_dispatches = 0  # variable-prefix wave dispatches
        self.cow_copies = 0       # copy-on-write block clones performed
        # -- robustness / fault-lifecycle state (DESIGN.md §14) ----------
        self.faults = faults
        self.retry_budget = retry_budget
        self.default_ttl = default_ttl
        self.mispredict = (mispredict if mispredict is not None
                           else MispredictionEWMA())
        # NaN/Inf logits quarantine: on when faults are injected (the
        # storm the guard exists for) unless explicitly forced — the
        # extra per-window readback must not tax fault-free serving
        self._nan_guard = (nan_guard if nan_guard is not None
                           else faults is not None)
        self.clock = 0            # scheduler clock: decode iters + stalls
        self.windows = 0          # step_window calls (fault-plan time base)
        self.stall_ticks = 0
        self.deadline_misses = 0
        self.quarantined = 0      # NaN/Inf-poisoned slots removed
        self.requeue_prefix_hits = 0  # evicted requests readmitted via radix
        self.shed_log: List[Shed] = []
        self.retries: Dict[int, int] = {}        # req_id -> eviction count
        self._observed_gen: Dict[int, int] = {}  # req_id -> max progress
        self._requeued: Set[int] = set()         # req_ids evicted at least once
        # -- host-memory swap tier (DESIGN.md §15) -----------------------
        # ``swap_blocks`` host page slots back non-destructive preemption:
        # pool pressure suspends a victim's KV image to host instead of
        # destroying it, and the victim resumes with zero re-prefilled
        # tokens once blocks free up.  0 = tier off (pre-§15 behavior).
        self.swap: Optional[HostSwapTier] = (
            HostSwapTier(swap_blocks) if swap_blocks > 0 else None)
        self._swapped: Dict[int, Dict[str, object]] = {}  # req_id -> image
        # req_ids that were suspended and have not resumed: an admission
        # of one through the prefill path is a re-prefill the §15
        # invariant forbids — counted exactly, floored at 0 by the bench
        self._swap_debt: Set[int] = set()
        self.swap_outs = 0
        self.swap_ins = 0
        self.swapped_blocks = 0        # host page copies performed
        self.swap_reused_blocks = 0    # dedup/device-shared: no copy
        self.reprefilled_swapped_tokens = 0
        self.swapped_ctx_tokens = 0    # context length at each suspension
        self.swap_in_s = 0.0           # wall time inside _swap_in
        # -- crash-safe serving (DESIGN.md §17) --------------------------
        # write-ahead admission journal hook (a RecoveryManager attaches
        # its journal here; None = durability off, zero-cost)
        self.journal = None
        # req_ids whose progress a restored snapshot already covers: a
        # re-prefill of one after restore is a recovery bug — counted
        # exactly, like the §15 swap-debt probe
        self._restored_ids: Set[int] = set()
        self.replayed_reprefill_tokens = 0
        # -- speculative decoding (DESIGN.md §16) ------------------------
        # a draft model proposes draft_k tokens per window from its own
        # paged pool carved out of the SAME BlockAllocator (one physical
        # budget, so admission, grow and the §13/§15 pressure valves see
        # draft footprint exactly like target footprint); the target
        # verifies all k+1 positions in one dispatch and the longest
        # agreeing prefix is accepted on-device — host syncs stay at one
        # per window
        self.spec_decode = bool(spec_decode)
        self.draft_k = int(draft_k)
        self.spec_w = self.draft_k + 1
        self.draft_cfg: Optional[ModelConfig] = None
        self.draft_params = None
        self.draft_pages = None
        self.draft_tables = None
        self.draft_logits = None
        self.spec_windows = 0
        self.spec_slot_windows = 0   # verify rows: active slots × windows
        self.spec_emitted = 0        # tokens emitted by speculative windows
        self.spec_accepted = 0       # draft proposals accepted (emitted - 1)
        self.spec_drafted = 0        # draft proposals offered (k per row)
        self.draft_quarantined = 0   # draft pools permanently iced by guard
        self.draft_prefill_tokens = 0    # draft-pool admission prefills
        self.draft_reprefill_tokens = 0  # draft rebuilds at swap resume
        if spec_decode:
            if draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            if not fuse:
                raise ValueError("spec_decode requires the fused window "
                                 "path (fuse=True)")
            dcfg = draft_cfg if draft_cfg is not None else cfg
            ok, why = M.supports_paged(dcfg)
            if not ok:
                raise NotImplementedError(f"draft {dcfg.name}: {why}")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft vocab must match the target vocab "
                    f"({dcfg.vocab_size} != {cfg.vocab_size}): proposals "
                    "are consumed verbatim by the target's embedding")
            self.draft_cfg = dcfg
            # self-draft (no explicit draft cfg or params) shares the
            # target weights: the acceptance-rate ceiling and the bench
            # sanity config — every proposal must verify
            self.draft_params = cast_params(
                draft_params if draft_params is not None
                else self.params if draft_cfg is None
                else M.init_params(dcfg, jax.random.PRNGKey(draft_seed)),
                dtype)
            djt = _jitted(dcfg, dtype)
            self._draft_prefill_wave = djt["prefill_wave"]
            self._draft_window = djt["draft_window"]
            self._verify_window = jt["verify_window"]
            self.draft_pages = M.init_paged_cache(
                dcfg, self.allocator.num_blocks, self.bt,
                dtype=jnp.float32 if dtype == jnp.float32 else jnp.bfloat16)
            self.draft_tables = jnp.tile(self._null_row[None, :], (b, 1))
            self.draft_logits = jnp.zeros((b, dcfg.padded_vocab), dtype)
        self.window_stats: Optional[Dict[str, int]] = None
        self.generated: Dict[int, List[int]] = {}   # finished req -> tokens
        # admission hot-path memo: encoded prompt ids per (instruction,
        # user_input) — LMaaS traffic re-uses templates and retries
        # whole prompts, and encoding is measurable against a wave
        self._ids_memo: Dict[Tuple[str, str], List[int]] = {}
        # radix publishes deferred off the admission hot path: queued at
        # reserve time, inserted into the tree by the next engine
        # operation that reads it or frees blocks (_flush_publishes)
        self._publish_queue: List[Tuple[Tuple[int, ...], List[int]]] = []
        # chains published earlier in the CURRENT admission wave (tree
        # inserts still pending): later same-wave requests share them and
        # dispatch one generation later, after the KV is written
        self._wave_pending: List[Dict[str, object]] = []
        if warmup:
            self.warmup()

    _NULL_SEQ = NULL_SEQ   # allocator seq_id owning the null block
                           # (shared constant: serving.paged_cache.NULL_SEQ)

    # §16: allocator seq_ids owning a slot's DRAFT pool blocks live in
    # their own negative band, distinct from NULL_SEQ (-1) and the fault
    # injector's FAULT_SEQ (-2), so drain checks and shadow reports can
    # name which pool leaked
    _DRAFT_SEQ_BASE = -100

    def _draft_seq(self, slot: int) -> int:
        return self._DRAFT_SEQ_BASE - slot

    # device-resident attrs: hotlint taints reads of these in hot regions
    # (pos_host and the allocator tables are HOST mirrors, deliberately
    # absent — reading them costs nothing)
    _DEVICE_STATE = ("pages", "tables", "positions", "active_mask", "logits",
                     "draft_pages", "draft_tables", "draft_logits")

    # -- admission -----------------------------------------------------------

    @property
    def num_active(self) -> int:
        return sum(a is not None for a in self.active)

    _IDS_MEMO_CAP = 4096   # bound the prompt memo: unique-prompt traffic
                           # must not grow engine memory without limit

    def _prompt_ids(self, req: Request) -> List[int]:
        key = (req.instruction, req.user_input)
        ids = self._ids_memo.get(key)
        if ids is None:
            ids = encode(f"{req.instruction} {req.user_input}",
                         self.cfg.vocab_size)[:self.max_len]
            if len(self._ids_memo) >= self._IDS_MEMO_CAP:
                # FIFO eviction (dict insertion order): recent retries
                # stay hot, a long-dead prompt goes first
                del self._ids_memo[next(iter(self._ids_memo))]
            self._ids_memo[key] = ids
        return ids

    def _shareable_ids(self, req: Request, ids: List[int]) -> List[int]:
        """Token ids of ``req``'s shareable span: the WHOLE prompt —
        instruction and user input — capped one short of its end (a
        prefill needs >= 1 query token to produce logits).

        §10-§11 capped the span at the instruction; §12 publishes the
        full prompt at block boundaries so byte-identical retries (retry
        storms re-sending the same prompt) hit end-to-end and prefill a
        single token.  Same-template-different-input traffic is
        unchanged: the radix walk stops at the instruction/input
        divergence point, and per-request input leaves are reclaimed by
        the ordinary leaf-LRU under pool pressure."""
        return ids[:len(ids) - 1]

    def _match_wave_pending(self, share_ids: List[int],
                            beat: int) -> Optional[Dict[str, object]]:
        """Longest full-block prefix of ``share_ids`` among chains
        published earlier in the CURRENT wave (radix-aware scheduling,
        DESIGN.md §12).  Full blocks only — the publisher's pages are
        written by its own dispatch, so a mid-block share would clone a
        page that holds nothing yet.  Only a strictly longer match than
        the tree's ``beat`` wins: a resident chain needs no generation
        delay."""
        best: Optional[Dict[str, object]] = None
        best_tokens = beat
        s1 = share_ids[1] if len(share_ids) > 1 else None
        for e in self._wave_pending:
            ids = e["ids"]
            # two-token gate (every prompt starts with BOS): skip the
            # LCP loop for chains whose LCP stops at token two and so
            # cannot reach the one-full-block floor (bt >= 2)
            if s1 is not None and self.bt > 1 and len(ids) > 1 \
                    and ids[1] != s1:
                continue
            n = 0
            for a, b in zip(ids, share_ids):
                if a != b:
                    break
                n += 1
            n = n // self.bt * self.bt
            if n >= self.bt and n > best_tokens:
                best_tokens = n
                best = {"tokens": n, "blocks": e["table"][:n // self.bt],
                        "gen": int(e["gen"]) + 1}
        return best

    def _flush_publishes(self) -> None:
        """Insert queued shareable spans into the radix tree.

        Publishing is deferred off the admission hot path — a pure-miss
        wave pays ~zero radix bookkeeping while admitting (the §12
        hit-rate-0 criterion: cache-on is never slower than cache-off) —
        and flushed by the next engine operation that reads the tree
        (:meth:`join` / :meth:`join_many`) or can free blocks
        (:meth:`step_window`, :meth:`_evict`), so a queued span's table
        blocks are always still live when the insert retains them."""
        if self.prefix_cache is None or not self._publish_queue:
            return
        if self.faults is not None:
            # §17 crash seam: mid-publish — queued spans not yet in the
            # tree (publishes are an optimization, not durable state:
            # restore re-derives nothing from them)
            self.faults.crash_due("publish", self.windows)
        queue, self._publish_queue = self._publish_queue, []
        with span("radix.publish", spans=len(queue)):
            for ids, table in queue:
                self.prefix_cache.insert(ids, table)

    def reserve_tokens(self, req: Request,
                       n_prompt: Optional[int] = None) -> int:
        """Admission footprint: encoded prompt + *predicted* generation
        tokens — the token span the request's block table must cover
        (shared prefix pages included; a radix hit claims only
        ``blocks_needed(reserve) - match.full_blocks`` new blocks: the
        fully-matched head is shared, while a partial tail block is
        cloned and so still costs one of the new blocks)."""
        if n_prompt is None:
            n_prompt = len(self._prompt_ids(req))
        g = (req.predicted_gen_length
             if req.predicted_gen_length is not None else self.max_gen)
        if self.faults is not None:
            g = self.faults.corrupt_prediction(req, g, self.windows)
        # misprediction guard rails (§14): the per-app EWMA headroom
        # multiplier damps under-prediction eviction storms for every
        # admission of that app...
        h = self.mispredict.factor(req.app)
        if h > 1.0:
            g = int(math.ceil(g * h))
        # ...and a request that exhausted its eviction-retry budget
        # escalates past its observed progress, so the readmission
        # cannot thrash at the same block boundary again
        if self.retries.get(req.req_id, 0) >= self.retry_budget:
            g = max(g, self._observed_gen.get(req.req_id, 0) + 1)
        return n_prompt + max(1, min(g, self.max_gen))

    def _reclaimable_blocks(self, keep=None) -> int:
        """Blocks radix leaf-LRU eviction would actually free: blocks of
        unpinned evictable nodes (``keep``'s path excluded) referenced
        by no live table."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.reclaimable_blocks(keep=keep)

    def can_admit(self, req: Request) -> bool:
        """Would :meth:`join` succeed right now?  Counts free blocks plus
        what cache eviction could reclaim, minus the fully-shared blocks
        a radix hit would not need to claim.  Flushes deferred publishes
        first, exactly like :meth:`join` — the answer must reflect the
        same tree state the join it predicts would see."""
        self._flush_publishes()
        if None not in self.active:
            return False
        ids = self._prompt_ids(req)
        want = self.reserve_tokens(req, n_prompt=len(ids))
        keep, full = None, 0
        if self.prefix_cache is not None:
            share = self._shareable_ids(req, ids)
            if share:
                m = self.prefix_cache.match(share, peek=True)
                keep = m.node
                full = m.full_blocks(self.bt) * self.bt
        need = self.allocator.blocks_needed(want - full)
        if self.spec_decode:
            # the draft pool shares nothing (no radix for drafts): a full
            # private copy of the reservation rides every admission
            need += self.allocator.blocks_needed(want)
        return need <= (len(self.allocator.free)
                        + self._reclaimable_blocks(keep=keep))

    def _reserve(self, req: Request) -> Dict[str, object]:
        """Claim a slot + blocks for ``req`` (raises EngineFull) and mark
        the slot active; the KV pages are written by the caller's
        variable-prefix wave dispatch.

        Admission state machine with the radix cache on:

        1. *match* — walk the tree for the longest cached prefix of the
           shareable span; pin the matched node's path (LRU-protected
           while the admission is in flight).  Chains published earlier
           in the SAME wave (tree inserts pending) also match at
           full-block granularity; winning against the tree costs one
           dispatch *generation* — the sharer prefills after the
           publisher's KV is written (radix-aware wave scheduling).
        2. *probe* — the request claims ``blocks_needed(reserve) -
           match.full_blocks`` new blocks; if the pool is short, evict
           cold cache leaves first, else refuse (``EngineFull``, match
           counters rolled back so retries don't inflate them).
        3. *share* — matched pages head the new table (ref-counted).
        4. *copy-on-write* — a tree match ending mid-block swaps the
           shared partial tail for a private clone (the device page copy
           runs inside the wave dispatch).
        5. *allocate* — fresh blocks for suffix + predicted generation.
        6. *queue publish* — the shareable span and the table's leading
           blocks go on the deferred publish queue (and the wave-pending
           list for same-wave sharers); the tree insert itself runs off
           the hot path (:meth:`_flush_publishes`).
        """
        if None not in self.active:
            raise EngineFull(f"all {self.slots} slots occupied")
        slot = self.active.index(None)
        ids = self._prompt_ids(req)
        share_ids: List[int] = []
        m: Optional[PrefixMatch] = None
        pend: Optional[Dict[str, object]] = None
        looked_up = False
        if self.prefix_cache is not None:
            share_ids = self._shareable_ids(req, ids)
            if share_ids:
                m = self.prefix_cache.match(share_ids)
                looked_up = True
                tree_tokens = m.tokens if m.node is not None else 0
                if m.node is None:
                    m = None
                pend = self._match_wave_pending(share_ids, beat=tree_tokens)
                if pend is not None:
                    if m is None:
                        # the walk called it a miss; the same-wave chain
                        # makes it a hit
                        self.prefix_cache.misses -= 1
                        self.prefix_cache.hits += 1
                    m = None            # the pending chain supersedes it
        gen = int(pend["gen"]) if pend is not None else 0
        cached = (int(pend["tokens"]) if pend is not None
                  else m.tokens if m is not None else 0)
        full = cached // self.bt * self.bt   # memory actually shared
        want = self.reserve_tokens(req, n_prompt=len(ids))
        if m is not None:
            self.prefix_cache.pin(m.node)   # protect from LRU while admitting
        try:
            need = self.allocator.blocks_needed(want - full)
            if self.spec_decode:
                # §16: the slot's draft pool claims a full private copy
                # of the reservation (drafts never share radix blocks)
                need += self.allocator.blocks_needed(want)
            if need > len(self.allocator.free):
                if self.prefix_cache is None \
                        or not self.prefix_cache.evict_until(need):
                    raise EngineFull(
                        f"{need} new blocks wanted, "
                        f"{len(self.allocator.free)} free")
            cow = None
            if pend is not None:
                # full blocks only, held live by the publisher's table
                self.allocator.share(slot, pend["blocks"])
            elif m is not None:
                self.allocator.share(slot, m.blocks)
                if cached % self.bt:
                    # the wave's suffix prefill appends into the matched
                    # partial tail: clone it (device copy in the wave)
                    cow = self.allocator.cow_if_not_appendable(
                        slot, len(m.blocks) - 1)
            table = list(self.allocator.allocate(slot, want))
        except EngineFull:
            if m is not None:
                self.prefix_cache.unpin(m.node)
            if looked_up:
                # a refused admission is retried later: don't let the
                # retry loop inflate the published hit/miss counters
                if m is not None or pend is not None:
                    self.prefix_cache.hits -= 1
                else:
                    self.prefix_cache.misses -= 1
            raise
        draft_table: List[int] = []
        if self.spec_decode:
            # allocated last, after every refusable step: an EngineFull
            # above leaves no half-claimed draft pool to roll back.  The
            # probe counted these blocks, so this allocate cannot fail.
            draft_table = list(self.allocator.allocate(
                self._draft_seq(slot), want))
        if self.prefix_cache is not None and share_ids:
            self._publish_queue.append((tuple(share_ids), list(table)))
            self._wave_pending.append(
                {"ids": share_ids, "table": list(table), "gen": gen})
        if cached and req.req_id in self._requeued:
            # an evicted-then-requeued request re-entered through the
            # radix hit path: its own published blocks survived eviction,
            # so the readmission prefills only its suffix (§14 small fix)
            self.requeue_prefix_hits += 1
        ttl = (req.ttl_steps if req.ttl_steps is not None
               else self.default_ttl)
        self.active[slot] = {"req": req, "generated": [],
                             "target": min(req.gen_length, self.max_gen),
                             "prefix": m.node if m is not None else None,
                             "deadline": (self.clock + ttl
                                          if ttl is not None else None),
                             "reserve_tokens": want,
                             "reserve_g": want - len(ids)}
        return {"slot": slot, "ids": ids, "table": table, "cached": cached,
                "cow": cow, "gen": gen, "req": req,
                "draft_table": draft_table}

    def _dispatch_wave(self, plans: List[Dict[str, object]]) -> None:
        """ONE jitted dispatch for a group of just-reserved requests
        sharing a suffix-length bucket: copy-on-write clones, the
        variable-prefix prefill (per-row ``prefix_lens``; a miss is
        ``prefix_len = 0``), the token-granular suffix-KV scatter, and
        the per-slot engine-state update all run inside the single
        donated wave call — the pool and the slot arrays are updated in
        place and nothing is read back.

        The prefix-gather table is width-1 all-null for a pure-miss
        group (the oracle/kernel then streams no dead prefix pages and
        the wave costs exactly what the old dense prefill did) and the
        full ``max_blocks`` table otherwise.  Pad rows repeat row 0's
        slot and values; their KV scatter drops via ``write_lens == 0``.
        """
        n = len(plans)
        nb = _pow2_ceil(n)
        sb = _bucket(max(len(p["ids"]) - p["cached"] for p in plans))
        width = self.max_blocks if any(p["cached"] for p in plans) else 1
        tokens = np.zeros((nb, sb), np.int32)
        lengths = np.ones(nb, np.int32)
        wlens = np.zeros(nb, np.int32)       # scatter validity: pads drop
        plens = np.zeros(nb, np.int32)
        rows = np.full((nb, self.max_blocks), self.null_block, np.int32)
        src = np.full(nb, self.null_block, np.int32)
        dst = np.full(nb, self.null_block, np.int32)
        slots = np.zeros(nb, np.int32)
        sel = np.zeros(nb, np.int32)
        pos_vals = np.ones(nb, np.int32)
        for i, p in enumerate(plans):
            sfx = p["ids"][p["cached"]:]
            tokens[i, :len(sfx)] = sfx
            lengths[i] = len(sfx)
            wlens[i] = len(sfx)
            plens[i] = p["cached"]
            rows[i, :len(p["table"])] = p["table"]
            slots[i] = p["slot"]
            sel[i] = i
            pos_vals[i] = len(p["ids"])
            if p["cow"] is not None:
                src[i], dst[i] = p["cow"]
                self.cow_copies += 1
            self.prefill_tokens += len(sfx)
            if p["req"].req_id in self._swap_debt:
                # a suspended request came back through the prefill path
                # instead of _swap_in: the §15 never-re-prefill invariant
                # is broken — count the wasted tokens exactly
                self.reprefilled_swapped_tokens += len(sfx)
            if p["req"].req_id in self._restored_ids:
                # a snapshot-covered request re-entered through the
                # prefill path: restore should have rebuilt its KV from
                # the image (§17) — count the wasted tokens exactly
                self.replayed_reprefill_tokens += len(sfx)
        # pad rows repeat row 0's slot/table/position (identical duplicate
        # scatter writes) and keep plens[0] for a valid attention gather
        plens[n:] = plens[0]
        rows[n:] = rows[0]
        slots[n:] = slots[0]
        pos_vals[n:] = pos_vals[0]
        attn = (rows[:, :width] if width > 1
                else np.full((nb, 1), self.null_block, np.int32))
        shadow = getattr(self.allocator, "_shadow", None)
        if shadow is not None:
            # every block this wave's KV scatter writes into (suffix +
            # predicted-generation tail) must be privately owned: the
            # shared head stops at cached // bt, and a matched partial
            # tail was COW-cloned by _reserve
            for p in plans:
                shadow.check_write(p["slot"],
                                   p["table"][p["cached"] // self.bt:])
        state = {"tables": self.tables, "positions": self.positions,
                 "active": self.active_mask, "logits": self.logits}
        # np arrays go to the jitted call as-is: jit batches the
        # host->device transfers (one device_put for the whole batch
        # dict beats eleven eager asarray round-trips)
        self.pages, state = self._prefill_wave(
            self.params, pages=self.pages, state=state,
            batch={"tokens": tokens, "lengths": lengths,
                   "prefix_lens": plens, "attn_tables": attn,
                   "tables": rows, "write_lens": wlens,
                   "cow_src": src, "cow_dst": dst, "slots": slots,
                   "row_sel": sel, "positions": pos_vals})
        self.tables = state["tables"]
        self.positions = state["positions"]
        self.active_mask = state["active"]
        self.logits = state["logits"]
        self.prefill_dispatches += 1
        for p in plans:
            self.pos_host[p["slot"]] = len(p["ids"])
            if shadow is not None:
                # the dispatch above wrote this slot's KV: from here on a
                # same-wave sharer writing into its pages is a violation
                shadow.mark_materialized(p["slot"])
        if self.spec_decode:
            # §16: seed the wave's draft pools in one extra dispatch
            # (draft-model weights — it does not ride, and is not
            # counted as, a target prefill_dispatches wave)
            self._draft_prefill(
                [(p["slot"], p["ids"], p["draft_table"]) for p in plans])

    def _draft_prefill(self, items: List[Tuple[int, List[int], List[int]]],
                       *, resume: bool = False) -> None:
        """ONE draft-model prefill dispatch building draft-pool KV for a
        group of ``(slot, token_ids, draft_table)`` rows (§16).  Always a
        full-history, prefix-0 wave — the draft pool has no radix tree to
        share from.  Rides the generic ``prefill_wave`` entry point under
        the DRAFT config; its state scatter rebinds positions/active with
        the values the target wave already set (identical), so only the
        draft tables and the draft carry logits actually change."""
        n = len(items)
        nb = _pow2_ceil(n)
        sb = _bucket(max(len(ids) for _, ids, _ in items))
        tokens = np.zeros((nb, sb), np.int32)
        lengths = np.ones(nb, np.int32)
        wlens = np.zeros(nb, np.int32)       # scatter validity: pads drop
        plens = np.zeros(nb, np.int32)
        rows = np.full((nb, self.max_blocks), self.null_block, np.int32)
        nulls = np.full(nb, self.null_block, np.int32)
        attn = np.full((nb, 1), self.null_block, np.int32)
        slots = np.zeros(nb, np.int32)
        sel = np.zeros(nb, np.int32)
        pos_vals = np.ones(nb, np.int32)
        shadow = getattr(self.allocator, "_shadow", None)
        for i, (slot, ids, table) in enumerate(items):
            tokens[i, :len(ids)] = ids
            lengths[i] = len(ids)
            wlens[i] = len(ids)
            rows[i, :len(table)] = table
            slots[i] = slot
            sel[i] = i
            pos_vals[i] = len(ids)
            if resume:
                self.draft_reprefill_tokens += len(ids)
            else:
                self.draft_prefill_tokens += len(ids)
            if shadow is not None:
                # draft blocks are never shared: the whole table must be
                # privately owned by this slot's draft seq
                shadow.check_write(self._draft_seq(slot), table)
        rows[n:] = rows[0]
        slots[n:] = slots[0]
        pos_vals[n:] = pos_vals[0]
        state = {"tables": self.draft_tables, "positions": self.positions,
                 "active": self.active_mask, "logits": self.draft_logits}
        self.draft_pages, state = self._draft_prefill_wave(
            self.draft_params, pages=self.draft_pages, state=state,
            batch={"tokens": tokens, "lengths": lengths,
                   "prefix_lens": plens, "attn_tables": attn,
                   "tables": rows, "write_lens": wlens,
                   "cow_src": nulls, "cow_dst": nulls, "slots": slots,
                   "row_sel": sel, "positions": pos_vals})
        self.draft_tables = state["tables"]
        self.positions = state["positions"]
        self.active_mask = state["active"]
        self.draft_logits = state["logits"]
        if shadow is not None:
            for slot, _, _ in items:
                shadow.mark_materialized(self._draft_seq(slot))

    def _prefill_admitted(self, admitted: List[Dict[str, object]]) -> None:
        """Order the wave radix-aware and dispatch it with the minimum
        number of variable-prefix prefill calls (DESIGN.md §12):

        - **generations** first: a request sharing a chain published
          earlier in the SAME wave dispatches one generation later, after
          the publisher's KV has been written (publish-then-admit —
          same-wave duplicate templates prefill their suffix only,
          instead of N full prompts);
        - **suffix-length buckets** within a generation: hits and misses
          ride the same dispatch (a miss is ``prefix_len = 0``), so a
          mixed wave whose rows pad to one bucket costs exactly one
          prefill dispatch — the §10 path paid two.
        """
        gens: Dict[int, List[Dict[str, object]]] = {}
        for a in admitted:
            gens.setdefault(int(a["gen"]), []).append(a)
        for g in sorted(gens):
            buckets: Dict[int, List[Dict[str, object]]] = {}
            for a in gens[g]:
                buckets.setdefault(
                    _bucket(max(len(a["ids"]) - a["cached"], 1)),
                    []).append(a)
            for sb in sorted(buckets):
                plans = buckets[sb]
                with span("engine.prefill_wave") as s:
                    if s:
                        cached = sum(int(p["cached"]) for p in plans)
                        s.set_metadata(
                            rows=len(plans), bucket=sb, cached_tokens=cached,
                            suffix_tokens=sum(len(p["ids"]) for p in plans)
                            - cached,
                            req_ids=[p["req"].req_id for p in plans])
                    self._dispatch_wave(plans)

    @hot_path
    def join(self, req: Request) -> int:
        self._flush_publishes()
        self._resume_swapped()   # suspended requests outrank admissions
        self._wave_pending = []
        plan = self._reserve(req)
        self._prefill_admitted([plan])
        return int(plan["slot"])

    @hot_path
    def join_many(self, reqs: Collection[Request]) -> int:
        """Admit the longest admissible prefix of ``reqs`` as ONE
        admission wave: radix-aware ordering (same-wave chain sharers
        admit a generation after their chain's publisher), then one
        variable-prefix prefill dispatch per (generation × suffix-length
        bucket) — exactly 1 for a wave whose suffixes share a bucket,
        hits and misses alike.  Returns how many were admitted (the
        caller pops that many).  Stops at the first request that does
        not fit (FIFO admission, same discipline as repeated ``join``).
        """
        with span("engine.admit", offered=len(reqs)) as s:
            self._flush_publishes()
            self._resume_swapped()   # suspended requests outrank admissions
            self._wave_pending = []
            admitted = []
            for req in reqs:
                try:
                    admitted.append(self._reserve(req))
                except EngineFull:
                    break
            if admitted:
                if self.faults is not None:
                    # §17 crash seam: mid-wave — reservations made, prefill
                    # not yet dispatched (the WAL already holds the admits)
                    self.faults.crash_due("wave", self.windows)
                self._prefill_admitted(admitted)
            s.set_metadata(admitted=len(admitted))
        return len(admitted)

    # -- eviction ------------------------------------------------------------

    def _release(self, slot: int) -> None:
        """Reset a slot's device/host state to idle (null table, pos 0)."""
        if self.spec_decode:
            # the slot's draft pool dies with it (finish, eviction and
            # swap-out all land here); already-quarantined drafts freed
            # their seq earlier — free_seq of a missing seq is a no-op
            self.allocator.free_seq(self._draft_seq(slot))
            self.draft_tables = self.draft_tables.at[slot].set(
                self._null_row)
        self.tables = self.tables.at[slot].set(self._null_row)
        self.positions = self.positions.at[slot].set(0)
        self.active_mask = self.active_mask.at[slot].set(False)
        self.pos_host[slot] = 0
        self.active[slot] = None

    def _unpin_prefix(self, slot: int) -> None:
        """Release the slot's in-flight pin on its matched radix path
        (finish and eviction both come through here)."""
        node = self.active[slot].get("prefix")
        if node is not None:
            self.prefix_cache.unpin(node)

    def _evict(self, slot: int) -> Request:
        self._flush_publishes()   # queued spans reference live tables only
        a = self.active[slot]
        req = a["req"]
        # bounded-retry bookkeeping (§14): count the eviction against the
        # request's retry budget and remember its decode progress, so an
        # escalated readmission reserves past the boundary it died at
        self.retries[req.req_id] = self.retries.get(req.req_id, 0) + 1
        if len(a["generated"]) > self._observed_gen.get(req.req_id, 0):
            self._observed_gen[req.req_id] = len(a["generated"])
        self._requeued.add(req.req_id)
        # destructive eviction: the readmission legitimately re-prefills
        # (§17 snapshot-coverage tripwire must not fire on it)
        self._restored_ids.discard(req.req_id)
        self._unpin_prefix(slot)
        self.allocator.free_seq(slot)     # shared prefix pages survive:
        self._release(slot)               # the cache still holds a reference
        self.evictions += 1
        return req

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Least decode progress first (cheapest recompute on readmit)."""
        best, best_prog = None, None
        for slot, a in enumerate(self.active):
            if a is None or slot == exclude:
                continue
            prog = len(a["generated"])
            if best is None or prog < best_prog:
                best, best_prog = slot, prog
        return best

    # -- host swap tier: suspend / resume (DESIGN.md §15) --------------------

    @property
    def num_suspended(self) -> int:
        """Requests suspended on the host tier (images awaiting resume)."""
        return len(self._swapped)

    def _pick_swap_victim(self, exclude: int) -> Optional[int]:
        """Victim policy for *suspension*: largest EWMA-inflated predicted
        remaining work first — the request expected to occupy the pool
        longest is the one whose blocks buy the most relief — with ties
        broken toward least progress (smallest image to transfer).  The
        EWMA term makes the policy misprediction-aware: an app under an
        under-prediction storm has inflated remaining-work estimates and
        its requests suspend before well-predicted ones are destroyed."""
        best, best_key = None, None
        for slot, a in enumerate(self.active):
            if a is None or slot == exclude:
                continue
            prog = len(a["generated"])
            remaining = (max(a["reserve_g"] - prog, 1)
                         * self.mispredict.factor(a["req"].app))
            key = (remaining, -prog)
            if best is None or key > best_key:
                best, best_key = slot, key
        return best

    @hot_path
    def _swap_out(self, slot: int) -> bool:
        """Suspend ``slot``'s request to the host tier: snapshot its pages
        (one gather + one counted readback for the whole image) and its
        logits row, free the slot and its device blocks, and register the
        image with the tier.  Shared blocks swap once: blocks already
        host-resident are deduplicated, and copied blocks that outlive the
        ``free_seq`` (radix/sibling holders) stay device-resident under a
        ``SWAP_HOLDER`` reference so the resume can re-``share`` them.
        Returns False (nothing changed) when the tier cannot hold the
        image's fresh pages."""
        a = self.active[slot]
        req = a["req"]
        self._flush_publishes()   # queued spans reference live tables only
        table = list(self.allocator.tables[slot])
        fresh = self.swap.fresh_blocks(table)
        if not self.swap.can_hold(len(fresh)):
            return False
        if self.faults is not None:
            # §17 crash seam: mid-swap — tier committed to, image not yet
            # read back (nothing of the suspension survives the crash)
            self.faults.crash_due("swap", self.windows)
        vals = None
        if fresh:
            pad = _pow2_ceil(len(fresh))
            blk = np.full(pad, self.null_block, np.int32)
            blk[:len(fresh)] = fresh
            stacked = self._gather_pages(self.pages, blk)
            # hotlint: sync(§15 swap-out page snapshot — ONE readback per suspension)
            vals = np.asarray(stacked)[:, :, :len(fresh)]
            self.host_syncs += count_sync()
        # np.int32 index: the row gather compiles once for every slot
        # hotlint: sync(§15 swap-out logits-row snapshot for bit-exact resume)
        logits_row = np.asarray(self.logits[np.int32(slot)])
        self.host_syncs += count_sync()
        image = {"req": req, "generated": a["generated"],
                 "target": a["target"], "deadline": a["deadline"],
                 "reserve_tokens": a["reserve_tokens"],
                 "reserve_g": a["reserve_g"],
                 "pos": int(self.pos_host[slot]),
                 "blocks": len(table), "logits": logits_row}
        self._unpin_prefix(slot)
        self.allocator.free_seq(slot)
        self._release(slot)
        self.swap.swap_out(req.req_id, table, fresh, vals, self.allocator)
        self._swapped[req.req_id] = image
        self._swap_debt.add(req.req_id)
        self.swap_outs += 1
        self.swapped_blocks += len(fresh)
        self.swap_reused_blocks += len(table) - len(fresh)
        self.swapped_ctx_tokens += int(image["pos"])
        shadow = getattr(self.allocator, "_shadow", None)
        if shadow is not None:
            shadow.on_swap_out(req.req_id)
        if self.journal is not None:
            self.journal.append("swap", rid=int(req.req_id), dir="out",
                                clock=int(self.clock))
        return True

    def _swap_out_victim(self, exclude: int) -> bool:
        """Suspend the policy's victim; True only when device blocks
        actually freed (a fully-shared image frees nothing — the caller
        then falls through to the next pressure valve)."""
        victim = self._pick_swap_victim(exclude)
        if victim is None:
            return False
        before = len(self.allocator.free)
        if not self._swap_out(victim):
            return False
        return len(self.allocator.free) > before

    @hot_path
    def _swap_in(self, rid: int, image: Dict[str, object],
                 shared: List[int], host_slots: List[int]) -> None:
        """Resume a suspended image into a free slot: re-``share`` the
        device-resident prefix the tier still holds, allocate fresh blocks
        for the rest, scatter the host pages back (donated, nothing read
        back), and restore the slot's device/host state bit-exactly —
        positions, table row, and the pre-suspension logits row, so the
        next decode window continues the stream with zero re-prefilled
        tokens."""
        t0 = time.perf_counter()
        slot = self.active.index(None)
        if shared:
            self.allocator.share(slot, shared)
        table = self.allocator.allocate(slot, int(image["blocks"]) * self.bt)
        fresh = table[len(shared):]
        shadow = getattr(self.allocator, "_shadow", None)
        if shadow is not None and fresh:
            shadow.check_write(slot, fresh)
        if fresh:
            pad = _pow2_ceil(len(fresh))
            blk = np.full(pad, self.null_block, np.int32)
            blk[:len(fresh)] = fresh
            vals = self.swap.read(host_slots)
            vals_p = np.zeros((vals.shape[0], vals.shape[1], pad)
                              + vals.shape[3:], vals.dtype)
            vals_p[:, :, :len(fresh)] = vals
            self.pages = self._scatter_pages(self.pages, blk, vals_p)
        row = np.full(self.max_blocks, self.null_block, np.int32)
        row[:len(table)] = table
        pos = int(image["pos"])
        # one fused dispatch restores all four slot arrays; the traced
        # np.int32 index keeps it slot-agnostic in the jit cache
        (self.tables, self.positions, self.active_mask,
         self.logits) = self._restore_slot(
            self.tables, self.positions, self.active_mask, self.logits,
            np.int32(slot), row, pos, image["logits"])
        self.pos_host[slot] = pos
        self.active[slot] = {"req": image["req"],
                             "generated": image["generated"],
                             "target": image["target"], "prefix": None,
                             "deadline": image["deadline"],
                             "reserve_tokens": image["reserve_tokens"],
                             "reserve_g": image["reserve_g"]}
        if self.spec_decode:
            # §16: the draft pool was dropped at suspension (draft KV is
            # disposable — verification is the correctness oracle), so
            # rebuild it with one DRAFT prefill over the full history.
            # The target stream itself re-prefills nothing: the §15
            # zero-re-prefill invariant and its counter are untouched.
            draft_table = list(self.allocator.allocate(
                self._draft_seq(slot), max(pos, 1)))
            self._draft_prefill(
                [(slot, self._prompt_ids(image["req"])
                  + list(image["generated"]), draft_table)], resume=True)
        self.swap.drop(rid, self.allocator)
        del self._swapped[rid]
        self._swap_debt.discard(rid)
        self.swap_ins += 1
        if shadow is not None:
            shadow.mark_materialized(slot)
            shadow.on_swap_in(rid)
        if self.journal is not None:
            self.journal.append("swap", rid=int(rid), dir="in",
                                clock=int(self.clock))
        self.swap_in_s += time.perf_counter() - t0

    def _try_resume(self, rid: int) -> bool:
        """Resume ``rid`` if device blocks can be found: escalate through
        the same non-destructive pressure valves as ``_grow`` (cold radix
        leaves, then the tier's own device holds) before giving up."""
        image = self._swapped[rid]
        while True:
            shared, host_slots = self.swap.split_resident(rid)
            need = len(host_slots)
            if self.spec_decode:
                # the resume also rebuilds the slot's draft pool (§16)
                need += self.allocator.blocks_needed(int(image["pos"]))
            if need <= len(self.allocator.free):
                self._swap_in(rid, image, shared, host_slots)
                return True
            if self.prefix_cache is not None \
                    and self.prefix_cache.evict_until(need):
                continue
            if self.swap.release_device_holds(self.allocator):
                continue   # holds freed; re-split (shared prefix shrank)
            return False

    def _resume_swapped(self) -> int:
        """Swap suspended requests back in, oldest first, while slots and
        blocks allow — called at the admission seams (``join`` /
        ``join_many``) and the window prologue, so resumes ride the same
        path as fresh admissions but at *higher* priority.  FIFO is
        strict: if the oldest image cannot resume, younger ones wait (no
        starvation).  A ``swap_stall`` fault refuses attempts."""
        if self.swap is None or not self._swapped:
            return 0
        self._flush_publishes()   # resume may evict radix leaves below
        n = 0
        for rid in list(self._swapped):
            if None not in self.active:
                break
            if self.faults is not None and self.faults.swap_stalled():
                break
            if not self._try_resume(rid):
                break
            n += 1
        return n

    def _drop_swapped(self, rid: int, reason: str) -> Request:
        """Give up on a suspended image: typed shed, host slots freed."""
        image = self._swapped.pop(rid)
        self._flush_publishes()   # drop may free tier-held device blocks
        self.swap.drop(rid, self.allocator)
        shadow = getattr(self.allocator, "_shadow", None)
        if shadow is not None:
            shadow.on_swap_in(rid)
        self.shed_log.append(Shed(image["req"], reason, self.clock))
        return image["req"]

    def shed_oldest_swapped(self) -> Optional[Request]:
        """Driver stall escape: shed the oldest suspended image with
        reason ``swapped_timeout`` (a wedged pool must degrade into a
        typed shed, never a hang)."""
        if not self._swapped:
            return None
        return self._drop_swapped(next(iter(self._swapped)),
                                  "swapped_timeout")

    def _expire_swapped(self) -> None:
        """Deadline sweep for suspended images (the §14 sweep only sees
        active slots): an image past its deadline sheds with
        ``swapped_timeout`` — suspended, never resumed in time."""
        if self.swap is None or not self._swapped:
            return
        for rid in list(self._swapped):
            image = self._swapped[rid]
            if image["deadline"] is None or self.clock < image["deadline"]:
                continue
            self._drop_swapped(rid, "swapped_timeout")
            self.deadline_misses += 1

    def _grow(self, slot: int,
              evicted: List[Request]) -> List[Tuple[int, int]]:
        """Ensure slot can hold pos_host[slot]+1 tokens AND privately
        owns every block the coming decode window writes into; evict on
        demand.  Returns (src, dst) copy-on-write page-copy pairs the
        caller must apply on device before decoding — a published
        partial instruction tail still shared with the radix cache is
        the case that triggers one (DESIGN.md §11).

        With speculation on, the window writes up to ``spec_w`` lookahead
        positions before rollback truncates the rejected tail (§16), so
        the capacity target grows from pos+1 to pos+spec_w."""
        need = int(self.pos_host[slot]) \
            + (self.spec_w if self.spec_decode else 1)
        if self.allocator.blocks_needed(need) > self.max_blocks:
            raise MemoryError(
                f"request outgrew max_len+max_gen table ({self.max_blocks} "
                f"blocks)")
        # impossible-fit check BEFORE any eviction: evicting the whole
        # world and then raising would strand the already-evicted requests
        if self.allocator.blocks_needed(need) > self.allocator.num_blocks - 1:
            raise MemoryError(
                f"paged pool ({self.allocator.num_blocks} blocks) smaller "
                f"than one request's "
                f"{self.allocator.blocks_needed(need)}-block KV")
        had = len(self.allocator.tables.get(slot, ()))
        while not self.allocator.can_allocate(slot, need):
            # victim policy (§15): non-destructive valves first.
            # 1. the swap tier's own device holds — free to drop, the
            #    host copies remain authoritative;
            # 2. cold cached radix leaves — reclaiming costs a future
            #    re-prefill for NEW requests only;
            # 3. suspend a live request to the host tier — bounded added
            #    latency, zero recompute;
            # 4. destructive evict-and-requeue — last resort (tier off,
            #    tier full, or nothing swappable).
            missing = (self.allocator.blocks_needed(need)
                       - len(self.allocator.tables.get(slot, ())))
            if self.swap is not None \
                    and self.swap.release_device_holds(self.allocator):
                continue
            if self.prefix_cache is not None \
                    and self.prefix_cache.evict_until(missing):
                continue
            if self.swap is not None and self._swap_out_victim(exclude=slot):
                continue
            victim = self._pick_victim(exclude=slot)
            if victim is None:
                # fits the pool on paper but no victim to free: blocks are
                # held by a foreign seq on a shared allocator
                raise MemoryError(
                    "paged pool exhausted by sequences outside this engine")
            evicted.append(self._evict(victim))
        table = self.allocator.allocate(slot, need)
        a = self.active[slot]
        if len(table) != had and need > a["reserve_tokens"]:
            # this growth ran past the admission reservation: feed the
            # misprediction EWMA mid-flight (once per overflow block), so
            # an under-prediction storm raises the app's headroom before
            # its victims are even readmitted (§14)
            self.mispredict.observe(
                a["req"].app, a["reserve_g"],
                need - (a["reserve_tokens"] - a["reserve_g"]))
        # copy-on-write: any still-shared block at or past the write
        # cursor must be cloned before the window appends into it (the
        # clone needs a free block; cold cache leaves go first — and
        # evicting the leaf that *is* this block drops its refcount to 1,
        # making the clone unnecessary, which the loop re-checks)
        pairs: List[Tuple[int, int]] = []
        start = int(self.pos_host[slot]) // self.bt
        for idx in range(start, len(table)):
            while self.allocator.refcount.get(table[idx], 0) > 1 \
                    and not self.allocator.free:
                # same §15 valve order as the grow loop above; dropping a
                # tier hold on THIS block can also make the clone
                # unnecessary (refcount falls to 1), which the loop
                # re-checks
                if self.swap is not None \
                        and self.swap.release_device_holds(self.allocator):
                    continue
                if self.prefix_cache is not None \
                        and self.prefix_cache.evict_until(1):
                    continue
                if self.swap is not None \
                        and self._swap_out_victim(exclude=slot):
                    continue
                victim = self._pick_victim(exclude=slot)
                if victim is None:
                    raise MemoryError(
                        "paged pool exhausted by sequences outside this "
                        "engine")
                evicted.append(self._evict(victim))
            pair = self.allocator.cow_if_not_appendable(slot, idx)
            if pair is not None:
                pairs.append(pair)
                self.cow_copies += 1
        if len(table) != had or pairs:
            row = np.full(self.max_blocks, self.null_block, np.int32)
            row[:len(table)] = table
            self.tables = self.tables.at[slot].set(jnp.asarray(row))
        return pairs

    def _grow_draft(self, slot: int, evicted: List[Request]) -> None:
        """§16 counterpart of :meth:`_grow` for the slot's draft pool:
        ensure it can hold ``pos + spec_w`` tokens through the same
        pressure-valve escalation.  No COW loop — draft blocks are never
        shared (refcount 1 always), so growth is pure allocation."""
        seq = self._draft_seq(slot)
        need = int(self.pos_host[slot]) + self.spec_w
        had = len(self.allocator.tables.get(seq, ()))
        while not self.allocator.can_allocate(seq, need):
            missing = (self.allocator.blocks_needed(need)
                       - len(self.allocator.tables.get(seq, ())))
            if self.swap is not None \
                    and self.swap.release_device_holds(self.allocator):
                continue
            if self.prefix_cache is not None \
                    and self.prefix_cache.evict_until(missing):
                continue
            if self.swap is not None and self._swap_out_victim(exclude=slot):
                continue
            victim = self._pick_victim(exclude=slot)
            if victim is None:
                raise MemoryError(
                    "paged pool exhausted by sequences outside this engine")
            evicted.append(self._evict(victim))
        table = self.allocator.allocate(seq, need)
        if len(table) != had:
            row = np.full(self.max_blocks, self.null_block, np.int32)
            row[:len(table)] = table
            self.draft_tables = self.draft_tables.at[slot].set(
                jnp.asarray(row))

    # -- decode --------------------------------------------------------------

    def _window_steps(self) -> int:
        """Fusion-window length: the minimum over active slots of
        steps-to-finish and steps-to-block-boundary, so no finish / grow /
        evict event can fall inside the window (the §9 invariant)."""
        k = self.max_gen
        for slot, a in enumerate(self.active):
            if a is None:
                continue
            to_finish = a["target"] - len(a["generated"])
            cap = len(self.allocator.tables[slot]) * self.bt
            to_boundary = cap - int(self.pos_host[slot])
            k = min(k, to_finish, to_boundary)
        return max(k, 1)

    def _expire_deadlines(self) -> None:
        """Free every active slot past its deadline (checked between
        windows on the scheduler clock).  An expired request is a typed
        shed, not an eviction: its blocks are freed, the miss is counted,
        and it is NOT requeued (§14)."""
        for slot, a in enumerate(self.active):
            if a is None or a["deadline"] is None \
                    or self.clock < a["deadline"]:
                continue
            self.shed_log.append(Shed(a["req"], "deadline", self.clock))
            self.deadline_misses += 1
            self._unpin_prefix(slot)
            self.allocator.free_seq(slot)
            self._release(slot)

    def _grow_window(self, evicted: List[Request]) -> None:
        """The grow loop before a window: every active slot grows to hold
        the window's writes (:meth:`_grow`), its copy-on-write page copies
        applied at once; a failed grow raises :class:`PoolExhausted`."""
        with span("engine.grow") as g:
            cow0, grown = self.cow_copies, 0
            try:
                for slot, a in enumerate(self.active):
                    if a is None:
                        continue
                    had = len(self.allocator.tables[slot])
                    try:
                        pairs = self._grow(slot, evicted)
                    except MemoryError:
                        if self.faults is not None and self.faults.held_blocks:
                            # transient fault-held pool: evict the growing
                            # request itself (requeued by the caller) instead
                            # of failing the window — a pool_restore later in
                            # the plan lets it finish
                            evicted.append(self._evict(slot))
                            continue
                        raise
                    grown += len(self.allocator.tables[slot]) > had
                    # apply this slot's COW page copies IMMEDIATELY: a
                    # later slot's _grow may evict this one and recycle
                    # its clone block — deferring to one batched copy
                    # would scatter stale pages into the new owner
                    # (duplicate destinations, undefined winner), and a
                    # later MemoryError would leave the clone's table
                    # swap applied but its prefix KV never copied
                    if pairs:
                        npairs = _pow2_ceil(len(pairs))
                        src = np.full(npairs, self.null_block, np.int32)
                        dst = np.full(npairs, self.null_block, np.int32)
                        for i, (s, d) in enumerate(pairs):
                            src[i], dst[i] = s, d
                        self.pages = self._copy_pages(self.pages, src, dst)
                    if self.spec_decode and not a.get("draft_cold"):
                        # the slot's draft pool grows to the same pos+spec_w
                        # target through the same valves (after the COW
                        # copies above so an eviction here cannot recycle a
                        # clone source before its page copy ran)
                        try:
                            self._grow_draft(slot, evicted)
                        except MemoryError:
                            if self.faults is not None \
                                    and self.faults.held_blocks:
                                evicted.append(self._evict(slot))
                                continue
                            raise
            except MemoryError as e:
                # don't strand anything on a failed grow: requests evicted
                # earlier in this same step ride the typed exception for
                # requeue, and the culprit slot is freed (and attached) so
                # the engine stays serviceable and drainable after the raise
                culprit = (self._evict(slot)
                           if self.active[slot] is not None else None)
                raise PoolExhausted(str(e), evicted=tuple(evicted),
                                    culprit=culprit) from e
            g.set_metadata(grown=grown, cow=self.cow_copies - cow0)

    def step_window(self, max_steps: Optional[int] = None
                    ) -> Tuple[List[Request], List[Request], int]:
        """Run one fused decode window over all active requests.
        Returns (finished, evicted, steps_run); evicted requests must be
        requeued by the caller (they restart from scratch on readmit).

        Window prologue, host-side between windows (DESIGN.md §14):
        fault events due this window fire first (pool shrink/restore,
        logits poisoning, stalls), then deadlines are swept, then the
        NaN/Inf guard quarantines any poisoned slot — all before the
        grow loop, so surviving slots decode a window identical to the
        one a fault-free engine would run.  A stalled window burns
        scheduler-clock ticks and returns ``steps_run == 0`` without
        dispatching."""
        with span("engine.window") as win:
            self.windows += 1
            stalled = 0
            evicted: List[Request] = []
            if self.faults is not None:
                # the fault seam fires even with nothing active: a restore
                # event must be able to un-wedge an engine whose whole active
                # set was evicted by the matching shrink
                self._flush_publishes()
                stalled = self.faults.before_window(self)
                if stalled:
                    self.clock += stalled
                    self.stall_ticks += stalled
            if self.swap is not None and self._swapped:
                # suspended images first (§15): expire the hopeless, resume
                # whatever fits — BEFORE the idle check, or an engine whose
                # whole active set is suspended could never wake up
                self._expire_swapped()
                self._resume_swapped()
            if not any(a is not None for a in self.active):
                return [], [], 0
            # deferred radix publishes land here — between admission waves,
            # off the admission hot path, and before any grow/evict/finish
            # could free a queued span's blocks
            self._flush_publishes()
            self._expire_deadlines()
            if self._nan_guard and any(a is not None for a in self.active):
                # hotlint: sync(§14 NaN/Inf quarantine guard readback)
                finite = np.isfinite(np.asarray(self.logits)).all(axis=1)
                self.host_syncs += count_sync()
                for slot, a in enumerate(self.active):
                    if a is not None and not bool(finite[slot]):
                        # quarantine: clear the poisoned row (idle rows feed
                        # the fused argmax, masked) and evict for readmission
                        # — the restart re-prefills from the prompt, so the
                        # re-served stream stays bit-exact
                        self.logits = self.logits.at[slot].set(0.0)
                        evicted.append(self._evict(slot))
                        self.quarantined += 1
            if (self.spec_decode and self._nan_guard
                    and any(a is not None for a in self.active)):
                # §16 draft-health guard: a poisoned DRAFT must not kill the
                # request — verification is the correctness oracle — so the
                # guard ices the slot's draft permanently (proposals stop,
                # the stream continues at one verified token per window)
                # instead of evicting anything
                # hotlint: sync(§16 draft-health guard readback)
                dfinite = np.isfinite(
                    np.asarray(self.draft_logits)).all(axis=1)
                self.host_syncs += count_sync()
                for slot, a in enumerate(self.active):
                    if a is not None and not a.get("draft_cold") \
                            and not bool(dfinite[slot]):
                        self._quarantine_draft(slot)
            if stalled or not any(a is not None for a in self.active):
                self.window_stats = None
                return [], evicted, 0
            if self.faults is not None:
                # §17 crash seam: mid-window — prologue done (stalls burned,
                # deadlines swept, guards run), decode not yet dispatched
                self.faults.crash_due("window", self.windows)
            self._grow_window(evicted)
            if not any(a is not None for a in self.active):
                self.window_stats = None
                return [], evicted, 0
            shadow = getattr(self.allocator, "_shadow", None)
            if shadow is not None:
                # the window appends from each slot's write cursor: every
                # block at or past it must be privately owned (post-_grow COW)
                for slot, a in enumerate(self.active):
                    if a is not None:
                        t = self.allocator.tables[slot]
                        shadow.check_write(
                            slot, t[int(self.pos_host[slot]) // self.bt:])
                        if self.spec_decode and not a.get("draft_cold"):
                            dseq = self._draft_seq(slot)
                            dt = self.allocator.tables.get(dseq, [])
                            shadow.check_write(
                                dseq, dt[int(self.pos_host[slot]) // self.bt:])
            if self.spec_decode:
                finished, k = self._spec_window(max_steps)
                return finished, evicted, k
            k = self._window_steps()
            if max_steps is not None:
                k = max(1, min(k, max_steps))
            # power-of-two windows bound the jit cache at O(log G_max) entries
            k = _pow2_floor(k) if self.fuse else 1
            # post-grow/evict snapshot: lets drivers reconstruct the exact
            # per-iteration utilization ramp the per-token loop would sample
            # (live tokens += num_active per iteration; blocks fixed in-window)
            self.window_stats = {
                "live0": int(sum(int(self.pos_host[s])
                                 for s, a in enumerate(self.active)
                                 if a is not None)),
                "active": self.num_active,
                "used_tokens": self.allocator.used_blocks * self.bt,
            }
            win.set_metadata(k=k, rows=self.window_stats["active"])
            with span("engine.decode", k=k) as dec:
                if dec:
                    # the paged decode kernel's work: its pages a step,
                    # and the pages it walks at the window's first step
                    dec.set_metadata(
                        pages_per_step=self._pages_per_step,
                        live_pages=sum(-(-(int(self.pos_host[s]) + 1)
                                         // self.bt)
                                       for s, a in enumerate(self.active)
                                       if a is not None))
                self.logits, self.pages, self.positions, toks = \
                    self._decode_multi(
                        self.params, pages=self.pages,
                        batch={"logits": self.logits,
                               "positions": self.positions,
                               "block_tables": self.tables,
                               "active": self.active_mask},
                        num_steps=k)
            with span("engine.readback"):
                # hotlint: sync(the one window token readback — §9 fused)
                toks = np.asarray(toks)
                self.host_syncs += count_sync()
            self.decode_steps += k
            self.clock += k
            finished = self._retire(toks, k)
            return finished, evicted, k

    def _retire(self, toks: np.ndarray, k: int) -> List[Request]:
        """Append a window's ``k`` tokens per slot and retire the slots
        that reached their target: stream kept, misprediction EWMA fed,
        prefix unpinned, blocks freed, slot reset.  Returns the finished
        requests."""
        with span("engine.retire") as r:
            finished = []
            for slot, a in enumerate(self.active):
                if a is None:
                    continue
                a["generated"].extend(toks[slot, :k].tolist())
                self.pos_host[slot] += k
                if len(a["generated"]) >= a["target"]:
                    finished.append(a["req"])
                    self.generated[a["req"].req_id] = a["generated"]
                    # close the misprediction feedback loop (§14): observed
                    # generation length vs the reservation's predicted g
                    self.mispredict.observe(a["req"].app, a["reserve_g"],
                                            len(a["generated"]))
                    self._unpin_prefix(slot)
                    self.allocator.free_seq(slot)
                    self._release(slot)
            r.set_metadata(finished=len(finished))
        return finished

    def _quarantine_draft(self, slot: int) -> None:
        """Permanently ice a slot's draft (§16): free its draft pool,
        null its draft table row and clear the poisoned carry row.  The
        slot keeps serving — every window still emits its one verified
        token — and only a fresh admission builds a new draft."""
        self.allocator.free_seq(self._draft_seq(slot))
        self.draft_tables = self.draft_tables.at[slot].set(self._null_row)
        self.draft_logits = self.draft_logits.at[slot].set(0.0)
        self.active[slot]["draft_cold"] = True
        self.draft_quarantined += 1

    @hot_path
    def _spec_window(self, max_steps: Optional[int]
                     ) -> Tuple[List[Request], int]:
        """One speculative window (§16): the draft proposes ``spec_w``
        tokens per active slot in one fused dispatch, the target
        verifies all of them in ONE batched dispatch over the same
        positions, and the longest agreeing prefix is accepted on-device
        — the host reads back a single packed [tokens | accept-count]
        row per slot, the same one-sync-per-window budget as the §9
        fused window.  Rollback of the rejected tail is block-table
        truncation on both pools plus the position rewind the verify
        dispatch already applied on device; truncation never mutates a
        block — a trailing block the radix tree still holds only loses
        this slot's reference (COW rules apply to rollback too)."""
        w = self.spec_w
        max_emit = np.ones(self.slots, np.int32)
        for slot, a in enumerate(self.active):
            if a is None:
                continue
            e = min(a["target"] - len(a["generated"]), w)
            if max_steps is not None:
                e = min(e, max_steps)
            max_emit[slot] = max(e, 1)
        # post-grow/evict snapshot (same contract as the fused window):
        # drivers reconstruct the per-iteration utilization ramp from it
        self.window_stats = {
            "live0": int(sum(int(self.pos_host[s])
                             for s, a in enumerate(self.active)
                             if a is not None)),
            "active": self.num_active,
            "used_tokens": self.allocator.used_blocks * self.bt,
        }
        self.draft_logits, self.draft_pages, proposed = self._draft_window(
            self.draft_params, pages=self.draft_pages,
            batch={"target_logits": self.logits,
                   "logits": self.draft_logits,
                   "positions": self.positions,
                   "block_tables": self.draft_tables,
                   "active": self.active_mask},
            num_steps=w, target_vocab=self.cfg.vocab_size)
        (self.logits, self.pages, self.positions,
         packed) = self._verify_window(
            self.params, pages=self.pages,
            batch={"proposed": proposed, "logits": self.logits,
                   "positions": self.positions,
                   "block_tables": self.tables,
                   "active": self.active_mask, "max_emit": max_emit})
        # hotlint: sync(the one spec-window readback — §16 packed tokens + accept counts)
        packed = np.asarray(packed)
        self.host_syncs += count_sync()
        self.spec_windows += 1
        finished: List[Request] = []
        kmax = 0
        for slot, a in enumerate(self.active):
            if a is None:
                continue
            e = int(packed[slot, w])
            a["generated"].extend(packed[slot, :e].tolist())
            self.pos_host[slot] += e
            kmax = max(kmax, e)
            self.spec_slot_windows += 1
            self.spec_emitted += e
            self.spec_accepted += max(e - 1, 0)
            if not a.get("draft_cold"):
                # proposals clamped away by max_emit (finish boundary,
                # max_steps) were never candidates — counting them as
                # rejections would understate real draft quality
                self.spec_drafted += min(w - 1, int(max_emit[slot]) - 1)
            if len(a["generated"]) >= a["target"]:
                finished.append(a["req"])
                self.generated[a["req"].req_id] = a["generated"]
                self.mispredict.observe(a["req"].app, a["reserve_g"],
                                        len(a["generated"]))
                self._unpin_prefix(slot)
                self.allocator.free_seq(slot)
                self._release(slot)
                continue
            # rollback = truncation: both pools drop every block past the
            # accepted stream, floored at the admission reservation so
            # speculation cannot silently un-reserve the blocks the §13
            # admission control promised this request
            keep = max(
                self.allocator.blocks_needed(
                    max(int(self.pos_host[slot]), 1)),
                self.allocator.blocks_needed(int(a["reserve_tokens"])))
            self.allocator.truncate(slot, keep)
            self.allocator.truncate(self._draft_seq(slot), keep)
        self.decode_steps += kmax
        self.clock += kmax
        return finished, kmax

    def step(self) -> Tuple[List[Request], List[Request]]:
        """One decode iteration (a k=1 window); returns (finished,
        evicted).  Kept for callers that interleave per-token."""
        finished, evicted, _ = self.step_window(max_steps=1)
        return finished, evicted

    # -- warmup (recompile audit) --------------------------------------------

    def warmup(self, *, suffix_buckets: Optional[List[int]] = None,
               batch_sizes: Optional[List[int]] = None,
               windows: Optional[List[int]] = None) -> None:
        """Pre-compile the serve path: the variable-prefix wave at every
        (batch-bucket × suffix-bucket) shape and the fused decode at
        every power-of-two window, so a mixed-length workload triggers
        zero mid-serve compiles (see tests/test_recompile.py).

        The unified wave shrinks the §10 warmup grid: one entry point
        replaces the dense prefill, the suffix prefill, AND the
        per-shape eager-op ensemble each of them dragged along (page
        scatter, suffix scatter, COW page copy, four slot-state
        updates).  With the prefix cache on, each (batch, suffix) shape
        compiles twice — the width-1 null prefix-gather table a
        pure-miss wave uses and the full ``max_blocks`` table of a
        mixed/hit wave; with the cache off, only the width-1 variant
        exists.

        Wave warmup calls write nothing: ``write_lens == 0`` drops every
        scatter row, the COW pairs clone the null block onto itself, and
        the slot-state update runs against sacrificial copies of the
        slot arrays (the donated buffers must not be the engine's live
        state).  ``pages`` rides through donated-and-reassigned, its
        contents untouched."""
        if suffix_buckets is None:
            top = _bucket(self.max_len)
            suffix_buckets = [b for b in _BUCKETS if b <= top]
            nxt = _BUCKETS[-1] * 2          # pow2 tail for max_len > table
            while nxt <= top:
                suffix_buckets.append(nxt)
                nxt *= 2
            suffix_buckets = suffix_buckets or [top]
        if batch_sizes is None:
            batch_sizes, n = [], 1
            while n < self.slots:
                batch_sizes.append(n)
                n <<= 1
            batch_sizes.append(n)
        if windows is None:
            windows, k = [], 1
            while k <= max(self.max_gen, 1):
                windows.append(k)
                k <<= 1
        widths = [1] + ([self.max_blocks]
                        if self.prefix_cache is not None else [])
        for nb in batch_sizes:
            zeros = np.zeros(nb, np.int32)
            nulls = np.full(nb, self.null_block, np.int32)
            for sb in suffix_buckets:
                for w in widths:
                    # batch arrays are np, exactly like _dispatch_wave's
                    # staging: the jit cache keys on avals, so warmup and
                    # serve must build them identically
                    state = {"tables": jnp.array(self.tables),
                             "positions": jnp.array(self.positions),
                             "active": jnp.array(self.active_mask),
                             "logits": jnp.array(self.logits)}
                    self.pages, _ = self._prefill_wave(
                        self.params, pages=self.pages, state=state,
                        batch={"tokens": np.zeros((nb, sb), np.int32),
                               "lengths": np.ones(nb, np.int32),
                               "prefix_lens": zeros,
                               "attn_tables": np.full(
                                   (nb, w), self.null_block, np.int32),
                               "tables": np.full(
                                   (nb, self.max_blocks),
                                   self.null_block, np.int32),
                               "write_lens": zeros,
                               "cow_src": nulls,
                               "cow_dst": nulls,
                               "slots": zeros,
                               "row_sel": zeros,
                               "positions": zeros})
        # the int-indexed per-slot variants used by _release and _grow
        self.tables.at[0].set(self._null_row)
        self.positions.at[0].set(0)
        self.active_mask.at[0].set(False)
        if self.prefix_cache is not None:
            # grow-path COW copies pad to a power of two <= slots
            # (donated: null -> null clones leave the pool unchanged)
            k = 1
            while k <= _pow2_ceil(self.slots):
                nulls = np.full(k, self.null_block, np.int32)
                self.pages = self._copy_pages(self.pages, nulls, nulls)
                k <<= 1
        if self.swap is not None:
            # §15 swap transfers: gather/scatter at every power-of-two
            # block count an image can pad to, plus the resume path's
            # eager per-slot restores — a mid-storm suspension must not
            # compile anything
            pool = self.pages["k"]
            k = 1
            while k <= _pow2_ceil(self.max_blocks):
                blk = np.full(k, self.null_block, np.int32)
                self._gather_pages(self.pages, blk)
                vals = np.zeros((len(self.pages), pool.shape[0], k)
                                + tuple(pool.shape[2:]), pool.dtype)
                self.pages = self._scatter_pages(self.pages, blk, vals)
                k <<= 1
            # the fused slot restore _swap_in issues and the logits-row
            # readback _swap_out issues (np.int32-indexed: one compile
            # covers every slot at runtime).  The restore runs against
            # sacrificial copies — its arguments are donated
            s0 = np.int32(0)
            self.logits[s0]
            self._restore_slot(
                jnp.array(self.tables), jnp.array(self.positions),
                jnp.array(self.active_mask), jnp.array(self.logits),
                s0, np.full(self.max_blocks, self.null_block, np.int32),
                0, np.zeros(self.logits.shape[1], self.logits.dtype))
        if self.spec_decode:
            # §16 speculative path: the spec engine never dispatches the
            # plain fused window, so warm its shapes instead — the draft
            # admission/rebuild wave grid, one draft-window shape and one
            # verify-window shape.  All idle-mask: junk lands in the
            # null block and every emit count is 0.
            dtop = self.max_len + (self.max_gen if self.swap is not None
                                   else 0)   # resume re-prefills history
            dbuckets = [b for b in _BUCKETS if b <= _bucket(dtop)]
            nxt = _BUCKETS[-1] * 2
            while nxt <= _bucket(dtop):
                dbuckets.append(nxt)
                nxt *= 2
            dbuckets = dbuckets or [_bucket(dtop)]
            for nb in batch_sizes:
                zeros = np.zeros(nb, np.int32)
                nulls = np.full(nb, self.null_block, np.int32)
                for sb in dbuckets:
                    state = {"tables": jnp.array(self.draft_tables),
                             "positions": jnp.array(self.positions),
                             "active": jnp.array(self.active_mask),
                             "logits": jnp.array(self.draft_logits)}
                    self.draft_pages, _ = self._draft_prefill_wave(
                        self.draft_params, pages=self.draft_pages,
                        state=state,
                        batch={"tokens": np.zeros((nb, sb), np.int32),
                               "lengths": np.ones(nb, np.int32),
                               "prefix_lens": zeros,
                               "attn_tables": np.full(
                                   (nb, 1), self.null_block, np.int32),
                               "tables": np.full(
                                   (nb, self.max_blocks),
                                   self.null_block, np.int32),
                               "write_lens": zeros,
                               "cow_src": nulls,
                               "cow_dst": nulls,
                               "slots": zeros,
                               "row_sel": zeros,
                               "positions": zeros})
            self.draft_logits, self.draft_pages, proposed = \
                self._draft_window(
                    self.draft_params, pages=self.draft_pages,
                    batch={"target_logits": self.logits,
                           "logits": self.draft_logits,
                           "positions": self.positions,
                           "block_tables": self.draft_tables,
                           "active": self.active_mask},
                    num_steps=self.spec_w,
                    target_vocab=self.cfg.vocab_size)
            self.logits, self.pages, self.positions, _ = \
                self._verify_window(
                    self.params, pages=self.pages,
                    batch={"proposed": proposed, "logits": self.logits,
                           "positions": self.positions,
                           "block_tables": self.tables,
                           "active": self.active_mask,
                           "max_emit": np.ones(self.slots, np.int32)})
            # the eager per-row ops the draft guard / quarantine /
            # release paths issue
            self.draft_tables.at[0].set(self._null_row)
            self.draft_logits.at[0].set(0.0)
            return
        for k in windows:
            # pages are donated-and-reassigned (dropping them would delete
            # the live pool); logits/positions/tokens are discarded — an
            # idle-mask window only writes junk into the null block
            _, self.pages, _, _ = self._decode_multi(
                self.params, pages=self.pages,
                batch={"logits": self.logits, "positions": self.positions,
                       "block_tables": self.tables,
                       "active": self.active_mask},
                num_steps=k)

    def utilization(self) -> float:
        """1 - internal fragmentation over live tokens (null block counts
        as overhead)."""
        live = int(sum(int(self.pos_host[s])
                       for s, a in enumerate(self.active) if a is not None))
        return self.allocator.utilization(live)

    def assert_drained(self) -> None:
        """Teardown invariant (DESIGN.md §13): with every request finished
        or evicted, the only live allocation is the null block and every
        refcount is exactly explained by the tables + the radix cache's
        retained references.  Raises ``BlockLeakError`` otherwise.  Works
        with the sanitizer off — the check reads only the real allocator."""
        self._flush_publishes()
        _san.check_engine_drained(self)

    # -- crash-safe snapshot / restore (DESIGN.md §17) -----------------------

    @hot_path
    def snapshot(self, path: str) -> str:
        """Serialize the complete engine image to ``path`` (checksummed
        npz, written atomically).  Exactly TWO counted readbacks: one
        ``gather_pages`` over every live block of the pool (null block
        excluded — its contents are junk by construction) and one logits
        readback; everything else the snapshot stores is host state.
        Must be taken at a window boundary — mid-wave state
        (``_wave_pending``) and §16 speculative engines refuse."""
        from repro.serving import snapshot as snaplib
        if self.spec_decode:
            raise snaplib.SnapshotError(
                "snapshot/restore does not cover speculative engines (§16)")
        self._flush_publishes()
        if self._wave_pending:
            raise snaplib.SnapshotError(
                "snapshot inside an admission wave (wave_pending non-empty)")
        used = sorted(b for b in self.allocator.refcount
                      if b != self.null_block)
        vals = None
        if used:
            pad = _pow2_ceil(len(used))
            blk = np.full(pad, self.null_block, np.int32)
            blk[:len(used)] = used
            stacked = self._gather_pages(self.pages, blk)
            # hotlint: sync(§17 snapshot page readback — ONE gather for the whole pool image)
            vals = np.asarray(stacked)[:, :, :len(used)]
            self.host_syncs += count_sync()
        # hotlint: sync(§17 snapshot logits readback for bit-exact restore)
        logits = np.asarray(self.logits)
        self.host_syncs += count_sync()
        return snaplib.save_engine(self, path, page_blocks=used,
                                   page_values=vals, logits=logits)

    def restore(self, path: str) -> None:
        """Apply a snapshot to this freshly constructed engine: pages
        scattered back through the jitted ``scatter_pages``, allocator
        books overwritten wholesale (free-list order included), radix
        tree and swap tier rebuilt, counters/EWMAs/clock restored, and
        the §13 shadow REBUILT from the snapshot then cross-checked
        against the restored books.  Not a hot path — restore happens
        once, at process start."""
        from repro.serving import snapshot as snaplib
        snaplib.load_engine(self, path)


def drive_paged(engine: PagedContinuousEngine, requests: List[Request], *,
                max_steps: int = 2_000,
                refill=None, backlog=None,
                queue_cap: Optional[int] = None,
                max_retries: Optional[int] = None,
                stall_limit: int = 64,
                recovery=None) -> Dict[str, object]:
    """The canonical paged serve loop: batched admission until the engine
    refuses, fused decode windows, evictions requeued at the queue front.
    One implementation shared by the benchmark, the launcher, and the
    tests so they all measure the same serving discipline.

    ``refill(steps)`` (optional) is called whenever the local queue
    drains and may return more requests (an external scheduler's next
    admission wave); ``backlog()`` (optional) reports whether that
    scheduler still holds work, keeping the loop alive (idle-stepping,
    like the pre-refactor launcher) until the scheduler releases it.

    Robustness knobs (DESIGN.md §14) — all off by default, so the
    fault-free serving discipline is byte-identical to before:
    ``queue_cap`` bounds the local admission queue (overflow is shed with
    reason ``queue_full``); ``max_retries`` bounds evict/requeue cycles
    per request (exhaustion sheds with ``retry_budget`` — with the
    default ``None`` the engine instead escalates the reservation via
    its retry budget and serves the request); ``stall_limit`` bounds
    consecutive no-progress iterations before the queue head is shed
    with ``admission_stalled`` instead of hanging.  A ``PoolExhausted``
    window sheds the culprit with reason ``oom`` and requeues the rest.

    ``steps`` counts decode *iterations* (one generated token per active
    slot), not windows; ``util`` holds one sample per decode iteration
    (the in-window ramp is reconstructed from ``engine.window_stats``, so
    samples stay comparable across fuse settings and with the per-token
    loop); ``host_syncs`` is the device→host readback count.

    ``recovery`` (optional) is a §17 ``RecoveryManager``: every request
    is journaled write-ahead — BEFORE any engine work touches it — and
    finish/shed records are fsync'd at each window boundary, with a
    full snapshot every ``snapshot_every`` windows."""
    pending: Deque[Request] = deque(requests)
    served = steps = peak = evictions = no_progress = 0
    syncs0 = engine.host_syncs
    shed0 = len(engine.shed_log)

    def _shed(req: Request, reason: str) -> None:
        engine.shed_log.append(Shed(req, reason, engine.clock))

    if recovery is not None:
        recovery.attach(engine)
        for r in pending:
            recovery.on_admit(r, engine)
    if queue_cap is not None:
        while len(pending) > queue_cap:
            _shed(pending.pop(), "queue_full")
    util: List[float] = []
    while (pending or engine.num_active or engine.num_suspended
           or (backlog() if backlog is not None else False)) \
            and steps < max_steps:
        swap_ins0 = engine.swap_ins
        admitted = 0
        while True:
            n = engine.join_many(pending)
            admitted += n
            for _ in range(n):
                pending.popleft()
            if pending or refill is None:
                break                        # head does not fit / no source
            more = refill(steps)
            if not more:
                break
            pending.extend(more)
            if recovery is not None:
                for r in more:
                    recovery.on_admit(r, engine)
            if queue_cap is not None:
                while len(pending) > queue_cap:
                    _shed(pending.pop(), "queue_full")
        if not (pending or engine.num_active or engine.num_suspended
                or (backlog() if backlog is not None else False)):
            break
        peak = max(peak, engine.num_active)
        try:
            finished, evicted, k = engine.step_window(
                max_steps=max_steps - steps)
        except PoolExhausted as e:
            # typed degradation: the culprit is shed, in-window evictions
            # are requeued, and the loop keeps serving what fits
            if e.culprit is not None:
                _shed(e.culprit, "oom")
            evictions += len(e.evicted)
            for r in reversed(e.evicted):
                pending.appendleft(r)
            if recovery is not None:
                recovery.after_window(engine)
            steps += 1
            no_progress += 1
            continue
        served += len(finished)
        evictions += len(evicted)
        for r in reversed(evicted):
            if max_retries is not None \
                    and engine.retries.get(r.req_id, 0) > max_retries:
                _shed(r, "retry_budget")
            else:
                pending.appendleft(r)
        if recovery is not None:
            # §17 window boundary: fsync the WAL tail, maybe snapshot
            recovery.after_window(engine, finished)
        # reconstruct the per-iteration utilization ramp from the window's
        # post-grow snapshot: one fused window must not contribute a single
        # low-biased sample where k per-token steps contributed k ramping
        # ones.  The final sample is taken live (post-release), exactly
        # where the per-token loop sampled it at finish events.
        ws = engine.window_stats
        if k > 1 and ws is not None and ws["used_tokens"] > 0:
            util.extend((ws["live0"] + i * ws["active"]) / ws["used_tokens"]
                        for i in range(1, k))
        util.append(engine.utilization())
        steps += max(k, 1)
        # progress = admissions, finishes or swap-ins (a resume decodes
        # real tokens next window); eviction churn and stalled windows are
        # not progress.  A long decode stretch still counts k steps toward
        # max_steps, so stall-shedding only fires when the queue head can
        # never fit (e.g. a fault-shrunk pool)
        if admitted or finished or engine.swap_ins > swap_ins0:
            no_progress = 0
        elif not engine.num_active:
            no_progress += 1
            if no_progress >= stall_limit:
                if pending:
                    _shed(pending.popleft(), "admission_stalled")
                    no_progress = 0
                elif engine.num_suspended:
                    # a wedged pool with only suspended images left must
                    # degrade into a typed shed, never a hang (§15)
                    engine.shed_oldest_swapped()
                    no_progress = 0
    shed = list(engine.shed_log[shed0:])
    return {"served": served, "steps": steps, "peak": peak,
            "evictions": evictions, "util": util,
            "host_syncs": engine.host_syncs - syncs0,
            "unserved": list(pending),
            "shed": shed,
            "deadline_misses": engine.deadline_misses,
            "quarantined": engine.quarantined,
            "requeue_prefix_hits": engine.requeue_prefix_hits,
            "retries_max": max(engine.retries.values(), default=0),
            "swap_outs": engine.swap_outs,
            "swap_ins": engine.swap_ins,
            "reprefilled_swapped_tokens": engine.reprefilled_swapped_tokens,
            "replayed_reprefill_tokens": engine.replayed_reprefill_tokens,
            # §16 speculative decoding (all zero with spec off)
            "spec_windows": engine.spec_windows,
            "spec_emitted": engine.spec_emitted,
            "spec_accepted": engine.spec_accepted,
            "spec_drafted": engine.spec_drafted,
            "draft_quarantined": engine.draft_quarantined,
            "draft_prefill_tokens": engine.draft_prefill_tokens,
            "draft_reprefill_tokens": engine.draft_reprefill_tokens,
            # headline §16 metric: tokens emitted per TARGET dispatch row
            # (1.0 is the non-speculative baseline; > 1.0 means the
            # verify dispatch amortized accepted draft work)
            "accepted_per_dispatch": (
                engine.spec_emitted / engine.spec_slot_windows
                if engine.spec_slot_windows else 0.0),
            "acceptance_rate": (
                engine.spec_accepted / engine.spec_drafted
                if engine.spec_drafted else 0.0)}
