"""jit'd public wrappers.  The paged kernels (the serving path) choose
between the Pallas kernel and the gather oracle by the platform the
program is *lowered* for (``lax.platform_dependent``): a TPU program
always holds the kernel, a CPU program always the oracle, whatever
device the process defaults to.  The dense kernels run in interpret mode
off the TPU."""
from __future__ import annotations

import functools

import jax

from repro.analysis.sanitizer import hot_path
from repro.kernels.decode_attention.kernel import (
    decode_attention_int8_kernel, decode_attention_kernel,
    paged_decode_attention_kernel, paged_prefix_prefill_attention_kernel)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, paged_decode_attention_ref,
    paged_prefix_prefill_attention_ref)


@functools.partial(jax.jit, static_argnames=("block_k", "use_ref"))
@hot_path
def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 512,
                     use_ref: bool = False):
    if use_ref:
        return decode_attention_ref(q, k_cache, v_cache, lengths)
    interpret = jax.devices()[0].platform != "tpu"
    return decode_attention_kernel(q, k_cache, v_cache, lengths,
                                   block_k=block_k, interpret=interpret)


def paged_decode_attention_impl(q, k_pages, v_pages, block_tables, lengths,
                                *, use_ref: bool = False):
    """Un-jitted dispatch for block-table paged decode attention.

    Fused multi-step decode (``models.transformer.decode_multi_paged``)
    calls this from inside an already-traced ``lax.scan`` body: the jit
    cache then stays keyed at the *engine's* fused entry point — one
    entry per (batch shape, pool shape, window length) — instead of
    paying a nested jit-cache lookup per inner step and per trace.
    Direct (eager) callers should use :func:`paged_decode_attention`."""
    args = (q, k_pages, v_pages, block_tables, lengths)
    if use_ref:
        return paged_decode_attention_ref(*args)
    return jax.lax.platform_dependent(*args,
                                      tpu=paged_decode_attention_kernel,
                                      default=paged_decode_attention_ref)


@functools.partial(jax.jit, static_argnames=("use_ref",))
@hot_path
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           use_ref: bool = False):
    """Block-table paged decode attention (shared page pool; per-request
    tables).  ``use_ref`` or a program lowered for any platform but the
    TPU takes the gather-based oracle.

    The TPU kernel walks each row's live pages only, ``ceil(length /
    block_tokens)`` of them: a scalar-core schedule lists every row's
    chunks of ``P`` pages, and the kernel takes one grid step a chunk,
    each page one DMA of its ``[Hkv, block_tokens, D]`` slab (all KV
    heads), double-buffered so the next chunk's pages are in flight while
    this one computes.  ``P`` is :func:`~repro.kernels.decode_attention.
    kernel.decode_pages_per_step` of the page's bytes and the table width:
    the largest power of two whose K pages fit a fixed VMEM share, at most
    ``max_blocks``.  Pages past a row's length are never copied, so the
    cost follows the live pages, not ``B x max_blocks``."""
    return paged_decode_attention_impl(q, k_pages, v_pages, block_tables,
                                       lengths, use_ref=use_ref)


def paged_prefix_prefill_attention_impl(q, k_suf, v_suf, k_pages, v_pages,
                                        block_tables, prefix_lens,
                                        suffix_lens, *,
                                        use_ref: bool = False):
    """Un-jitted dispatch for variable-prefix suffix-prefill attention.

    ``prefix_lens`` is per-row and may be 0 — the single-dispatch
    admission wave (DESIGN.md §12) runs radix misses and hits through
    one call; a pure-miss wave passes a width-1 null ``block_tables`` so
    neither backend streams dead prefix pages.  Called from inside the
    already-traced ``models.transformer`` layer scan (same rationale as
    :func:`paged_decode_attention_impl`: the jit cache stays keyed at the
    engine's entry point).  Direct callers should use
    :func:`paged_prefix_prefill_attention`."""
    args = (q, k_suf, v_suf, k_pages, v_pages, block_tables, prefix_lens,
            suffix_lens)
    if use_ref:
        return paged_prefix_prefill_attention_ref(*args)
    return jax.lax.platform_dependent(
        *args, tpu=paged_prefix_prefill_attention_kernel,
        default=paged_prefix_prefill_attention_ref)


@functools.partial(jax.jit, static_argnames=("use_ref",))
@hot_path
def paged_prefix_prefill_attention(q, k_suf, v_suf, k_pages, v_pages,
                                   block_tables, prefix_lens, suffix_lens,
                                   *, use_ref: bool = False):
    """Suffix-prefill attention against cached prefix pages (shared
    instruction KV; per-request tables).  ``use_ref`` or a program
    lowered for any platform but the TPU takes the gather-based
    oracle."""
    return paged_prefix_prefill_attention_impl(
        q, k_suf, v_suf, k_pages, v_pages, block_tables, prefix_lens,
        suffix_lens, use_ref=use_ref)


@functools.partial(jax.jit, static_argnames=("block_k",))
@hot_path
def decode_attention_int8(q, k_cache, v_cache, k_scale, v_scale, lengths, *,
                          block_k: int = 512):
    """int8-KV-cache decode attention (in-VMEM dequant; §Perf cache_int8)."""
    interpret = jax.devices()[0].platform != "tpu"
    return decode_attention_int8_kernel(q, k_cache, v_cache, k_scale,
                                        v_scale, lengths, block_k=block_k,
                                        interpret=interpret)
