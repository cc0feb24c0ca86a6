"""Pure-jnp oracle for single-token decode attention with valid-length
masking (the paper's wasted-memory-access quantity lives in the masked
slots: a real engine still reads them from HBM)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """Gather-based oracle for the block-table kernel: pages
    [num_blocks, Hkv, block_tokens, D] are gathered through
    ``block_tables`` [B, max_blocks] into a dense [B, S, Hkv, D] view and
    fed to the dense oracle.  S = max_blocks * block_tokens; positions
    past ``lengths`` (including whole pad-table pages) are masked."""
    return decode_attention_ref(q, gather_dense(k_pages, block_tables),
                                gather_dense(v_pages, block_tables), lengths)


def gather_dense(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Head-major pages [num_blocks, Hkv, block_tokens, D] gathered
    through ``block_tables`` [B, M] into the dense token-major view
    [B, M * block_tokens, Hkv, D]."""
    b = block_tables.shape[0]
    _, hkv, _, d = pages.shape
    return pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(b, -1, hkv, d)


def paged_prefix_prefill_attention_ref(
        q: jax.Array, k_suf: jax.Array, v_suf: jax.Array,
        k_pages: jax.Array, v_pages: jax.Array, block_tables: jax.Array,
        prefix_lens: jax.Array, suffix_lens: jax.Array) -> jax.Array:
    """Gather-based oracle for suffix prefill against cached prefix pages.

    q, k_suf, v_suf: [B, S, H*, D] — the *suffix* tokens only, already
    rope'd at absolute positions ``prefix_lens[b] + i``; the pages hold
    the prefix KV at positions ``[0, prefix_lens[b])`` (written by an
    earlier instruction prefill).  ``block_tables`` [B, M] gathers the
    pages into a dense prefix view; each suffix query attends every valid
    prefix position (all strictly earlier) plus the suffix causally:
    score(q_i, k_j) is masked unless ``j < prefix_lens[b]`` (prefix part)
    or ``j - P <= i`` and ``j - P < suffix_lens[b]`` (suffix part, P the
    gathered prefix capacity).  Returns [B, S, Hq, D]."""
    b, s, hq, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    kp = gather_dense(k_pages, block_tables)
    vp = gather_dense(v_pages, block_tables)
    p_cap = kp.shape[1]
    k_cat = jnp.concatenate([kp, k_suf], axis=1).astype(jnp.float32)
    v_cat = jnp.concatenate([vp, v_suf], axis=1).astype(jnp.float32)
    q_idx = jnp.arange(s)
    kv_idx = jnp.arange(p_cap + s)
    in_prefix = kv_idx < p_cap
    prefix_ok = kv_idx[None, :] < prefix_lens[:, None]            # [B, K]
    suffix_ok = ((kv_idx[None, None, :] - p_cap <= q_idx[None, :, None])
                 & (kv_idx[None, :] - p_cap
                    < suffix_lens[:, None])[:, None, :])          # [B, S, K]
    mask = jnp.where(in_prefix[None, None, :],
                     prefix_ok[:, None, :], suffix_ok)            # [B, S, K]
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, s, hkv, g, d)
    sc = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k_cat)
    sc = jnp.where(mask[:, :, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v_cat)
    return o.reshape(b, s, hq, d).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, lengths: jax.Array) -> jax.Array:
    """q: [B, Hq, D]; caches: [B, S, Hkv, D]; lengths: [B] -> [B, Hq, D]."""
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) * d ** -0.5
    sc = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, hq, d).astype(q.dtype)
