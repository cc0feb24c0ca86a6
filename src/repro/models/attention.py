"""Attention: GQA prefill/train (blockwise online-softmax, memory-bounded),
single-token decode against a (possibly ring-buffer) KV cache.

The blockwise path is the production jnp implementation that XLA lowers for
TPU dry-runs; `repro.kernels.flash_attention` / `decode_attention` are the
Pallas TPU kernels for the same contractions (validated vs `ref.py` oracles).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _pick_chunk(s: int, target: int = 1024) -> int:
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def gqa_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool = True,
                          window: Optional[int] = None,
                          q_offset: int = 0,
                          kv_len: Optional[int] = None,
                          chunk: int = 1024) -> jax.Array:
    """Blockwise causal attention.

    q: [B, Sq, Hq, D]; k,v: [B, Sk, Hkv, D]; returns [B, Sq, Hq, D].
    Scans KV chunks with an online softmax so no [Sq, Sk] score matrix is
    ever materialized (required for the 32k prefill shapes).
    ``q_offset`` positions the queries inside the KV timeline (cross-chunk
    prefill); ``window`` enables sliding-window masking.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = d ** -0.5
    ck = _pick_chunk(sk, chunk)
    n_blocks = sk // ck

    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, g, d)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, i):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(k, i * ck, ck, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, i * ck, ck, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, ks.astype(jnp.float32))
        k_pos = i * ck + jnp.arange(ck)
        mask = jnp.ones((sq, ck), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        if kv_len is not None:
            mask &= k_pos[None, :] < kv_len
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bqhgk,bkhd->bqhgd", p, vs.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    # checkpoint the KV-block body: without this, autodiff stacks every
    # block's f32 score matrix as a scan residual (O(S^2) memory/traffic).
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_blocks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def gqa_decode_attention_cp(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, lengths: jax.Array, *,
                            mesh, batch_axes=("data",),
                            seq_axis: str = "model") -> jax.Array:
    """Context-parallel flash-decode via shard_map (beyond-paper §Perf).

    The KV cache is sequence-sharded over ``seq_axis``; instead of letting
    XLA all-gather the [B, H, S] score tensor for the softmax, every shard
    computes a *local* online-softmax partial (max, sum-exp, weighted sum)
    over its cache slice and the partials merge with one pmax + two psums
    of [B, H, D]-sized tensors — the TPU analogue of flash-decoding's
    split-KV reduction.
    """
    from jax.sharding import PartitionSpec as P

    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = d ** -0.5
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = dims[seq_axis]
    local_s = s // n_shards
    ba = tuple(a for a in batch_axes if a in dims)
    if ba and b % int(np.prod([dims[a] for a in ba])) == 0:
        bspec = ba[0] if len(ba) == 1 else ba
    else:
        bspec = None

    def local(q_l, k_l, v_l, len_l):
        qf = (q_l.astype(jnp.float32) * scale).reshape(-1, hkv, g, d)
        sc = jnp.einsum("bhgd,bkhd->bhgk", qf, k_l.astype(jnp.float32))
        off = jax.lax.axis_index(seq_axis) * local_s
        idx = off + jnp.arange(local_s)[None, :]
        valid = idx < len_l[:, None]
        sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
        m_l = sc.max(axis=-1)                             # [b,hkv,g]
        p = jnp.exp(sc - m_l[..., None])
        l_l = p.sum(axis=-1)
        acc = jnp.einsum("bhgk,bkhd->bhgd", p, v_l.astype(jnp.float32))
        # merge partials across the sequence shards
        m = jax.lax.pmax(m_l, seq_axis)
        corr = jnp.exp(m_l - m)
        l = jax.lax.psum(l_l * corr, seq_axis)
        out = jax.lax.psum(acc * corr[..., None], seq_axis)
        out = out / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(-1, 1, hq, d).astype(q_l.dtype)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec, seq_axis), P(bspec, seq_axis),
                  P(bspec)),
        out_specs=P(bspec),
        check_vma=False)
    return f(q, k_cache, v_cache, lengths)


def gqa_decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array, *,
                         window: Optional[int] = None,
                         positions: Optional[jax.Array] = None) -> jax.Array:
    """One-token attention against a KV cache with per-request valid lengths.

    q: [B, 1, Hq, D]; caches: [B, S, Hkv, D]; lengths: [B] (#valid cache
    entries per request — padded/waiting slots beyond it are masked, which is
    exactly the paper's wasted-memory-access quantity when they are *not*
    maskable on real reads).  For ring-buffer (sliding window) caches the
    whole buffer is valid once wrapped; masking handles the warmup.
    """
    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = d ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    idx = jnp.arange(s)[None, :]                       # [1, S]
    valid = idx < lengths[:, None]
    if window is not None:
        valid &= idx >= (lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)
