"""Decoder-only transformer covering the dense / moe / vlm / hybrid / ssm
families, with three lowered entry points:

- ``forward_train``  : full-sequence logits (+ MoE aux, + MTP loss inputs)
- ``prefill``        : full-sequence pass that also returns the decode cache
- ``decode_step``    : one token against the cache (KV, MLA-latent, or SSM
                       state; ring-buffer for sliding-window attention)

Layers are stacked on a leading ``layers`` axis and executed with
``lax.scan`` so the HLO stays compact for 48-61 layer configs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (ParamSpec, apply_rope, axes_of, is_spec,
                                 materialize, mlp_spec, rms_norm, swiglu)
from repro.partitioning import constrain


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _attn_spec(cfg: ModelConfig, dtype) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hq = max(hq, cfg.pad_heads_to)   # shardability padding (zero heads)
    spec = {
        "wq": ParamSpec((d, hq, hd), ("embed", "q_heads", "head_dim"), dtype=dtype),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": ParamSpec((hq, hd, d), ("q_heads", "head_dim", "embed"), dtype=dtype),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamSpec((hq, hd), ("q_heads", "head_dim"), init="zeros", dtype=dtype)
        spec["bk"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
        spec["bv"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
    return spec


def _block_spec(cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    spec: Dict[str, Any] = {
        "norm1": ParamSpec((d,), ("embed",), init="ones", dtype=dtype),
    }
    if cfg.family == "ssm":
        spec["mamba"] = ssm_lib.mamba_spec(d, cfg.ssm, dtype=dtype)
        return spec
    # attention sub-layer
    if cfg.uses_mla:
        spec["mla"] = mla_lib.mla_spec(d, cfg.num_heads, cfg.mla, dtype=dtype)
    else:
        spec["attn"] = _attn_spec(cfg, dtype)
    if cfg.family == "hybrid":
        d_inner = cfg.ssm.expand * d // 2
        spec["mamba"] = ssm_lib.mamba_spec(d, cfg.ssm, d_inner=d_inner, dtype=dtype)
    # ffn sub-layer
    spec["norm2"] = ParamSpec((d,), ("embed",), init="ones", dtype=dtype)
    if cfg.moe is not None:
        spec["moe"] = moe_lib.moe_spec(d, cfg.moe, dtype=dtype)
    else:
        spec["mlp"] = mlp_spec(d, cfg.d_ff, dtype=dtype)
    return spec


def _stack(spec_tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.scale, s.dtype),
        spec_tree, is_leaf=is_spec)


def model_spec(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    spec: Dict[str, Any] = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), scale=1.0, dtype=dtype),
        "blocks": _stack(_block_spec(cfg, dtype), cfg.num_layers),
        "final_norm": ParamSpec((d,), ("embed",), init="ones", dtype=dtype),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((d, v), ("embed", "vocab"), dtype=dtype)
    if cfg.family == "vlm":
        spec["projector"] = ParamSpec((d, d), ("embed", "embed_out"), dtype=dtype)
    if cfg.mtp_depth:
        spec["mtp"] = {
            "proj": ParamSpec((2 * d, d), ("embed", "embed_out"), dtype=dtype),
            "block": _block_spec(cfg, dtype),
            "norm_h": ParamSpec((d,), ("embed",), init="ones", dtype=dtype),
            "norm_e": ParamSpec((d,), ("embed",), init="ones", dtype=dtype),
        }
    return spec


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    return materialize(model_spec(cfg, dtype), key)


def param_axes(cfg: ModelConfig, dtype=jnp.float32):
    return axes_of(model_spec(cfg, dtype))


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _attention(ap: dict, x, cfg: ModelConfig, positions, *, rules,
               window, q_offset: int = 0):
    """Full-sequence GQA attention; returns (out, (k, v))."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None), rules)
    out = attn_lib.gqa_prefill_attention(q, k, v, causal=True, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, ap["wo"]), (k, v)


def _quant_i8(t):
    """Symmetric int8 quant over the head_dim axis: t [B,1,H,D] ->
    (int8 values, bf16 scales [B,1,H])."""
    sc = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    sc = jnp.maximum(sc, 1e-8)
    q = jnp.round(t.astype(jnp.float32) / sc[..., None])
    return q.astype(jnp.int8), sc.astype(jnp.bfloat16)


def _attention_decode(ap: dict, x, cfg: ModelConfig, kv_cache, lengths,
                      positions, *, rules, window):
    """One-token GQA attention; returns (out, new (k, v) cache).
    With ``cfg.cache_int8`` the cache is (k_i8, v_i8, k_scale, v_scale)."""
    int8 = cfg.cache_int8
    if int8:
        k_cache, v_cache, k_sc, v_sc = kv_cache
    else:
        k_cache, v_cache = kv_cache
    s_cache = k_cache.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k = apply_rope(k, positions[:, None], cfg.rope_theta)
    slot = positions % s_cache                            # ring when windowed
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, 0))
    if int8:
        k_q, k_s = _quant_i8(k)
        v_q, v_s = _quant_i8(v)
        k_cache = upd(k_cache, k_q, slot)
        v_cache = upd(v_cache, v_q, slot)
        k_sc = upd(k_sc, k_s, slot)
        v_sc = upd(v_sc, v_s, slot)
        k_deq = k_cache.astype(jnp.bfloat16) * k_sc[..., None]
        v_deq = v_cache.astype(jnp.bfloat16) * v_sc[..., None]
    else:
        k_cache = upd(k_cache, k.astype(k_cache.dtype), slot)
        v_cache = upd(v_cache, v.astype(v_cache.dtype), slot)
        k_deq, v_deq = k_cache, v_cache
    valid = jnp.minimum(positions + 1, s_cache)
    mesh = (rules or {}).get("_mesh")
    if (cfg.decode_cp and mesh is not None
            and "model" in mesh.axis_names
            and s_cache % dict(zip(mesh.axis_names,
                                   mesh.devices.shape))["model"] == 0):
        batch_axes = (rules or {}).get("cache_batch", ("data",))
        out = attn_lib.gqa_decode_attention_cp(
            q, k_deq, v_deq, valid, mesh=mesh, batch_axes=batch_axes)
    else:
        out = attn_lib.gqa_decode_attention(q, k_deq, v_deq, valid)
    new_cache = (k_cache, v_cache, k_sc, v_sc) if int8 \
        else (k_cache, v_cache)
    return jnp.einsum("bshk,hkd->bsd", out, ap["wo"]), new_cache


def _ffn(bp: dict, x, cfg: ModelConfig, rules):
    h = rms_norm(x, bp["norm2"], cfg.norm_eps)
    if cfg.moe is not None:
        if cfg.moe_ragged:
            y, aux = moe_lib.moe_forward_ragged(bp["moe"], h, cfg.moe,
                                                rules=rules)
        else:
            y, aux = moe_lib.moe_forward(bp["moe"], h, cfg.moe, rules=rules,
                                         group_size=cfg.moe_group_size)
        return x + y, aux
    y = swiglu(h, bp["mlp"]["gate"], bp["mlp"]["up"], bp["mlp"]["down"])
    y = constrain(y, ("act_batch", "act_seq", "act_embed"), rules)
    return x + y, jnp.float32(0.0)


def block_forward(bp: dict, x, cfg: ModelConfig, positions, *,
                  rules=None, window=None, collect_cache: bool = False):
    """Full-sequence block. Returns (x, aux, cache_slice|None)."""
    h = rms_norm(x, bp["norm1"], cfg.norm_eps)
    cache = None
    if cfg.family == "ssm":
        if collect_cache:
            y, cache = ssm_lib.mamba_forward(
                bp["mamba"], h, cfg.ssm, cfg.ssm.d_inner(cfg.d_model),
                return_state=True)
        else:
            y = ssm_lib.mamba_forward(bp["mamba"], h, cfg.ssm,
                                      cfg.ssm.d_inner(cfg.d_model))
        return x + y, jnp.float32(0.0), (
            {"ssm": cache} if cache is not None else None)
    if cfg.uses_mla:
        y, kv = mla_lib.mla_prefill(bp["mla"], h, cfg.mla, cfg.num_heads,
                                    positions, cfg.rope_theta)
    else:
        y, kv = _attention(bp["attn"], h, cfg, positions, rules=rules,
                           window=window)
    if cfg.family == "hybrid":
        d_inner = cfg.ssm.expand * cfg.d_model // 2
        if collect_cache:
            ym, sstate = ssm_lib.mamba_forward(bp["mamba"], h, cfg.ssm,
                                               d_inner, return_state=True)
        else:
            ym = ssm_lib.mamba_forward(bp["mamba"], h, cfg.ssm, d_inner)
            sstate = None
        y = (y + ym) * 0.5
        cache = {"kv": kv, "ssm": sstate} if collect_cache else None
    elif collect_cache:
        cache = {"kv": kv}
    x = x + y
    x, aux = _ffn(bp, x, cfg, rules)
    return x, aux, cache


def block_decode(bp: dict, x, cfg: ModelConfig, cache, lengths, positions,
                 *, rules=None, window=None):
    """One-token block. Returns (x, new_cache_slice)."""
    h = rms_norm(x, bp["norm1"], cfg.norm_eps)
    new_cache = dict(cache) if isinstance(cache, dict) else {}
    if cfg.family == "ssm":
        y, sstate = ssm_lib.mamba_decode(bp["mamba"], h, cfg.ssm,
                                         cfg.ssm.d_inner(cfg.d_model),
                                         cache["ssm"])
        x = x + y
        return x, {"ssm": sstate}
    if cfg.uses_mla:
        y, kv = mla_lib.mla_decode(bp["mla"], h, cfg.mla, cfg.num_heads,
                                   cache["kv"], lengths, positions,
                                   cfg.rope_theta)
    else:
        y, kv = _attention_decode(bp["attn"], h, cfg, cache["kv"], lengths,
                                  positions,
                                  rules=rules, window=window)
    new_cache["kv"] = kv
    if cfg.family == "hybrid":
        ym, sstate = ssm_lib.mamba_decode(bp["mamba"], h, cfg.ssm,
                                          cfg.ssm.expand * cfg.d_model // 2,
                                          cache["ssm"])
        y = (y + ym) * 0.5
        new_cache["ssm"] = sstate
    x = x + y
    x, _ = _ffn(bp, x, cfg, rules)
    return x, new_cache


# ---------------------------------------------------------------------------
# Full model entry points
# ---------------------------------------------------------------------------

_KEEP_F32 = {"A_log", "D", "dt_bias", "router"}


def cast_params(tree, dtype):
    """Cast float weights to the compute dtype (mixed-precision at-use cast);
    SSM decay/router parameters stay f32 for numerical stability."""
    def c(path, w):
        last = path[-1]
        name = getattr(last, "key", None) or str(last)
        if name in _KEEP_F32 or not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        return w.astype(dtype)
    return jax.tree_util.tree_map_with_path(c, tree)


def _embed_in(params, cfg: ModelConfig, tokens, patches=None,
              act_dtype=jnp.bfloat16):
    x = jnp.take(params["embed"], tokens, axis=0).astype(act_dtype)
    if cfg.family == "vlm" and patches is not None:
        proj = (patches.astype(act_dtype) @ params["projector"].astype(act_dtype))
        x = jnp.concatenate([proj, x], axis=1)
    return x


def _logits(params, cfg: ModelConfig, x, rules):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    return constrain(logits, ("act_batch", "act_seq", "act_vocab"), rules)


def forward_train(params, cfg: ModelConfig, tokens, *, patches=None,
                  rules=None, act_dtype=jnp.bfloat16, remat: bool = True):
    """tokens: [B, S] -> (logits [B, S', V], aux_loss, hidden [B, S', d])."""
    params = cast_params(params, act_dtype)
    x = _embed_in(params, cfg, tokens, patches, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)
    s = x.shape[1]
    positions = jnp.arange(s)

    def body(carry, bp):
        h, aux = carry
        h, a, _ = block_forward(bp, h, cfg, positions, rules=rules,
                                window=cfg.sliding_window)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return (h, aux + a), None

    f = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if (remat and cfg.remat_mode != "none") else body
    (x, aux), _ = jax.lax.scan(f, (x, jnp.float32(0.0)), params["blocks"])
    return _logits(params, cfg, x, rules), aux, x


def cross_entropy(logits, targets, mask=None):
    """Gather-free CE: lse(logits) - logits[target] via a one-hot einsum,
    so a vocab-sharded logits tensor never gets all-gathered and no f32
    [B,S,V] log-softmax copy materializes."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    oh = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    correct = jnp.einsum("bsv,bsv->bs", logits, oh).astype(jnp.float32)
    ce = lse - correct
    if mask is not None:
        return (ce * mask).sum() / jnp.maximum(mask.sum() * ce.shape[0]
                                               / mask.shape[0], 1.0)
    return ce.mean()


def lm_loss(params, cfg: ModelConfig, tokens, *, patches=None, rules=None,
            act_dtype=jnp.bfloat16, mtp_coef: float = 0.3):
    """Next-token CE (+ MoE aux + MTP). tokens: [B, S]; labels = shifted."""
    logits, aux, hidden = forward_train(params, cfg, tokens, patches=patches,
                                        rules=rules, act_dtype=act_dtype)
    if cfg.family == "vlm":       # drop patch positions
        logits = logits[:, -tokens.shape[1]:]
        hidden = hidden[:, -tokens.shape[1]:]
    ce = cross_entropy(logits[:, :-1], tokens[:, 1:])
    loss = ce + aux
    if cfg.mtp_depth:
        # MTP over the full (padded) sequence so the token count matches the
        # main stack's sharding/grouping; the tail positions are masked out.
        mp = params["mtp"]
        h = rms_norm(hidden, mp["norm_h"], cfg.norm_eps)
        shifted = jnp.roll(tokens, -1, axis=1)          # t+1 ids (tail junk)
        e = rms_norm(
            jnp.take(params["embed"], shifted, axis=0).astype(h.dtype),
            mp["norm_e"], cfg.norm_eps)
        hm = jnp.concatenate([h, e], axis=-1) @ mp["proj"].astype(h.dtype)
        hm = constrain(hm, ("act_batch", "act_seq", "act_embed"), rules)
        pos = jnp.arange(hm.shape[1])
        hm, _, _ = block_forward(mp["block"], hm, cfg, pos, rules=rules,
                                 window=cfg.sliding_window)
        mtp_logits = _logits(params, cfg, hm, rules)
        mtp_tgt = jnp.roll(tokens, -2, axis=1)
        mask = (jnp.arange(tokens.shape[1]) < tokens.shape[1] - 2)
        mtp_ce = cross_entropy(mtp_logits, mtp_tgt,
                               mask=mask[None, :].astype(jnp.float32))
        loss = loss + mtp_coef * mtp_ce
    return loss, {"ce": ce, "aux": aux}


def _fit_cache(leaf, s: int, cache_len: int):
    """Grow (pad) or ring-pack (roll last W) a stacked cache leaf whose seq
    dim is axis 2 ([L, B, S, ...])."""
    if cache_len == s:
        return leaf
    if cache_len > s:
        pad = [(0, 0)] * leaf.ndim
        pad[2] = (0, cache_len - s)
        return jnp.pad(leaf, pad)
    # ring-pack: position p lives at slot p % W (uniform padded length S)
    last = jax.lax.slice_in_dim(leaf, s - cache_len, s, axis=2)
    return jnp.roll(last, s % cache_len, axis=2)


def prefill(params, cfg: ModelConfig, tokens, lengths, *, patches=None,
            rules=None, act_dtype=jnp.bfloat16, cache_len=None):
    """Build the decode cache. tokens: [B, S] (right-padded to S), lengths:
    [B] valid counts. Returns (next-token logits [B, V], cache pytree).
    ``cache_len`` sets the decode cache capacity (>=S pads; <S ring-packs,
    for sliding-window archs)."""
    params = cast_params(params, act_dtype)
    x = _embed_in(params, cfg, tokens, patches, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)
    s = x.shape[1]
    positions = jnp.arange(s)

    def body(h, bp):
        h, _, cache = block_forward(bp, h, cfg, positions, rules=rules,
                                    window=cfg.sliding_window,
                                    collect_cache=True)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return h, cache

    x, cache = jax.lax.scan(body, x, params["blocks"])
    if cache_len is not None and cache and "kv" in cache:
        cache["kv"] = tuple(_fit_cache(c, s, cache_len) for c in cache["kv"])
    logits = _logits(params, cfg, x, rules)
    if cfg.family == "vlm":
        offs = cfg.num_patches
    else:
        offs = 0
    last = jnp.take_along_axis(
        logits, (offs + lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, cache


def decode_step(params, cfg: ModelConfig, cache, tokens, positions, *,
                rules=None, act_dtype=jnp.bfloat16,
                window: Optional[int] = None):
    """tokens: [B] new token ids; positions: [B] absolute positions.
    Returns (logits [B, V], updated cache). ``positions`` are text-relative;
    VLM caches hold the patch prefix, so the patch offset is added here."""
    params = cast_params(params, act_dtype)
    if cfg.family == "vlm":
        positions = positions + cfg.num_patches
    x = _embed_in(params, cfg, tokens[:, None], None, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)
    win = window if window is not None else cfg.sliding_window
    lengths = positions  # cache holds `positions` entries before this token

    def body(h, xs):
        bp, cache_l = xs
        h, new_cache = block_decode(bp, h, cfg, cache_l, lengths, positions,
                                    rules=rules, window=win)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return h, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    logits = _logits(params, cfg, x, rules)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Cache construction (shapes + logical axes for sharding / dry-runs)
# ---------------------------------------------------------------------------

def cache_struct(cfg: ModelConfig, batch: int, seq: int,
                 dtype=jnp.bfloat16) -> Tuple[Any, Any]:
    """Returns (ShapeDtypeStruct pytree, logical-axes pytree) of the decode
    cache. ``seq`` is the cache capacity (window size for SWA archs)."""
    l = cfg.num_layers
    entry_shapes: Dict[str, Any] = {}
    entry_axes: Dict[str, Any] = {}
    if cfg.family != "ssm":
        if cfg.uses_mla:
            m = cfg.mla
            kv_shapes = (
                jax.ShapeDtypeStruct((l, batch, seq, m.kv_lora_rank), dtype),
                jax.ShapeDtypeStruct((l, batch, seq, m.qk_rope_dim), dtype))
            kv_axes = (("layers", "cache_batch", "kv_seq", None),
                       ("layers", "cache_batch", "kv_seq", None))
        elif cfg.cache_int8:
            kv_shape = (l, batch, seq, cfg.num_kv_heads, cfg.head_dim)
            sc_shape = (l, batch, seq, cfg.num_kv_heads)
            kv_shapes = (jax.ShapeDtypeStruct(kv_shape, jnp.int8),
                         jax.ShapeDtypeStruct(kv_shape, jnp.int8),
                         jax.ShapeDtypeStruct(sc_shape, jnp.bfloat16),
                         jax.ShapeDtypeStruct(sc_shape, jnp.bfloat16))
            ax = ("layers", "cache_batch", "kv_seq", "cache_heads", None)
            ax_sc = ("layers", "cache_batch", "kv_seq", "cache_heads")
            kv_axes = (ax, ax, ax_sc, ax_sc)
        else:
            kv_shape = (l, batch, seq, cfg.num_kv_heads, cfg.head_dim)
            kv_shapes = (jax.ShapeDtypeStruct(kv_shape, dtype),
                         jax.ShapeDtypeStruct(kv_shape, dtype))
            ax = ("layers", "cache_batch", "kv_seq", "cache_heads", None)
            kv_axes = (ax, ax)
        entry_shapes["kv"] = kv_shapes
        entry_axes["kv"] = kv_axes
    if cfg.family in ("ssm", "hybrid"):
        d_inner = (cfg.ssm.d_inner(cfg.d_model) if cfg.family == "ssm"
                   else cfg.ssm.expand * cfg.d_model // 2)
        shapes, axes = ssm_lib.mamba_state_spec(cfg, batch, d_inner)
        entry_shapes["ssm"] = tuple(
            jax.ShapeDtypeStruct((l,) + s, jnp.float32) for s in shapes)
        entry_axes["ssm"] = tuple(("layers",) + a for a in axes)
    return entry_shapes, entry_axes


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    shapes, _ = cache_struct(cfg, batch, seq, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


# ---------------------------------------------------------------------------
# Paged decode: shared physical block pool + per-request block tables
# (serving.PagedContinuousEngine; DESIGN.md §8)
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> Tuple[bool, str]:
    """Paged decode covers the plain-GQA KV families; the exotic cache
    layouts (MLA latents, SSM states, int8 pairs, SWA rings) keep the
    dense path."""
    if cfg.family not in ("dense", "moe"):
        return False, f"family {cfg.family} has no paged cache layout"
    if cfg.uses_mla:
        return False, "MLA latent caches are not paged"
    if cfg.cache_int8:
        return False, "int8 (value, scale) caches are not paged"
    if cfg.sliding_window is not None:
        return False, "sliding-window ring caches are not paged"
    hq = max(cfg.num_heads, cfg.pad_heads_to)
    if hq % cfg.num_kv_heads:
        return False, "padded q-heads not a multiple of kv-heads"
    return True, ""


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_tokens: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """One K and one V pool per layer: [L, num_blocks, Hkv,
    block_tokens, D].  Block ids index axis 1; every request addresses
    the same physical block id across all layers (one table, L pools).
    Pages are head-major so the paged kernels' per-head block
    ``(1, 1, block_tokens, D)`` tiles the TPU's (8, 128) layout: a
    token-major page would put a size-1 block on the second-minor
    (head) axis, which the Pallas TPU lowering refuses."""
    ok, why = supports_paged(cfg)
    if not ok:
        raise NotImplementedError(why)
    shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads,
             block_tokens, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attention_decode_paged(ap: dict, x, cfg: ModelConfig, k_pages, v_pages,
                            block_tables, positions):
    """One-token GQA attention against the shared pool.  The new K/V is
    scattered to (table[pos // bt], pos % bt); attention runs through the
    block-table kernel (gather oracle off-TPU).  Uses the un-jitted
    dispatch so fused multi-step callers keep a single jit-cache entry at
    their own entry point (see kernels.decode_attention.ops)."""
    from repro.kernels.decode_attention.ops import paged_decode_attention_impl \
        as paged_decode_attention
    bt = k_pages.shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = apply_rope(q, positions[:, None], cfg.rope_theta)
    k = apply_rope(k, positions[:, None], cfg.rope_theta)
    phys = jnp.take_along_axis(block_tables, (positions // bt)[:, None],
                               axis=1)[:, 0]
    slot = positions % bt
    k_pages = k_pages.at[phys, :, slot].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, slot].set(v[:, 0].astype(v_pages.dtype))
    out = paged_decode_attention(q[:, 0], k_pages, v_pages,
                                 block_tables, positions + 1)
    return (jnp.einsum("bshk,hkd->bsd", out[:, None].astype(x.dtype),
                       ap["wo"]),
            {"k": k_pages, "v": v_pages})


def _attention_prefill_suffix(ap: dict, x, cfg: ModelConfig, k_pages,
                              v_pages, block_tables, prefix_lens,
                              suffix_lens):
    """Suffix-token GQA attention against cached prefix pages + the new
    suffix K/V (DESIGN.md §10).  Queries sit at absolute positions
    ``prefix_lens[b] + i``; the prefix KV (positions ``< prefix_lens[b]``)
    is gathered through the block table, so the shared pages are read,
    never re-computed.  Returns (out, (k_suf, v_suf)) — the suffix K/V is
    the request's *private* cache slice, scattered into its own blocks by
    the caller."""
    from repro.kernels.decode_attention.ops import \
        paged_prefix_prefill_attention_impl as prefix_attention
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    positions = prefix_lens[:, None] + jnp.arange(s)[None, :]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = prefix_attention(q, k, v, k_pages, v_pages, block_tables,
                           prefix_lens, suffix_lens)
    return (jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), ap["wo"]),
            (k, v))


def prefill_suffix(params, cfg: ModelConfig, pages, tokens, lengths,
                   prefix_lens, block_tables, *, rules=None,
                   act_dtype=jnp.bfloat16):
    """Suffix-only prefill against cached prefix pages.

    tokens: [B, S] *suffix* ids (the prompt minus its cached radix-
    matched prefix, right-padded); lengths: [B] valid suffix counts;
    prefix_lens: [B] cached prefix tokens — any offset, including a
    partial final block whose positions past ``prefix_lens`` are masked
    (DESIGN.md §11); block_tables: [B, M] — the request's table, shared
    prefix pages first (beyond-prefix entries are gathered but masked).

    Returns (next-token logits [B, V], suffix KV (k, v) each
    [L, B, S, Hkv, D]) — same contract as :func:`prefill`, computing only
    ``S_suffix`` token positions instead of the full prompt."""
    params = cast_params(params, act_dtype)
    x = _embed_in(params, cfg, tokens, None, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)

    def body(h, xs):
        bp, page_l = xs
        hh = rms_norm(h, bp["norm1"], cfg.norm_eps)
        y, kv = _attention_prefill_suffix(
            bp["attn"], hh, cfg, page_l["k"], page_l["v"], block_tables,
            prefix_lens, lengths)
        h = h + y
        h, _ = _ffn(bp, h, cfg, rules)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return h, kv

    x, kv = jax.lax.scan(body, x, (params["blocks"], pages))
    logits = _logits(params, cfg, x, rules)
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return last, kv


def prefill_wave(params, cfg: ModelConfig, pages, state, *, tokens,
                 lengths, prefix_lens, attn_tables, tables, write_lens,
                 cow_src, cow_dst, slots, row_sel, positions, rules=None,
                 act_dtype=jnp.bfloat16):
    """Single-dispatch variable-prefix admission wave (DESIGN.md §12).

    One jitted call admits a whole wave of requests with ANY per-row
    cached-prefix length — a radix miss is just ``prefix_lens[b] = 0`` —
    by chaining four device steps that used to be separate dispatches:

    1. **Copy-on-write clones** — ``pages[:, cow_dst] = pages[:, cow_src]``
       (matched partial tail blocks; ``(null, null)`` pads are the null
       block rewriting itself).
    2. **Variable-prefix prefill** — :func:`prefill_suffix` over the
       wave's suffix tokens: causal attention over (gathered prefix
       pages ‖ suffix K/V) with per-row ``prefix_lens``.  ``attn_tables``
       is the gather table — callers pass a width-1 all-null table for a
       pure-miss wave so the oracle/kernel streams no dead prefix pages.
    3. **Suffix-KV scatter** — token-granular at each row's offset
       (:func:`write_suffix_pages_batched`); rows with ``write_lens == 0``
       (batch pads, warmup) drop entirely.
    4. **Slot-state update** — one scatter per engine array (block
       tables, seed positions, active mask, seed logits).  Pad rows
       repeat row 0's slot *and* values, so the undefined duplicate-
       scatter winner is moot.

    ``state`` is ``{"tables", "positions", "active", "logits"}`` and is
    **donated** together with ``pages`` by the engine's jitted wrapper:
    admission updates the pools and the per-slot engine state in place,
    with zero host read-backs.  Returns ``(pages, state)``."""
    pages = copy_pages(pages, cow_src, cow_dst)
    logits, kv = prefill_suffix(params, cfg, pages, tokens, lengths,
                                prefix_lens, attn_tables, rules=rules,
                                act_dtype=act_dtype)
    pages = write_suffix_pages_batched(pages, kv, tables, prefix_lens,
                                       write_lens)
    state = {
        "tables": state["tables"].at[slots].set(tables),
        "positions": state["positions"].at[slots].set(positions),
        "active": state["active"].at[slots].set(True),
        "logits": state["logits"].at[slots].set(
            logits[row_sel].astype(state["logits"].dtype)),
    }
    return pages, state


def decode_step_paged(params, cfg: ModelConfig, pages, tokens, positions,
                      block_tables, *, rules=None, act_dtype=jnp.bfloat16):
    """tokens: [B] new ids; positions: [B] tokens already cached;
    block_tables: [B, max_blocks] physical page ids (pad entries must be
    valid ids).  Returns (logits [B, V], updated pages)."""
    params = cast_params(params, act_dtype)
    x = _embed_in(params, cfg, tokens[:, None], None, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)

    def body(h, xs):
        bp, page_l = xs
        hh = rms_norm(h, bp["norm1"], cfg.norm_eps)
        y, new_pages = _attention_decode_paged(
            bp["attn"], hh, cfg, page_l["k"], page_l["v"],
            block_tables, positions)
        h = h + y
        h, _ = _ffn(bp, h, cfg, rules)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return h, new_pages

    x, new_pages = jax.lax.scan(body, x, (params["blocks"], pages))
    logits = _logits(params, cfg, x, rules)[:, 0]
    return logits, new_pages


def decode_multi_paged(params, cfg: ModelConfig, pages, logits, positions,
                       block_tables, active, *, num_steps: int, rules=None,
                       act_dtype=jnp.bfloat16):
    """Fused ``num_steps``-step paged greedy decode (DESIGN.md §9).

    One on-device ``lax.scan``: each step argmaxes the carried logits
    (the ``[B, padded_vocab]`` tensor never leaves the device), runs
    :func:`decode_step_paged`, and advances ``positions`` where ``active``
    (inactive/pad slots keep decoding into the null block at a frozen
    position).  Emitted tokens stack into one ``[B, num_steps]`` buffer —
    the only thing the host reads back per window.

    Fusion-window invariant (caller-guaranteed): every active slot has
    >= ``num_steps`` tokens left to its target AND >= ``num_steps`` free
    positions in its block table, so no finish / grow / evict event can
    fall inside the window.

    Returns ``(logits, pages, positions, tokens [B, num_steps])`` —
    bit-exact with ``num_steps`` sequential :func:`decode_step_paged`
    calls plus host argmax."""
    inc = active.astype(positions.dtype)

    def body(carry, _):
        logits, pages, positions = carry
        tok = jnp.argmax(logits[:, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
        logits, pages = decode_step_paged(
            params, cfg, pages, tok, positions, block_tables,
            rules=rules, act_dtype=act_dtype)
        return (logits, pages, positions + inc), tok

    (logits, pages, positions), toks = jax.lax.scan(
        body, (logits, pages, positions), None, length=num_steps)
    return logits, pages, positions, jnp.swapaxes(toks, 0, 1)


def draft_window(params, cfg: ModelConfig, pages, target_logits, logits,
                 positions, block_tables, active, *, num_steps: int,
                 target_vocab: int, rules=None, act_dtype=jnp.bfloat16):
    """Draft ``num_steps`` speculative tokens per slot (DESIGN.md §16).

    Runs the *draft* model's fused paged decode over its own pools.  The
    first consumed token is forced to the target's greedy pick (argmax of
    ``target_logits[:, :target_vocab]``) — it is already verified, being
    the target's own next token — and the remaining ``num_steps - 1``
    come from the draft's carried logits.  The proposed window
    ``[t1, d1, .., d_{k}]`` (``num_steps = k + 1``) never leaves the
    device; :func:`verify_window` consumes it in place.

    ``target_vocab`` is static: the draft and target configs must share a
    token id space but may pad their vocabs differently.  Inactive slots
    keep positions frozen and decode into the null block, exactly like
    :func:`decode_multi_paged`.

    Returns ``(draft_logits, pages, proposed [B, num_steps])``.  The
    draft's position advance is discarded by the caller — verification's
    emitted count governs both pools' shared positions."""
    inc = active.astype(positions.dtype)
    t1 = jnp.argmax(target_logits[:, :target_vocab],
                    axis=-1).astype(jnp.int32)

    def body(carry, i):
        dlogits, pages, positions = carry
        dtok = jnp.argmax(dlogits[:, :cfg.vocab_size],
                          axis=-1).astype(jnp.int32)
        tok = jnp.where(i == 0, t1, dtok)
        dlogits, pages = decode_step_paged(
            params, cfg, pages, tok, positions, block_tables,
            rules=rules, act_dtype=act_dtype)
        return (dlogits, pages, positions + inc), tok

    (dlogits, pages, _), toks = jax.lax.scan(
        body, (logits, pages, positions), jnp.arange(num_steps))
    return dlogits, pages, jnp.swapaxes(toks, 0, 1)


def verify_window(params, cfg: ModelConfig, pages, proposed, logits,
                  positions, block_tables, active, max_emit, *, rules=None,
                  act_dtype=jnp.bfloat16):
    """Verify a drafted window in ONE batched target dispatch
    (DESIGN.md §16).

    ``proposed`` is ``[B, W]`` (``W = draft_k + 1``): the already-verified
    target token ``t1`` followed by the draft's ``k`` guesses.  The whole
    window runs through the *prefix-prefill* path — causal attention over
    (gathered prefix pages at ``positions`` ‖ in-flight window K/V) — so
    ``all_logits[b, i]`` equals what sequential decode would produce after
    consuming ``proposed[b, i]``.  Draft token ``d_{i+1}`` is accepted iff
    it matches the target's greedy pick at the previous slot; the emitted
    count per slot is ``1 + longest agreeing prefix``, clamped to
    ``max_emit`` (host-computed per-slot budget: tokens to finish,
    ``max_steps``).  On rejection no correction token is emitted — the
    carried logits at the last accepted slot produce it as the NEXT
    window's forced ``t1``, which keeps the emitted stream bit-identical
    to plain greedy decode.

    KV for all W positions is scattered (rejected tails are reclaimed by
    block-table truncation + position rewind on the host; stale slots
    within kept blocks are overwritten before ever being attended).

    Returns ``(logits, pages, positions, packed [B, W+1])`` where
    ``packed = concat(proposed, emitted[:, None])`` — the window's single
    host readback."""
    params = cast_params(params, act_dtype)
    b, w = proposed.shape
    x = _embed_in(params, cfg, proposed, None, act_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), rules)
    suffix_lens = jnp.full((b,), w, jnp.int32)

    def body(h, xs):
        bp, page_l = xs
        hh = rms_norm(h, bp["norm1"], cfg.norm_eps)
        y, kv = _attention_prefill_suffix(
            bp["attn"], hh, cfg, page_l["k"], page_l["v"], block_tables,
            positions, suffix_lens)
        h = h + y
        h, _ = _ffn(bp, h, cfg, rules)
        h = constrain(h, ("act_batch", "act_seq", "act_embed"), rules)
        return h, kv

    x, kv = jax.lax.scan(body, x, (params["blocks"], pages))
    all_logits = _logits(params, cfg, x, rules)          # [B, W, Vp]
    pages = write_suffix_pages_batched(
        pages, kv, block_tables, positions,
        jnp.where(active, w, 0).astype(jnp.int32))
    greedy = jnp.argmax(all_logits[:, :, :cfg.vocab_size],
                        axis=-1).astype(jnp.int32)
    match = (proposed[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
    agree = jnp.cumprod(match, axis=1).sum(axis=1)       # longest prefix
    emitted = jnp.minimum(agree + 1, max_emit)
    emitted = jnp.where(active, emitted, 0).astype(positions.dtype)
    new_positions = positions + emitted
    idx = jnp.maximum(emitted - 1, 0).astype(jnp.int32)
    carry = jnp.take_along_axis(all_logits, idx[:, None, None],
                                axis=1)[:, 0]
    new_logits = jnp.where(active[:, None], carry.astype(logits.dtype),
                           logits)
    packed = jnp.concatenate(
        [proposed, emitted[:, None].astype(jnp.int32)], axis=1)
    return new_logits, pages, new_positions, packed


def write_prefill_pages_batched(pages, kv, tables, *, null_block: int = 0,
                                pad_to: int = 0) -> Dict[str, jax.Array]:
    """Scatter a batched dense prefill cache (k, v each [L, B, S, Hkv, D])
    into every request's blocks with ONE scatter per pool.

    ``tables`` is a list of per-request (host-side) block-id lists, one
    per batch row; short/empty rows pad with ``null_block`` (rows past
    ``len(tables)`` — prefill-batch bucketing pad — are all-null).  Each
    row's S is clipped/padded to the common table capacity; positions past
    a request's prompt length land in its own reserved blocks (masked by
    ``lengths`` at attention time) or in the null block, never in another
    request's pages.

    ``pad_to`` fixes the per-row block count (engines pass their
    ``max_blocks``) so the scatter's shape depends only on the prefill
    batch/bucket shape — a warmed engine never re-compiles it for a new
    mix of table lengths (tests/test_recompile.py).

    All-empty tables with ``pad_to=0`` are a no-op — nothing may be
    scattered anywhere, least of all into physical block 0, which is a
    perfectly live allocatable block (``null_block`` has no safe
    default; callers with pad entries must pass their engine's)."""
    import numpy as np
    bt = pages["k"].shape[3]
    b = kv[0].shape[1]
    max_nb = max([len(t) for t in tables] + [pad_to])
    if max_nb == 0:
        return {"k": pages["k"], "v": pages["v"]}
    rows = np.full((b, max_nb), null_block, np.int32)
    for i, t in enumerate(tables):
        rows[i, :len(t)] = t
    idx = jnp.asarray(rows.reshape(-1))

    def put(pool, c):
        l, bb, s, h, dh = c.shape
        cap = max_nb * bt
        c = c[:, :, :min(s, cap)]
        if c.shape[2] < cap:
            c = jnp.pad(c, ((0, 0), (0, 0), (0, cap - c.shape[2]),
                            (0, 0), (0, 0)))
        c = c.reshape(l, bb * max_nb, bt, h, dh).swapaxes(2, 3)
        return pool.at[:, idx].set(c.astype(pool.dtype))

    k, v = kv
    return {"k": put(pages["k"], k), "v": put(pages["v"], v)}


def write_suffix_pages_batched(pages, kv, block_tables, starts, lengths,
                               *, null_block: int = 0
                               ) -> Dict[str, jax.Array]:
    """Scatter batched *suffix* KV (k, v each [L, B, S, Hkv, D]) into the
    pool at arbitrary token offsets — ONE scatter per pool.

    Row ``b``'s position ``j`` lands at physical page
    ``block_tables[b, (starts[b]+j) // bt]`` slot ``(starts[b]+j) % bt``.
    Unlike :func:`write_prefill_pages_batched` (block-granular, offset
    0), this writes token-granular and **only** the ``lengths[b]`` valid
    positions: slots *before* ``starts[b]`` — the copied partial-prefix
    KV of a copy-on-write clone (DESIGN.md §11) — are never touched, and
    positions at or past ``lengths[b]`` (bucket pad, pad rows) scatter to
    an out-of-range index and are dropped (``mode="drop"``).  Pad rows
    must carry ``lengths == 0``.

    Shape-stable per ``(B, S, M)``: tables/starts/lengths are data, so a
    warmed engine never re-compiles this for a new hit mix."""
    bt = pages["k"].shape[3]
    nb_total = pages["k"].shape[1]
    k, v = kv
    l, b, s, h, dh = k.shape
    j = jnp.arange(s)[None, :]                          # [1, S]
    abspos = starts[:, None] + j                        # [B, S]
    blk = jnp.clip(abspos // bt, 0, block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(block_tables, blk, axis=1)
    valid = j < lengths[:, None]
    phys = jnp.where(valid, phys, nb_total)             # OOB -> dropped
    slot = abspos % bt
    fp = phys.reshape(-1)
    fs = slot.reshape(-1)

    def put(pool, c):
        # the block and slot indices are split by the head axis, so NumPy
        # indexing rules put the token axis first: [B*S, L, Hkv, D]
        vals = c.reshape(l, b * s, h, dh).swapaxes(0, 1).astype(pool.dtype)
        return pool.at[:, fp, :, fs].set(vals, mode="drop")

    return {"k": put(pages["k"], k), "v": put(pages["v"], v)}


def copy_pages(pages, src, dst) -> Dict[str, jax.Array]:
    """Device-side block clone for copy-on-write: ``pages[:, dst[i]] =
    pages[:, src[i]]`` for each pair, one gather + one scatter per pool.

    ``src``/``dst`` are int32 ``[N]``; callers pad to a warmed
    power-of-two N with (null_block, null_block) pairs — duplicate
    destinations are only ever the null block rewriting itself, so the
    undefined scatter winner is moot."""
    def cp(pool):
        return pool.at[:, dst].set(pool[:, src])

    return {"k": cp(pages["k"]), "v": cp(pages["v"])}


def gather_pages(pages, blocks) -> jax.Array:
    """Stack the pools' pages at ``blocks`` for a host swap-out
    (DESIGN.md §15): one ``[P, L, N, Hkv, bt, D]`` array with the pool
    axis in sorted key order ("k", "v"), so the single device→host
    readback of the result is the whole swap transfer.  ``blocks`` is
    int32 ``[N]``; callers pad to a warmed power-of-two N with the null
    block and slice the junk rows off host-side."""
    return jnp.stack([pages[key][:, blocks] for key in sorted(pages)])


def scatter_pages(pages, blocks, values) -> Dict[str, jax.Array]:
    """Write swapped-in host pages back into the device pools — the
    inverse of :func:`gather_pages`, one scatter per pool.  ``values``
    is ``[P, L, N, Hkv, bt, D]`` aligned with ``blocks``; pad entries
    target the null block, whose contents are junk by design."""
    return {key: pages[key].at[:, blocks].set(
                values[i].astype(pages[key].dtype))
            for i, key in enumerate(sorted(pages))}


def write_prefill_pages(pages, kv, table) -> Dict[str, jax.Array]:
    """Single-request convenience wrapper over
    :func:`write_prefill_pages_batched` (k, v each [L, 1, S, Hkv, D])."""
    return write_prefill_pages_batched(pages, kv, [list(table)])
