"""Plain reference of a llama-style dense decoder, in float32.

The architecture as published for the SmolLM / Llama family: token
embedding, then per layer ``h += Attn(RMSNorm(h))`` and
``h += SwiGLU(RMSNorm(h))``, a final RMSNorm and the (tied or untied)
output head.  Attention is causal grouped-query attention: query head
``i`` reads key/value head ``i // (Hq / Hkv)``, scores scaled by
``head_dim ** -0.5``, with rotary embeddings on queries and keys that
rotate the first half of each head against the second (frequencies
``theta ** (-2j / head_dim)``).

Nothing of the program is imported: the weights are made again from the
seed by ``weights.py``, one layer at a time, so a model that fills the
chip in bf16 fits here in float32.  Every matrix product runs at the
highest precision.

``quant="fp8"`` selects the control: the same forward computed in a
precision below bf16, every matrix-product operand (weights per output
channel, activations per token, attention operands per head) rounded to
float8 e4m3 with its own scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def _round_to(x, axes, quant):
    """``x`` rounded as the control's precision stores it, per slice over
    ``axes`` (the contracted axes of the product it feeds)."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax / _F8_MAX, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: [S, H, D]; rotates the first half of D against the second."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_one(w, h, m, quant):
    """One decoder layer over one sequence ``h`` [S, d].  Padding at the
    end of a row sees only itself and earlier positions, so it never
    reaches a real one."""
    s = h.shape[0]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    g = hq // hkv
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(s)
    x = _round_to(rms_norm(h, w["norm1"], eps), (1,), quant)
    q = jnp.einsum("sd,dhk->shk", x, w["wq"], precision=HIGHEST)
    k = jnp.einsum("sd,dhk->shk", x, w["wk"], precision=HIGHEST)
    v = jnp.einsum("sd,dhk->shk", x, w["wv"], precision=HIGHEST)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    q, k, v = (_round_to(t, (2,), quant) for t in (q, k, v))
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("shk,thk->hst", q, kr, precision=HIGHEST) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    p = _round_to(jax.nn.softmax(sc, axis=-1), (2,), quant)
    o = jnp.einsum("hst,thk->shk", p, vr, precision=HIGHEST)
    o = _round_to(o, (1, 2), quant)
    h = h + jnp.einsum("shk,hkd->sd", o, w["wo"], precision=HIGHEST)
    x = _round_to(rms_norm(h, w["norm2"], eps), (1,), quant)
    a = jnp.einsum("sd,df->sf", x, w["gate"], precision=HIGHEST)
    b = jnp.einsum("sd,df->sf", x, w["up"], precision=HIGHEST)
    y = _round_to(jax.nn.silu(a) * b, (1,), quant)
    return h + jnp.einsum("sf,fd->sd", y, w["down"], precision=HIGHEST)


def _prepare(lw, quant):
    """A layer's bf16 leaves as float32, rounded to the control's
    precision per output channel (the contracted axes per leaf)."""
    f = {k: v.astype(jnp.float32) for k, v in
         {**lw["attn"], **lw["mlp"], "norm1": lw["norm1"],
          "norm2": lw["norm2"]}.items()}
    contracted = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
                  "gate": (0,), "up": (0,), "down": (0,)}
    for name, axes in contracted.items():
        f[name] = _round_to(f[name], axes, quant)
    return f


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(lw, h, *, m, quant):
    f = _prepare(lw, quant)
    return jax.lax.map(lambda row: _layer_one(f, row, dict(m), quant), h)


@functools.partial(jax.jit, static_argnames=("m",))
def _make_layer(key, layer, *, m):
    return W.make_layer(key, layer, dict(m), jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _row_logits(h, head, final_norm, pos, *, m, quant):
    """One row's logits over the real vocabulary at positions ``pos``
    [T], from final hidden states ``h`` [S, d]."""
    m = dict(m)
    x = rms_norm(h, final_norm.astype(jnp.float32), m["rms_norm_eps"])
    x = _round_to(jnp.take(x, pos, axis=0), (1,), quant)
    hd = _round_to(head.astype(jnp.float32)[:, :m["vocab_size"]], (0,),
                   quant)
    return jnp.einsum("td,dv->tv", x, hd, precision=HIGHEST)


@jax.jit
def _gap_of(ref, picked, valid):
    """Per position: how far the reference's logit of ``picked`` lies
    below the reference's best; 0 where ``valid`` is false."""
    got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
    return jnp.where(valid, ref.max(-1) - got, 0.0)


def _seq_arrays(seqs, bucket: int = 128):
    """Pad token rows to one length; positions that chose each served
    token.  ``seqs``: list of (prompt ids, served ids).  Lengths round up
    to ``bucket`` so that few shapes compile."""
    full = [list(p) + list(s[:-1]) for p, s in seqs]
    s_len = -(-max(len(f) for f in full) // bucket) * bucket
    t_len = -(-max(len(s) for _, s in seqs) // bucket) * bucket
    toks = np.zeros((len(seqs), s_len), np.int32)
    pos = np.zeros((len(seqs), t_len), np.int32)
    served = np.zeros((len(seqs), t_len), np.int32)
    valid = np.zeros((len(seqs), t_len), bool)
    for r, ((p, s), f) in enumerate(zip(seqs, full)):
        toks[r, :len(f)] = f
        pos[r, :len(s)] = len(p) - 1 + np.arange(len(s))
        served[r, :len(s)] = s
        valid[r, :len(s)] = True
    return toks, pos, served, valid


def _frozen(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool))))


def hidden_states(seed: int, m: dict, toks, quant=None):
    """Final hidden states [R, S, d] of the reference (or of the control
    with ``quant``), layer by layer from weights made again from the
    seed."""
    key = W.seed_key(seed)
    fm = _frozen(m)
    emb = W.embed_table(key, m, jnp.bfloat16)
    h = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    del emb
    for layer in range(m["num_hidden_layers"]):
        lw = _make_layer(key, layer, m=fm)
        h = _layer(lw, h, m=fm, quant=quant)
    return h


def output_head(seed: int, m: dict):
    key = W.seed_key(seed)
    if m["tie_word_embeddings"]:
        head = W.embed_table(key, m, jnp.bfloat16).T
    else:
        head = W.head_table(key, m, jnp.bfloat16)
    return head, jnp.ones((m["hidden_size"],), jnp.bfloat16)


def served_gaps(seed: int, m: dict, seqs, control=None) -> dict:
    """Compare served greedy tokens with the reference.

    ``seqs``: list of (prompt ids, served ids).  Returns per row the
    widest gap (logit units) by which a served token lies below the
    reference's best at the position that chose it, and, with
    ``control="fp8"``, the widest
    gap of the token the control's own forward puts first there."""
    toks, pos, served, valid = _seq_arrays(seqs)
    fm = _frozen(m)
    head, fnorm = output_head(seed, m)
    h = hidden_states(seed, m, toks)
    hc = (hidden_states(seed, m, toks, quant=control)
          if control is not None else None)
    gaps, cgaps = [], []
    for r in range(len(seqs)):
        ref = _row_logits(h[r], head, fnorm, jnp.asarray(pos[r]), m=fm,
                          quant=None)
        gaps.append(float(_gap_of(ref, jnp.asarray(served[r]),
                                  jnp.asarray(valid[r])).max()))
        if hc is not None:
            lc = _row_logits(hc[r], head, fnorm, jnp.asarray(pos[r]), m=fm,
                             quant=control)
            cgaps.append(float(_gap_of(ref, jnp.argmax(lc, -1),
                                       jnp.asarray(valid[r])).max()))
    out = {"gap": np.asarray(gaps), "tokens": int(valid.sum())}
    if hc is not None:
        out["control_gap"] = np.asarray(cgaps)
    return out
