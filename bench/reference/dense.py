"""The llama-style dense decoder: its plain reference in float32, and the
architecture facts the harness takes from here (``arch.py``): the
program's configuration, the parameter layout, the operation and byte
counts and the prompt's ids.

The architecture as published for the SmolLM / Llama family: token
embedding, then per layer ``h += Attn(RMSNorm(h))`` and
``h += SwiGLU(RMSNorm(h))``, a final RMSNorm and the (tied or untied)
output head.  Attention is causal grouped-query attention: query head
``i`` reads key/value head ``i // (Hq / Hkv)``, scores scaled by
``head_dim ** -0.5``, with rotary embeddings on queries and keys that
rotate the first half of each head against the second (frequencies
``theta ** (-2j / head_dim)``).

The reference takes nothing of the program (only ``program_config``,
which the harness calls, builds the program's own configuration object):
the weights are made again from the seed by ``weights.py``, one layer at
a time, so a model that fills the chip in bf16 fits here in float32.
Every matrix product runs at the highest precision.

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

import generator as G
import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


def program_config(m: dict):
    """The program's ModelConfig for the configuration file ``m``."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m["name"], family="dense", num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_embeddings=m["tie_word_embeddings"], source=m["source"])


def num_layers(m: dict) -> int:
    return m["num_hidden_layers"]


def layer_shapes(m: dict) -> dict:
    """One decoder layer's leaves in the program's layout."""
    d, hq, hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd, ff = m["head_dim"], m["intermediate_size"]
    norm = W.Leaf((d,), "ones")

    def proj(shape, fan_in):
        return W.Leaf(shape, "normal", fan_in)

    return {"norm1": norm,
            "attn": {"wq": proj((d, hq, hd), d), "wk": proj((d, hkv, hd), d),
                     "wv": proj((d, hkv, hd), d),
                     "wo": proj((hq, hd, d), hq * hd)},
            "norm2": norm,
            "mlp": {"gate": proj((d, ff), d), "up": proj((d, ff), d),
                    "down": proj((ff, d), ff)}}


def top_shapes(m: dict) -> dict:
    """The leaves outside the layers: the embedding, the final norm and,
    where the embeddings are not tied, the output head."""
    d, v = m["hidden_size"], W.padded_vocab(m)
    top = {"embed": W.Leaf((v, d), "embed"),
           "final_norm": W.Leaf((d,), "ones")}
    if not m["tie_word_embeddings"]:
        top["lm_head"] = W.Leaf((d, v), "head")
    return top


def prompt_ids(req, m: dict, max_len: int):
    return G.prompt_ids(req, m["vocab_size"], max_len)


# -- operations and bytes (read through flops.py) -----------------------------

def _dims(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one layer (projections and MLP)."""
    _, d, hq, hkv, hd, ff, _ = _dims(m)
    return d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff


def decode_window(m: dict, k: int, rows: int, ctx: int, b: int = 2) -> dict:
    """A fused decode window of ``k`` steps over ``rows`` active requests
    whose contexts sum to ``ctx`` tokens when the window starts.  At step
    ``i`` each row attends over its context plus ``i + 1`` tokens.

    ``attn_*``: the paged decode attention kernel (all layers);
    ``model_flops``: the whole step (projections, MLP, attention, head)."""
    L, d, hq, hkv, hd, _, v = _dims(m)
    span = k * ctx + rows * k * (k + 1) // 2     # sum of attended lengths
    attn_flops = 4 * hq * hd * L * span
    attn_bytes = L * (2 * hkv * hd * b * span + k * rows * 2 * hq * hd * b)
    tokens = k * rows
    model_flops = tokens * 2 * (L * layer_matmul_params(m) + d * v) \
        + attn_flops
    return {"attn_flops": attn_flops, "attn_bytes": attn_bytes,
            "model_flops": model_flops, "tokens": tokens}


def prefill_wave(m: dict, rows, b: int = 2) -> dict:
    """An admission wave; ``rows`` are (suffix tokens run, cached prefix
    tokens).  Suffix queries attend causally among themselves and to the
    whole cached prefix, which the kernel reads from the page pool.

    ``attn_*``: the prefix-prefill attention kernel (all layers);
    ``model_flops``: the wave's useful work, with one logits row a row."""
    L, d, hq, hkv, hd, _, v = _dims(m)
    attn_flops = attn_bytes = model_flops = tokens = 0
    for s, p in rows:
        f = 2 * hq * hd * L * s * (2 * p + s + 1)
        attn_flops += f
        attn_bytes += L * b * hd * (2 * hkv * p + s * (2 * hq + 2 * hkv))
        model_flops += 2 * s * L * layer_matmul_params(m) + 2 * d * v + f
        tokens += s
    return {"attn_flops": attn_flops, "attn_bytes": attn_bytes,
            "model_flops": model_flops, "tokens": tokens}


# -- the plain reference ------------------------------------------------------


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
    return jax.lax.map(lambda row: _layer_one(f, row, W.thawed(m), quant), h)


@functools.partial(jax.jit, static_argnames=("m",))
def _make_layer(key, layer, *, m):
    return W.make_layer(key, layer, layer_shapes(W.thawed(m)), jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _row_logits(h, head, final_norm, pos, *, m, quant):
    """One row's logits over the real vocabulary at positions ``pos``
    [T], from final hidden states ``h`` [S, d]."""
    m = W.thawed(m)
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


def hidden_states(seed: int, m: dict, toks, quant=None):
    """Final hidden states [R, S, d] of the reference (or of the control
    with ``quant``), layer by layer from weights made again from the
    seed."""
    key = W.seed_key(seed)
    fm = W.frozen(m)
    emb = W.top_leaf(key, top_shapes(m)["embed"], jnp.bfloat16)
    h = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    del emb
    for layer in range(m["num_hidden_layers"]):
        lw = _make_layer(key, layer, m=fm)
        h = _layer(lw, h, m=fm, quant=quant)
    return h


def output_head(seed: int, m: dict):
    key = W.seed_key(seed)
    top = top_shapes(m)
    if "lm_head" in top:
        head = W.top_leaf(key, top["lm_head"], jnp.bfloat16)
    else:
        head = W.top_leaf(key, top["embed"], jnp.bfloat16).T
    return head, W.top_leaf(key, top["final_norm"], jnp.bfloat16)


def served_gaps(seed: int, m: dict, seqs, control=None) -> dict:
    """Compare served greedy tokens with the reference.

    ``seqs``: list of (prompt ids, served ids).  Returns per row the
    widest gap (logit units) by which a served token lies below the
    reference's best at the position that chose it, and, with
    ``control="fp8"``, the widest
    gap of the token the control's own forward puts first there."""
    toks, pos, served, valid = _seq_arrays(seqs)
    fm = W.frozen(m)
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
