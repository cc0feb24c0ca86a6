"""Seeded random weights, made on the device in the serving dtype.

The benchmark makes the weights itself, from ``--seed``, in the program's
parameter layout (a pytree the engine accepts as ``params``).  The same
function regenerates any one layer alone, bit for bit, so the plain
reference can rebuild a model too large to hold twice, layer by layer,
without taking anything the program holds.

Scales: every projection is N(0, 1/fan_in), norms are ones, and the
token embedding is N(0, EMBED_STD^2).  A small embedding keeps the
current token's own row a small part of the final hidden state, so a
tied head does not simply repeat the current token: greedy decoding then
meets near-ties between tokens, which is what makes a comparison of
logits sensitive to precision (at 0.3 every stream repeated one token;
at 0.01 they vary, on smollm-135m in bf16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EMBED_STD = 0.01
_EMBED_CHUNKS = 16        # the vocabulary table is made in row chunks
_TAG_EMBED, _TAG_HEAD, _TAG_LAYER = 1, 2, 3


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, wider than 32 bits too."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(m: dict) -> dict:
    """Shapes of one decoder layer's leaves in the program's layout."""
    d, hq, hkv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd, ff = m["head_dim"], m["intermediate_size"]
    return {"norm1": (d,),
            "attn": {"wq": (d, hq, hd), "wk": (d, hkv, hd),
                     "wv": (d, hkv, hd), "wo": (hq, hd, d)},
            "norm2": (d,),
            "mlp": {"gate": (d, ff), "up": (d, ff), "down": (ff, d)}}


def _fan_in(name: str, shape) -> int:
    if name == "wo":
        return shape[0] * shape[1]
    return shape[0]


def make_layer(key: jax.Array, layer, m: dict, dtype) -> dict:
    """Layer ``layer`` (a Python or traced int) of the model of sizes
    ``m``; the same values wherever it is called from."""
    lk = jax.random.fold_in(jax.random.fold_in(key, _TAG_LAYER), layer)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        layer_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name.startswith("norm"):
            out.append(jnp.ones(shape, dtype))
            continue
        std = _fan_in(name, shape) ** -0.5
        w = jax.random.normal(jax.random.fold_in(lk, i), shape, dtype)
        out.append(w * jnp.asarray(std, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _table(key: jax.Array, tag: int, rows: int, cols: int, std: float,
           dtype) -> jax.Array:
    """A [rows, cols] table made in ``_EMBED_CHUNKS`` row chunks, so the
    random bits of the whole table are never live at once."""
    tk = jax.random.fold_in(key, tag)
    per = rows // _EMBED_CHUNKS

    def chunk(i):
        w = jax.random.normal(jax.random.fold_in(tk, i), (per, cols), dtype)
        return w * jnp.asarray(std, dtype)

    return jax.lax.map(chunk, jnp.arange(_EMBED_CHUNKS)).reshape(rows, cols)


def padded_vocab(m: dict) -> int:
    """The program's padded vocabulary: rounded up to a multiple of 2048
    rows (of 16 below 2048)."""
    v = m["vocab_size"]
    step = 2048 if v >= 2048 else 16
    return -(-v // step) * step


def embed_table(key, m: dict, dtype) -> jax.Array:
    return _table(key, _TAG_EMBED, padded_vocab(m), m["hidden_size"],
                  EMBED_STD, dtype)


def head_table(key, m: dict, dtype) -> jax.Array:
    """The untied output head, [hidden, padded vocab]."""
    d = m["hidden_size"]
    return _table(key, _TAG_HEAD, padded_vocab(m), d, d ** -0.5, dtype).T


def _make_params(key, m: dict, dtype) -> dict:
    layers = jnp.arange(m["num_hidden_layers"])
    params = {"embed": embed_table(key, m, dtype),
              "blocks": jax.lax.map(
                  lambda l: make_layer(key, l, m, dtype), layers),
              "final_norm": jnp.ones((m["hidden_size"],), dtype)}
    if not m["tie_word_embeddings"]:
        params["lm_head"] = head_table(key, m, dtype)
    return params


@functools.lru_cache(maxsize=None)
def _compiled(frozen_sizes: tuple, dtype_name: str):
    m = dict(frozen_sizes)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda key: _make_params(key, m, dtype))


def make_params(seed: int, m: dict, dtype) -> dict:
    """The whole model in one jitted call on the default device."""
    sizes = tuple(sorted((k, v) for k, v in m.items()
                         if isinstance(v, (int, float, bool))))
    return _compiled(sizes, jnp.dtype(dtype).name)(seed_key(seed))
