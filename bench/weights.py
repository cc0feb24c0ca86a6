"""Seeded random weights, made on the device in the serving dtype.

The benchmark makes the weights itself, from ``--seed``, in the program's
parameter layout (a pytree the engine accepts as ``params``).  The same
function regenerates any one layer alone, bit for bit, so the plain
reference can rebuild a model too large to hold twice, layer by layer,
without taking anything the program holds.

The layout comes from the configuration's architecture module
(``arch.py``): a tree of ``Leaf`` for one layer and one for the leaves
outside the layers, each with its first draw (projections N(0, 1/fan_in),
norms ones, biases zeros).  How each leaf's values follow from the seed is
shared by every architecture.  The token embedding is N(0, EMBED_STD^2).
A small embedding keeps the current token's own row a small part of the
final hidden state, so a tied head does not simply repeat the current
token: greedy decoding then meets near-ties between tokens, which is what
makes a comparison of logits sensitive to precision (at 0.3 every stream
repeated one token; at 0.01 they vary, on smollm-135m in bf16).
"""
from __future__ import annotations

import functools
import json
from typing import NamedTuple

import jax
import jax.numpy as jnp

import arch

EMBED_STD = 0.01
_EMBED_CHUNKS = 16        # the vocabulary table is made in row chunks
_TAG_EMBED, _TAG_HEAD, _TAG_LAYER = 1, 2, 3


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, wider than 32 bits too."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Leaf(NamedTuple):
    """One parameter leaf: its shape and how it is first drawn.

    ``init``: ``"normal"`` (N(0, 1/``fan_in``)), ``"ones"`` or ``"zeros"``;
    outside the layers also ``"embed"`` (the token embedding,
    [padded vocab, hidden]) and ``"head"`` (the untied output head,
    [hidden, padded vocab])."""
    shape: tuple
    init: str
    fan_in: int = 0


def _fill(leaf: Leaf, dtype) -> jax.Array:
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    raise ValueError(f"unknown leaf init {leaf.init!r}")


def make_layer(key: jax.Array, layer, shapes: dict, dtype) -> dict:
    """Layer ``layer`` (a Python or traced int) of the layout ``shapes``
    (a tree of ``Leaf``); the same values wherever it is called from."""
    lk = jax.random.fold_in(jax.random.fold_in(key, _TAG_LAYER), layer)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, Leaf))
    out = []
    for i, leaf in enumerate(leaves):
        if leaf.init != "normal":
            out.append(_fill(leaf, dtype))
            continue
        std = leaf.fan_in ** -0.5
        w = jax.random.normal(jax.random.fold_in(lk, i), leaf.shape, dtype)
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


def top_leaf(key, leaf: Leaf, dtype) -> jax.Array:
    """A leaf outside the layers, the same wherever it is made."""
    if leaf.init == "embed":
        return _table(key, _TAG_EMBED, *leaf.shape, EMBED_STD, dtype)
    if leaf.init == "head":
        d, v = leaf.shape
        return _table(key, _TAG_HEAD, v, d, d ** -0.5, dtype).T
    return _fill(leaf, dtype)


def _make_params(key, m: dict, dtype) -> dict:
    A = arch.of(m)
    shapes = A.layer_shapes(m)
    params = {name: top_leaf(key, leaf, dtype)
              for name, leaf in A.top_shapes(m).items()}
    params["blocks"] = jax.lax.map(
        lambda l: make_layer(key, l, shapes, dtype),
        jnp.arange(A.num_layers(m)))
    return params


def frozen(m: dict) -> str:
    """The configuration, hashable (a static argument of a jitted call);
    ``thawed`` gives it back whole, nested groups too."""
    return json.dumps(m, sort_keys=True)


def thawed(frozen_m: str) -> dict:
    return json.loads(frozen_m)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_m: str, dtype_name: str):
    m = thawed(frozen_m)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda key: _make_params(key, m, dtype))


def make_params(seed: int, m: dict, dtype) -> dict:
    """The whole model in one jitted call on the default device."""
    return _compiled(frozen(m), jnp.dtype(dtype).name)(seed_key(seed))
