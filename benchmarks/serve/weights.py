"""Seeded weights in the served type, and the helpers every
architecture (``architectures/<name>.py``) makes its tree with.

The program gets its weights as one tree in its own layout, made on the
device in one jitted call by the configuration's architecture.  The
reference makes the same numbers again from the seed, one layer at a
time, so it takes nothing that the program has held.  Every leaf draws
from a key folded from (seed, part, layer, leaf), and a layer's leaves
are the same whether they are made alone or stacked under ``vmap``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

import catalog

Tree = Dict[str, Any]

# part ids folded into the root key
_EMBED, _LAYERS, _FINAL_NORM, _HEAD = 0, 1, 2, 3


def root_key(seed: int) -> jax.Array:
    """A key from a seed of any size: its 32-bit words folded in turn,
    since ``PRNGKey`` keeps only the low word."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def _part_key(key, part: int, index: int = 0):
    return jax.random.fold_in(jax.random.fold_in(key, part), index)


def matrix(key, d_in: int, d_out: int, dtype) -> jax.Array:
    """A (d_in, d_out) matrix of normals with std sqrt(2/(d_in+d_out)),
    drawn in float32 and cast to ``dtype``."""
    std = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * std).astype(dtype)


def scale(key, dim: int) -> jax.Array:
    """A norm's scale, 1 + 0.1 * normal, in float32."""
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), jnp.float32)


def layer_key(key: jax.Array, index: int) -> jax.Array:
    return _part_key(key, _LAYERS, index)


def padded_vocab(config: Dict[str, Any]) -> int:
    """The program's embedding rows: the vocabulary rounded up to 256."""
    return -(-config["vocab_size"] // 256) * 256


def table(key: jax.Array, config: Dict[str, Any], part: int) -> jax.Array:
    """Embedding or output-head rows; rows past the vocabulary are zero."""
    d, v = config["hidden_size"], config["vocab_size"]
    rows = jax.random.normal(_part_key(key, part), (v, d), jnp.float32)
    rows = (rows * d ** -0.5).astype(jnp.dtype(config["torch_dtype"]))
    return jnp.pad(rows, ((0, padded_vocab(config) - v), (0, 0)))


def embed_table(key, config):
    return table(key, config, _EMBED)


def head_table(key, config):
    """The output head's rows (the embedding's, when tied)."""
    if config["tie_word_embeddings"]:
        return embed_table(key, config)
    return table(key, config, _HEAD)


def final_norm(key, config):
    return scale(_part_key(key, _FINAL_NORM), config["hidden_size"])


def make_params(config: Dict[str, Any], seed: int) -> Tree:
    """The whole tree on the device, from the seed, in one jitted call
    of the configuration's architecture's ``build``."""
    build = catalog.architecture(config["architecture"]).build
    made = jax.jit(lambda key: build(key, config))(root_key(seed))
    return jax.block_until_ready(made)


def check_layout(params: Tree, program_shapes: Tree) -> None:
    """The tree has the program's structure, shapes and dtypes."""
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        program_shapes)
    if got != want:
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"made {got}\nprogram {want}")


def count(params: Tree) -> int:
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(params)))
