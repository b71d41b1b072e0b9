"""Seeded weights for a dense decoder, in the served type.

The program gets them as one tree in its own layout (layers stacked on a
leading axis), made on the device in one jitted call.  The reference
makes the same numbers again from the seed, one layer at a time, so it
takes nothing that the program has held.  Every leaf draws from a key
folded from (seed, part, layer, leaf), and a layer's leaves are the same
whether they are made alone or stacked under ``vmap``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Tree = Dict[str, Any]

# part ids folded into the root key
_EMBED, _LAYERS, _FINAL_NORM, _HEAD = 0, 1, 2, 3
_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2", "ln1", "ln2",
           "q_norm", "k_norm")


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


def _matrix(key, d_in: int, d_out: int, dtype) -> jax.Array:
    std = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * std).astype(dtype)


def _scale(key, dim: int) -> jax.Array:
    return 1.0 + 0.1 * jax.random.normal(key, (dim,), jnp.float32)


def layer(key: jax.Array, config: Dict[str, Any]) -> Tree:
    """One decoder layer's leaves from its key, in the program's layout."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    dh = config["head_dim"]
    qd = config["num_attention_heads"] * dh
    kvd = config["num_key_value_heads"] * dh
    dt = jnp.dtype(config["torch_dtype"])
    k = {name: jax.random.fold_in(key, i) for i, name in enumerate(_LEAVES)}
    attn = {"wq": _matrix(k["wq"], d, qd, dt),
            "wk": _matrix(k["wk"], d, kvd, dt),
            "wv": _matrix(k["wv"], d, kvd, dt),
            "wo": _matrix(k["wo"], qd, d, dt)}
    if config["qk_norm"]:
        attn["q_norm"] = _scale(k["q_norm"], dh)
        attn["k_norm"] = _scale(k["k_norm"], dh)
    return {"ln1": _scale(k["ln1"], d), "attn": attn,
            "ln2": _scale(k["ln2"], d),
            "mlp": {"w1": _matrix(k["w1"], d, ff, dt),
                    "w3": _matrix(k["w3"], d, ff, dt),
                    "w2": _matrix(k["w2"], ff, d, dt)}}


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
    return _scale(_part_key(key, _FINAL_NORM), config["hidden_size"])


def build(key: jax.Array, config: Dict[str, Any]) -> Tree:
    """The whole tree from the root key, in the program's layout."""
    keys = jax.vmap(lambda i: layer_key(key, i))(
        jnp.arange(config["num_hidden_layers"]))
    params = {"embed": {"table": embed_table(key, config)},
              "layers": jax.vmap(lambda k: layer(k, config))(keys),
              "final_norm": final_norm(key, config)}
    if not config["tie_word_embeddings"]:
        params["lm_head"] = {"table": head_table(key, config)}
    return params


def make_params(config: Dict[str, Any], seed: int) -> Tree:
    """The whole tree on the device, from the seed, in one jitted call."""
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
