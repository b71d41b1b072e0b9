"""The dense decoder: every layer the same, pre-norm grouped-query
attention with an optional RMSNorm of each query and key head, and a
SwiGLU MLP.  Configurations that name ``"architecture": "dense_decoder"``
are run with it.

What the harness takes from an architecture: the program's
``ArchConfig`` from the published keys, the seeded weight tree in the
program's layout, and the work of its steps and kernels counted from
shapes, at each request's real length and summed over the layers.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

import weights
import work

Tree = Dict[str, Any]
_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2", "ln1", "ln2",
           "q_norm", "k_norm")


def arch_config(config: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file: the
    published keys mapped onto the program's names, nothing else."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=config.get("model_type", "dense"), family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        d_head=config["head_dim"], qk_norm=bool(config["qk_norm"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["torch_dtype"], act_dtype=config["torch_dtype"],
        use_pallas_kernels=bool(config["serving"]["use_pallas_kernels"]))


# -- weights ----------------------------------------------------------------
def layer(key: jax.Array, config: Dict[str, Any]) -> Tree:
    """One decoder layer's leaves from its key, in the program's layout."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    dh = config["head_dim"]
    qd = config["num_attention_heads"] * dh
    kvd = config["num_key_value_heads"] * dh
    dt = jnp.dtype(config["torch_dtype"])
    k = {name: jax.random.fold_in(key, i) for i, name in enumerate(_LEAVES)}
    attn = {"wq": weights.matrix(k["wq"], d, qd, dt),
            "wk": weights.matrix(k["wk"], d, kvd, dt),
            "wv": weights.matrix(k["wv"], d, kvd, dt),
            "wo": weights.matrix(k["wo"], qd, d, dt)}
    if config["qk_norm"]:
        attn["q_norm"] = weights.scale(k["q_norm"], dh)
        attn["k_norm"] = weights.scale(k["k_norm"], dh)
    return {"ln1": weights.scale(k["ln1"], d), "attn": attn,
            "ln2": weights.scale(k["ln2"], d),
            "mlp": {"w1": weights.matrix(k["w1"], d, ff, dt),
                    "w3": weights.matrix(k["w3"], d, ff, dt),
                    "w2": weights.matrix(k["w2"], ff, d, dt)}}


def build(key: jax.Array, config: Dict[str, Any]) -> Tree:
    """The whole tree from the root key, layers stacked on a leading axis."""
    keys = jax.vmap(lambda i: weights.layer_key(key, i))(
        jnp.arange(config["num_hidden_layers"]))
    params = {"embed": {"table": weights.embed_table(key, config)},
              "layers": jax.vmap(lambda k: layer(k, config))(keys),
              "final_norm": weights.final_norm(key, config)}
    if not config["tie_word_embeddings"]:
        params["lm_head"] = {"table": weights.head_table(key, config)}
    return params


# -- work -------------------------------------------------------------------
def _dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_hidden_layers"], c["vocab_size"])


def layer_matmul_params(c: Dict) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o, and the
    SwiGLU gate, up and down projections."""
    d, ff, nh, nkv, dh, _, _ = _dims(c)
    return d * nh * dh + 2 * d * nkv * dh + nh * dh * d + 3 * d * ff


def prefill_flops(c: Dict, plen: int) -> float:
    """A whole-prompt prefill: every matmul over ``plen`` tokens, causal
    attention (QK and PV over the lower triangle) and one head row."""
    _, _, nh, _, dh, n_layers, _ = _dims(c)
    causal_pairs = plen * (plen + 1) / 2
    per_layer = (2.0 * plen * layer_matmul_params(c)
                 + 4.0 * nh * dh * causal_pairs)
    return n_layers * per_layer + work.head_flops(c)


def decode_flops(c: Dict, context: int) -> float:
    """One decoded token that attends over ``context`` positions."""
    _, _, nh, _, dh, n_layers, _ = _dims(c)
    per_layer = 2.0 * layer_matmul_params(c) + 4.0 * nh * dh * context
    return n_layers * per_layer + work.head_flops(c)


def paged_attention_call(c: Dict, contexts: List[int]
                         ) -> Tuple[float, float]:
    """Paged decode attention over rows attending to ``contexts``
    positions each, summed over the layers: (flops, bytes).  Bytes are
    the K and V of those positions, each query and each output once."""
    _, _, nh, nkv, dh, n_layers, _ = _dims(c)
    eb = work.elem_bytes(c)
    flops = sum(4.0 * nh * dh * ctx for ctx in contexts)
    nbytes = sum(2.0 * nkv * ctx * dh * eb + 2.0 * nh * dh * eb
                 for ctx in contexts)
    return n_layers * flops, n_layers * nbytes


def mlp_gemms(c: Dict, m: int) -> List[Tuple[int, int, int, int]]:
    """(layers, M, K, N) of the three SwiGLU GEMMs over ``m`` tokens: each
    GEMM's shape and the number of layers that run it."""
    d, ff, _, _, _, n_layers, _ = _dims(c)
    return [(n_layers, m, d, ff), (n_layers, m, d, ff), (n_layers, m, ff, d)]
