"""A mixture-of-experts decoder, as a configuration of another family
brings its architecture to the harness: the program's ``family="moe"``
path.  Each layer has pre-norm grouped-query attention and, in place of
the MLP, a softmax router over ``n_routed_experts`` SwiGLU experts of
width ``moe_intermediate_size``, of which each token takes
``num_experts_per_tok``, beside ``n_shared_experts`` shared experts that
every token takes (one SwiGLU of their summed width).

The tests copy it into ``architectures/`` of a scratch checkout; it is
not one of the benchmark's architectures.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

import weights
import work

Tree = Dict[str, Any]
_LEAVES = ("wq", "wk", "wv", "wo", "ln1", "ln2", "router", "w1", "w3", "w2",
           "shared_w1", "shared_w3", "shared_w2")


def arch_config(config: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=config["model_type"], family="moe",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"],
        vocab_size=config["vocab_size"], d_head=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        param_dtype=config["torch_dtype"], act_dtype=config["torch_dtype"],
        use_pallas_kernels=bool(config["serving"]["use_pallas_kernels"]))


def _experts(key, n: int, d_in: int, d_out: int, dtype) -> jax.Array:
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))
    return jax.vmap(lambda k: weights.matrix(k, d_in, d_out, dtype))(keys)


def layer(key: jax.Array, config: Dict[str, Any]) -> Tree:
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    e, fs = config["n_routed_experts"], f * config["n_shared_experts"]
    dh = config["head_dim"]
    qd = config["num_attention_heads"] * dh
    kvd = config["num_key_value_heads"] * dh
    dt = jnp.dtype(config["torch_dtype"])
    k = {name: jax.random.fold_in(key, i) for i, name in enumerate(_LEAVES)}
    return {
        "ln1": weights.scale(k["ln1"], d),
        "attn": {"wq": weights.matrix(k["wq"], d, qd, dt),
                 "wk": weights.matrix(k["wk"], d, kvd, dt),
                 "wv": weights.matrix(k["wv"], d, kvd, dt),
                 "wo": weights.matrix(k["wo"], qd, d, dt)},
        "ln2": weights.scale(k["ln2"], d),
        "moe": {"router": weights.matrix(k["router"], d, e, jnp.float32),
                "w1": _experts(k["w1"], e, d, f, dt),
                "w3": _experts(k["w3"], e, d, f, dt),
                "w2": _experts(k["w2"], e, f, d, dt),
                "shared": {"w1": weights.matrix(k["shared_w1"], d, fs, dt),
                           "w3": weights.matrix(k["shared_w3"], d, fs, dt),
                           "w2": weights.matrix(k["shared_w2"], fs, d, dt)}},
    }


def build(key: jax.Array, config: Dict[str, Any]) -> Tree:
    keys = jax.vmap(lambda i: weights.layer_key(key, i))(
        jnp.arange(config["num_hidden_layers"]))
    params = {"embed": {"table": weights.embed_table(key, config)},
              "layers": jax.vmap(lambda k: layer(k, config))(keys),
              "final_norm": weights.final_norm(key, config)}
    if not config["tie_word_embeddings"]:
        params["lm_head"] = {"table": weights.head_table(key, config)}
    return params


def layer_matmul_params(c: Dict) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o, the
    router, and the SwiGLU of each expert it takes, routed and shared."""
    d, dh = c["hidden_size"], c["head_dim"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    taken = c["num_experts_per_tok"] + c["n_shared_experts"]
    return (2 * d * nh * dh + 2 * d * nkv * dh + d * c["n_routed_experts"]
            + taken * 3 * d * c["moe_intermediate_size"])


def _attention_flops(c: Dict, pairs: float) -> float:
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * pairs


def prefill_flops(c: Dict, plen: int) -> float:
    per_layer = (2.0 * plen * layer_matmul_params(c)
                 + _attention_flops(c, plen * (plen + 1) / 2))
    return c["num_hidden_layers"] * per_layer + work.head_flops(c)


def decode_flops(c: Dict, context: int) -> float:
    per_layer = 2.0 * layer_matmul_params(c) + _attention_flops(c, context)
    return c["num_hidden_layers"] * per_layer + work.head_flops(c)


def paged_attention_call(c: Dict, contexts: List[int]
                         ) -> Tuple[float, float]:
    nh, nkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eb = work.elem_bytes(c)
    flops = sum(_attention_flops(c, ctx) for ctx in contexts)
    nbytes = sum(2.0 * nkv * ctx * dh * eb + 2.0 * nh * dh * eb
                 for ctx in contexts)
    n_layers = c["num_hidden_layers"]
    return n_layers * flops, n_layers * nbytes
