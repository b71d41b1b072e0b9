"""Plain reference of a dense decoder, in float32 at HIGHEST precision.

Pre-norm layers: RMSNorm, grouped-query attention with rotary positions
(rotate-half, theta from the configuration) and an optional RMSNorm of
each query and key head, a residual add, RMSNorm, a SwiGLU MLP and a
residual add; a final RMSNorm and the output head.  Written from that
description in straightforward ``jax.numpy``; it imports nothing of the
program.  The weights are made again from the seed, one layer at a time
(``weights.py``), and raised from the served type to float32.

``control=True`` computes the same forward with every matmul operand
rounded to 4 significant bits, which is fp8 e4m3 with an ideal scale:
the precision below bfloat16 that a later change could be tempted by.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

HI = jax.lax.Precision.HIGHEST
PAD_TO = 256            # sequence lengths round up to this: few compiles


def fp8_round(x: jax.Array) -> jax.Array:
    """Round to 4 significant bits (1 implicit + 3 stored, as e4m3)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(spec: str, a, b, control: bool):
    if control:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x: (..., T, D); rotate-half rotary embedding at positions 0..T-1."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("config_items", "control"))
def _layer(x, lp, config_items: Tuple, control: bool):
    """One layer over a block of sequences x: (c, T, d) float32."""
    cfg = dict(config_items)
    c, t, _ = x.shape
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lp["ln1"], eps)
    q = _mm("ctd,df->ctf", h, lp["attn"]["wq"], control).reshape(c, t, nh, dh)
    k = _mm("ctd,df->ctf", h, lp["attn"]["wk"], control).reshape(c, t, nkv, dh)
    v = _mm("ctd,df->ctf", h, lp["attn"]["wv"], control).reshape(c, t, nkv, dh)
    if cfg["qk_norm"]:
        q = _rms(q, lp["attn"]["q_norm"], eps)
        k = _rms(k, lp["attn"]["k_norm"], eps)
    q = _rope(q.transpose(0, 2, 1, 3), cfg["rope_theta"])     # (c, nh, T, dh)
    k = _rope(k.transpose(0, 2, 1, 3), cfg["rope_theta"])     # (c, nkv, T, dh)
    v = v.transpose(0, 2, 1, 3)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = _mm("chqd,chkd->chqk", q, k, control) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("chqk,chkd->chqd", p, v, control)
    o = o.transpose(0, 2, 1, 3).reshape(c, t, nh * dh)
    x = x + _mm("ctf,fd->ctd", o, lp["attn"]["wo"], control)
    h = _rms(x, lp["ln2"], eps)
    gate = _mm("ctd,df->ctf", h, lp["mlp"]["w1"], control)
    up = _mm("ctd,df->ctf", h, lp["mlp"]["w3"], control)
    return x + _mm("ctf,fd->ctd", jax.nn.silu(gate) * up, lp["mlp"]["w2"],
                   control)


@functools.partial(jax.jit, static_argnames=("config_items",))
def _layer_weights(key, config_items: Tuple):
    lp = weights.layer(key, dict(config_items))
    return jax.tree.map(lambda a: a.astype(jnp.float32), lp)


@functools.partial(jax.jit, static_argnames=("config_items", "part"))
def _table(key, config_items: Tuple, part: str):
    cfg = dict(config_items)
    rows = (weights.embed_table if part == "embed"
            else weights.head_table)(key, cfg)
    return rows[:cfg["vocab_size"]].astype(jnp.float32)


def _items(config) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "qk_norm", "rope_theta", "hidden_size",
            "intermediate_size", "vocab_size", "torch_dtype",
            "tie_word_embeddings")
    return tuple((k, config[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("control",))
def _head_pass(h, head, served, control: bool):
    """Per scored position: the best logit, the logit of ``served``, and
    the argmax (first index of the best) of this pass's logits."""
    logits = _mm("nd,vd->nv", h, head, control)
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def _final_hidden(config, seed: int, sequences: List[np.ndarray],
                  scored: List[np.ndarray], control: bool) -> jax.Array:
    """Final-normed hidden states at the scored positions of each
    sequence, one layer at a time over every sequence, each sequence
    padded at its end to a multiple of ``PAD_TO`` (causal: the padding
    changes nothing before it)."""
    items = _items(config)
    key = weights.root_key(seed)
    embed = _table(key, items, "embed")
    if control:
        embed = fp8_round(embed)
    xs = []
    for seq in sequences:
        t = -(-len(seq) // PAD_TO) * PAD_TO
        tok = np.zeros((1, t), np.int32)
        tok[0, :len(seq)] = seq
        xs.append(jnp.take(embed, jnp.asarray(tok), axis=0))
    del embed
    for i in range(config["num_hidden_layers"]):
        lp = _layer_weights(weights.layer_key(key, i), items)
        xs = [_layer(x, lp, items, control) for x in xs]
        del lp
    h = jnp.concatenate([x[0, jnp.asarray(cols)] for x, cols
                         in zip(xs, scored)], 0)
    scale = weights.final_norm(key, config)
    return _rms(h, scale, config["rms_norm_eps"])


def score(config: Dict, seed: int,
          requests: Sequence[Tuple[np.ndarray, Sequence[int]]],
          control: bool = False) -> List[Dict[str, np.ndarray]]:
    """Teacher-forced reference over each (prompt, served tokens).

    For every served token: ``best``, the reference's best logit at its
    position, and ``at``, the reference's logit of the served token.
    With ``control``: also ``ctl_at``, the reference's logit of the token
    the fp8 forward puts first at that position.
    """
    vocab = config["vocab_size"]
    sequences, scored, served = [], [], []
    for prompt, out in requests:
        seq = np.concatenate([np.asarray(prompt, np.int64),
                              np.asarray(out[:-1], np.int64)])
        sequences.append(np.clip(seq, 0, vocab - 1).astype(np.int32))
        plen = len(prompt)
        scored.append(np.arange(plen - 1, plen - 1 + len(out)))
        served += list(out)
    served = np.asarray(served, np.int64)
    valid = (served >= 0) & (served < vocab)
    served_idx = jnp.asarray(np.where(valid, served, 0).astype(np.int32))
    key = weights.root_key(seed)
    h = _final_hidden(config, seed, sequences, scored, control=False)
    head = _table(key, _items(config), "head")
    best, at, _ = _head_pass(h, head, served_idx, control=False)
    out = {"best": np.array(best), "at": np.array(at)}
    out["at"][~valid] = -np.inf
    if control:
        hc = _final_hidden(config, seed, sequences, scored, control=True)
        _, _, ctl_tok = _head_pass(hc, head, served_idx, control=True)
        del hc
        _, ctl_at, _ = _head_pass(h, head, ctl_tok, control=False)
        out["ctl_at"] = np.array(ctl_at)
    del head, h
    result, start = [], 0
    for _, toks in requests:
        stop = start + len(toks)
        result.append({k: v[start:stop] for k, v in out.items()})
        start = stop
    return result
