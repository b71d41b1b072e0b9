"""Mixture-of-Experts layer: sort-based capacity dispatch + EP all-to-all.

Dispatch is scatter/sort-based (no GShard one-hot (T, E, C) tensor — that
blows past HBM at 128 experts); tokens are sorted by expert id, placed into
an (E, C, D) capacity buffer, exchanged over the ``model`` mesh axis with
``jax.lax.all_to_all`` (expert parallelism), run through the local experts
as one batched GEMM, and returned.

Two modes:
  * ``moe_apply`` — local (single shard) path: used by smoke tests and as
    the shard_map body.
  * ``moe_apply_sharded`` — shard_map-wrapped EP path used by the
    distributed train/serve steps; the all-to-alls appear explicitly in
    the lowered HLO (they are the collective term of the MoE roofline).

Shared experts (moonshot-style) run as a plain dense MLP on every token —
data-independent of the dispatched path, so XLA overlaps them with the
all-to-all (documented in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers

Params = Dict[str, jax.Array]


def init_moe(key, cfg) -> Params:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = (2.0 / (d + f)) ** 0.5
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32)
                   * d ** -0.5).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale
               ).astype(dt),
        "w3": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale
               ).astype(dt),
        "w2": (jax.random.normal(ks[3], (e, f, d), jnp.float32) * scale
               ).astype(dt),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = layers.init_mlp(ks[4], d, fs, cfg.param_dtype)
    return p


def _route(x_flat: jax.Array, router: jax.Array, top_k: int):
    """Top-k routing with renormalized gates. x_flat: (T, D)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(probs, top_k)              # (T, k)
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    e = router.shape[1]
    f_e = jnp.mean(
        jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(axis=1), axis=0
    )
    p_e = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(f_e * p_e) / top_k
    return top_g, top_e, aux_loss


def _dispatch_indices(top_e: jax.Array, top_k: int, n_experts: int,
                      capacity: int):
    """Sort token->expert assignments; compute per-expert slot positions."""
    t = top_e.shape[0]
    flat_e = top_e.reshape(-1)                              # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]                                      # sorted expert id
    st = order // top_k                                     # source token
    starts = jnp.searchsorted(se, jnp.arange(n_experts), side="left")
    pos = jnp.arange(t * top_k) - starts[se]
    keep = pos < capacity
    pos_c = jnp.minimum(pos, capacity - 1)
    return order, se, st, pos_c, keep


def _expert_ffn(p: Params, xs: jax.Array) -> jax.Array:
    """Batched SwiGLU over experts: xs (E_loc, C*, D)."""
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["w1"]))
    up = jnp.einsum("ecd,edf->ecf", xs, p["w3"])
    return jnp.einsum("ecf,efd->ecd", gate * up, p["w2"])


# §Perf iteration 4: int8-compressed dispatch all-to-all.  Forward sends
# int8 payload + per-slot scales (~2x fewer ICI bytes); backward routes the
# cotangent through a plain bf16 all-to-all (straight-through estimator —
# the quantization error is treated as identity, the standard MoE-a2a
# compression arrangement).
A2A_INT8 = True


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _a2a(x, axis_name: str, split_axis: int, concat_axis: int):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=False)


def _a2a_fwd(x, axis_name, split_axis, concat_axis):
    if not A2A_INT8:
        return _a2a(x, axis_name, split_axis, concat_axis), None
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    q = jax.lax.all_to_all(q, axis_name, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=False)
    scale = jax.lax.all_to_all(scale, axis_name, split_axis=split_axis,
                               concat_axis=concat_axis, tiled=False)
    return (q.astype(jnp.float32) * scale).astype(x.dtype), None


def _a2a_bwd(axis_name, split_axis, concat_axis, _, g):
    # all_to_all is its own inverse with swapped axes
    return (jax.lax.all_to_all(g, axis_name, split_axis=concat_axis,
                               concat_axis=split_axis, tiled=False),)


_a2a.defvjp(_a2a_fwd, _a2a_bwd)


def moe_apply(
    p: Params, x: jax.Array, cfg, ep_axis: Optional[str] = None
) -> Tuple[jax.Array, jax.Array]:
    """MoE block. x: (B, S, D). Returns (y, aux_loss).

    With ``ep_axis`` set this function is running inside shard_map: experts
    in ``p`` are the local shard (E_loc = E / axis_size) and capacity
    buffers are exchanged with all_to_all over that axis.
    """
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    e, k = cfg.n_experts, cfg.top_k
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    e_loc = e // ep

    top_g, top_e, aux = _route(x_flat, p["router"], k)
    capacity = max(8, int(cfg.capacity_factor * t * k / e))
    order, se, st, pos_c, keep = _dispatch_indices(top_e, k, e, capacity)

    buf = jnp.zeros((e, capacity, d), x.dtype)
    vals = x_flat[st] * keep[:, None].astype(x.dtype)
    buf = buf.at[se, pos_c].add(vals)

    if ep_axis:
        # (E, C, D) -> (ep, E_loc, C, D) -> exchange -> local experts hold
        # one (C) slab from every peer: (ep, E_loc, C, D) -> (E_loc, ep*C, D)
        buf = buf.reshape(ep, e_loc, capacity, d)
        buf = _a2a(buf, ep_axis, 0, 0)
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, ep * capacity, d)

    out_buf = _expert_ffn(p, buf)

    if ep_axis:
        out_buf = out_buf.reshape(e_loc, ep, capacity, d).transpose(1, 0, 2, 3)
        out_buf = _a2a(out_buf, ep_axis, 0, 0)
        out_buf = out_buf.reshape(e, capacity, d)

    gathered = out_buf[se, pos_c] * keep[:, None].astype(out_buf.dtype)
    y_sorted = jnp.zeros((t * k, d), x.dtype)
    y_flat = y_sorted.at[order].set(gathered.astype(x.dtype))
    y = (y_flat.reshape(t, k, d)
         * top_g[..., None].astype(x.dtype)).sum(axis=1)

    if "shared" in p:
        y = y + layers.mlp_apply(p["shared"], x_flat, cfg)

    return y.reshape(b, s, d), aux


def moe_apply_psum_local(
    p: Params, x: jax.Array, cfg, ep_axis: str
) -> Tuple[jax.Array, jax.Array]:
    """EP without all-to-all: every shard routes all its tokens, runs only
    its local experts, and the outputs are psum-combined over the EP axis.

    Used for decode (seq=1 cannot shard over the model axis) where the
    token count is tiny and the psum of (T, D) is cheaper than an a2a.
    """
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    e, k = cfg.n_experts, cfg.top_k
    ep = jax.lax.axis_size(ep_axis)
    e_loc = e // ep
    rank = jax.lax.axis_index(ep_axis)

    top_g, top_e, aux = _route(x_flat, p["router"], k)
    capacity = max(8, int(cfg.capacity_factor * t * k / e))
    order, se, st, pos_c, keep = _dispatch_indices(top_e, k, e, capacity)

    buf = jnp.zeros((e, capacity, d), x.dtype)
    vals = x_flat[st] * keep[:, None].astype(x.dtype)
    buf = buf.at[se, pos_c].add(vals)
    # local experts only: slice [rank*e_loc, (rank+1)*e_loc)
    buf_loc = jax.lax.dynamic_slice_in_dim(buf, rank * e_loc, e_loc, axis=0)
    out_loc = _expert_ffn(p, buf_loc)
    out_buf = jnp.zeros((e, capacity, d), out_loc.dtype)
    out_buf = jax.lax.dynamic_update_slice_in_dim(
        out_buf, out_loc, rank * e_loc, axis=0
    )

    gathered = out_buf[se, pos_c] * keep[:, None].astype(out_buf.dtype)
    y_flat = jnp.zeros((t * k, d), x.dtype).at[order].set(
        gathered.astype(x.dtype))
    y = (y_flat.reshape(t, k, d)
         * top_g[..., None].astype(x.dtype)).sum(axis=1)
    y = jax.lax.psum(y, ep_axis)
    if "shared" in p:
        y = y + layers.mlp_apply(p["shared"], x_flat, cfg)
    return y.reshape(b, s, d), aux


def moe_apply_sharded(
    p: Params, x: jax.Array, cfg, mesh: jax.sharding.Mesh,
    dp_axes: Tuple[str, ...], tp_axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """shard_map-wrapped EP MoE.

    Training/prefill: x sharded (batch over dp_axes, seq over tp_axis);
    experts over tp_axis (EP == TP, n_experts % tp == 0); capacity
    buffers exchanged by all_to_all.  Decode (seq < tp): psum-local mode.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[tp_axis]
    s = x.shape[1]
    a2a_mode = s % tp == 0 and s >= tp

    pspec_x = P(dp_axes, tp_axis if a2a_mode else None, None)
    pspec_experts = P(tp_axis, None, None)
    in_specs = (
        {
            **{kk: pspec_experts for kk in ("w1", "w2", "w3")},
            "router": P(),
            **({"shared": {kk: P() for kk in ("w1", "w2", "w3")}}
               if "shared" in p else {}),
        },
        pspec_x,
    )

    def body(p_loc, x_loc):
        if a2a_mode:
            y, aux = moe_apply(p_loc, x_loc, cfg, ep_axis=tp_axis)
        else:
            y, aux = moe_apply_psum_local(p_loc, x_loc, cfg, ep_axis=tp_axis)
        aux = jax.lax.pmean(jax.lax.pmean(aux, tp_axis), dp_axes)
        return y, aux

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(pspec_x, P()),
    )
    return fn(p, x)
