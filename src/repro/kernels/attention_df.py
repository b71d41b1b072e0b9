"""Dataflow-parameterized attention Pallas kernels (TPU target).

The paper's central result — OS-anchored dataflows with auxiliary weight
stationarity win — *predicts* flash attention when applied to the attention
operator:

  * OS anchor: the output tile (one block of query rows) is the anchored
    operand; the online-softmax statistics and the output accumulator live
    in VMEM scratch across the whole KV sweep; outputs are written to HBM
    exactly once.  KV blocks stream (they are the "weights").
  * WS anchor (comparison variant): KV blocks are anchored — each is
    fetched exactly once — while the running (acc, m, l) partials are
    read-modify-written through HBM once per KV block.  This reproduces the
    paper's WS output-traffic pathology at attention scale and is used by
    the benchmarks, not the models.

Banded execution (PR 5): both lowerings take a *traced* valid KV length
(``kv_len`` — the filled prefix of a padded KV-cache buffer) and a
static or traced sliding ``window``, and skip KV blocks the mask fully
excludes — in the *grid*, not just in the lanes:

  * the banding scalars ride in a ``PrefetchScalarGridSpec`` info array,
    so the KV *index maps* clamp out-of-band grid steps onto the band's
    edge block (a revisited index — no new DMA is issued) and
    ``pl.when`` skips their compute entirely;
  * with a static window the flash grid's KV dimension itself shrinks to
    the band width ``ceil((bq + window - 1) / bkv) + 1`` and the WS
    compiled per-block loop drops statically-invisible blocks, so the
    skipped work disappears from the lowering (visible in the
    ``pallas_call`` grid / dispatch counts);
  * decode traffic therefore scales with the *valid* cache length, not
    ``max_len`` — the "prune work the dataflow can prove is masked"
    discipline the banded cost model (``cost_model.attention_band``)
    charges for.  The cost model and these index maps share one banding
    rule; keep them in sync.

Per-row banding (PR 8): ``kv_len`` may be a per-batch-row ``(R,)``
array — ``make_band_info`` then builds an ``(R, 2)`` info array and
every index map / mask derives its row as ``b // (bh // R)``, so a
ragged continuous-batching decode step bands each request at its own
valid length in ONE dispatch.  ``paged_flash_attention`` decodes off a
paged KV cache with one grid step per row: the lengths and block tables
ride in SMEM, and the step gathers the row's pages by manual DMA, each
page once for all heads, in a walk that stops at the row's length.

int8 KV caches dequantize at the block load: K/V stream as int8 with
per-position f32 scales (``k_scale``/``v_scale``, shape (BHkv, Skv, 1)),
multiplied in-register after the VMEM fetch — the cache never
round-trips HBM as a float copy.  (The (…, 1) scale lane is
interpret-mode friendly; a compiled TPU lowering would lane-pad it.)

GQA is handled by an index-map head mapping (q head -> kv head).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# "no sliding window" sentinel inside the banding info array (matches
# models/lm.FULL_WINDOW so traced per-layer windows pass through).
HUGE_WINDOW = 2 ** 30


# ---------------------------------------------------------------------------
# Banding: the one rule deciding which KV blocks a q tile visits.
# ---------------------------------------------------------------------------
def make_band_info(kv_len, window, window_dyn, skv_valid: int) -> jax.Array:
    """The int32 scalar-prefetch array: ``[valid KV length, window]``.

    ``kv_len`` (traced or int) overrides the static true length
    ``skv_valid``; ``window_dyn`` (traced) overrides the static
    ``window``; no window at all encodes as ``HUGE_WINDOW``.

    Shape contract (PR 8): a scalar ``kv_len`` yields the legacy
    ``(2,)`` array; a *per-batch-row* ``kv_len`` of shape ``(R,)``
    yields ``(R, 2)`` — one ``[kv_valid, window]`` pair per row — and
    the kernels derive each grid step's row as ``b // (bh // R)`` to
    band per row.  ``window``/``window_dyn`` broadcast across rows.
    """
    kv_valid = skv_valid if kv_len is None else kv_len
    if window_dyn is not None:
        w = window_dyn
    elif window is not None:
        w = window
    else:
        w = HUGE_WINDOW
    kv_valid = jnp.asarray(kv_valid, jnp.int32)
    if kv_valid.ndim == 1:                  # ragged: one band per row
        w = jnp.broadcast_to(jnp.asarray(w, jnp.int32).reshape(-1),
                             kv_valid.shape)
        return jnp.stack([kv_valid, w], axis=-1)
    return jnp.stack([
        kv_valid.reshape(()),
        jnp.asarray(w, jnp.int32).reshape(()),
    ])


def _info_pair(info, row):
    """``(kv_valid, window)`` for batch row ``row`` of an info array in
    either the legacy ``(2,)`` or the per-row ``(R, 2)`` shape."""
    if len(info.shape) == 2:
        return info[row, 0], info[row, 1]
    return info[0], info[1]


def _heads_per_row(bh: int, info: jax.Array) -> int:
    """Head-rows per batch row for a per-row ``(R, 2)`` info array; 0
    (the "no row mapping" sentinel) for the legacy ``(2,)`` shape."""
    if info.ndim != 2:
        return 0
    rows = info.shape[0]
    if bh % rows:
        raise ValueError(
            f"folded bh={bh} not divisible by {rows} per-row kv_len rows"
        )
    return bh // rows


def _band_lo_hi(i, info, *, bq: int, bkv: int, sq: int, causal: bool,
                windowed: bool, row=0):
    """Traced [lo, hi] inclusive KV-block band for q tile ``i`` of batch
    row ``row``.

    Mirrors ``cost_model.attention_band`` exactly (the cost model is the
    documented source of the rule): q rows right-align against the valid
    KV length, ``hi`` is clamped by the valid prefix and the causal
    diagonal, ``lo`` by the sliding window.  ``row`` indexes a per-row
    ``(R, 2)`` info array (ignored for the legacy ``(2,)`` shape).
    """
    kv_valid, win = _info_pair(info, row)
    off = kv_valid - sq
    hi = jnp.maximum(0, (kv_valid + bkv - 1) // bkv - 1)
    if causal:
        qmax = (i + 1) * bq - 1 + off
        hi = jnp.minimum(hi, jnp.maximum(qmax, 0) // bkv)
    if windowed:
        qmin = i * bq + off
        lo = jnp.maximum(0, (qmin - win + 1) // bkv)
        lo = jnp.minimum(lo, hi)
    else:
        lo = jnp.zeros_like(hi)
    return lo, hi


def static_band(gkv: int, skv_valid: int, bq: int, bkv: int,
                window: Optional[int], causal: bool = True) -> int:
    """The static KV grid extent per q tile (the flash grid's dim 2).

    The valid true length bounds it at ``ceil(skv_valid / bkv)``; a
    *static* window under a *causal* mask tightens it to the band
    width — each q tile's visible blocks then span at most
    ``bq + window - 1`` positions.  Without the causal upper bound the
    window only cuts the past (the band still reaches the last valid
    block), so no static shrink applies.  Traced lengths/windows can
    only shrink the band further at run time (the index-map clamp +
    ``pl.when`` skip handle those steps).
    """
    band = -(-skv_valid // bkv)
    if window is not None and causal:
        band = min(band, -(-(bq + window - 1) // bkv) + 1)
    return max(1, min(band, gkv))


def _score_mask(i, jblk, info, *, bq: int, bkv: int, sq: int, causal: bool,
                windowed: bool, row=0):
    """(bq, bkv) lane mask for q tile ``i`` against KV block ``jblk``."""
    kv_valid, win = _info_pair(info, row)
    off = kv_valid - sq
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + off
    kpos = jblk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < kv_valid
    if causal:
        mask &= kpos <= qpos
    if windowed:
        mask &= kpos > qpos - win
    return mask


def _load_kv(k_ref, v_ref, ks_ref, vs_ref):
    """Fetch one KV block, dequantizing int8 at the load when scales
    are present — the float image exists only in registers/VMEM."""
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    if ks_ref is not None:
        k = k * ks_ref[0]                 # (bkv, 1) per-position scales
        v = v * vs_ref[0]
    return k, v


# ---------------------------------------------------------------------------
# OS-anchored (flash) attention.
# ---------------------------------------------------------------------------
def _flash_kernel(info_ref, *refs, bq: int, bkv: int, band: int,
                  scale: float, causal: bool, windowed: bool, sq: int,
                  quant: bool, heads: int = 0):
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref \
            = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    i, jr = pl.program_id(1), pl.program_id(2)
    # per-row banding: grid dim 0 walks batch*heads; ``heads`` head-rows
    # share each batch row's [kv_valid, window] pair (0 = legacy scalar)
    row = pl.program_id(0) // heads if heads else 0
    lo, hi = _band_lo_hi(i, info_ref, bq=bq, bkv=bkv, sq=sq, causal=causal,
                         windowed=windowed, row=row)
    jblk = jnp.minimum(lo + jr, hi)       # == the index-map fetch

    @pl.when(jr == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(lo + jr <= hi)               # out-of-band step: zero work
    def _update():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k, v = _load_kv(k_ref, v_ref, ks_ref, vs_ref)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = _score_mask(i, jblk, info_ref, bq=bq, bkv=bkv, sq=sq,
                           causal=causal, windowed=windowed, row=row)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                         # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # explicit lane zeroing: a fully-masked block must contribute
        # nothing even while m is still NEG_INF (exp(s - m_new) = 1.0)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jr == band - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # (BH, Sq, D)   batch*q_heads folded
    k: jax.Array,            # (BHkv, Skv, D)  float, or int8 with scales
    v: jax.Array,
    group: int = 1,          # q_heads per kv head (GQA)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    skv_valid: Optional[int] = None,
    sq_valid: Optional[int] = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
    kv_len: Optional[jax.Array] = None,
    window_dyn: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,   # (BHkv, Skv, 1) f32
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """OS-anchored attention. Sq % bq == 0 and Skv % bkv == 0 (pre-padded).

    ``sq_valid``/``skv_valid`` are the true (pre-padding) lengths;
    ``kv_len`` (traced) restricts further to the filled prefix of a
    KV-cache buffer and the causal mask right-aligns the true q rows
    against it.  The KV grid dimension is the static band
    (``static_band``); out-of-band steps are clamped onto the band edge
    by the index maps (no DMA) and skipped by ``pl.when`` (no compute),
    so realized traffic scales with the *visited* blocks the banded
    cost model charges.  ``bq``/``bkv`` come from the caller —
    ``ops.attention`` resolves them from the autotuned registry spec
    and clamps them (``cost_model.attention_block_clamp``) before
    calling in.  int8 K/V dequantize at the block load via the
    per-position ``k_scale``/``v_scale``.
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    gq, gkv = sq // bq, skv // bkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    skv_valid = skv if skv_valid is None else skv_valid
    sq_valid = sq if sq_valid is None else sq_valid
    windowed = window is not None or window_dyn is not None
    quant = k_scale is not None
    band = static_band(gkv, skv_valid, bq, bkv, window, causal)
    info = make_band_info(kv_len, window, window_dyn, skv_valid)
    heads = _heads_per_row(bh, info)
    bounds = dict(bq=bq, bkv=bkv, sq=sq_valid, causal=causal,
                  windowed=windowed)

    def kv_block(b, i, jr, info_ref):
        lo, hi = _band_lo_hi(i, info_ref,
                             row=b // heads if heads else 0, **bounds)
        return jnp.minimum(lo + jr, hi)

    kernel = functools.partial(
        _flash_kernel, band=band, scale=scale, quant=quant, heads=heads,
        **bounds,
    )
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, jr, info: (b, i, 0)),
        pl.BlockSpec((1, bkv, d),
                     lambda b, i, jr, info, g=group:
                     (b // g, kv_block(b, i, jr, info), 0)),
        pl.BlockSpec((1, bkv, d),
                     lambda b, i, jr, info, g=group:
                     (b // g, kv_block(b, i, jr, info), 0)),
    ]
    args = [q, k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, bkv, 1),
                         lambda b, i, jr, info, g=group:
                         (b // g, kv_block(b, i, jr, info), 0)),
            pl.BlockSpec((1, bkv, 1),
                         lambda b, i, jr, info, g=group:
                         (b // g, kv_block(b, i, jr, info), 0)),
        ]
        args += [k_scale, v_scale]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, gq, band),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda b, i, jr, info: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(info, *args)


# ---------------------------------------------------------------------------
# WS-anchored (KV-stationary) attention: benchmark variant.
# ---------------------------------------------------------------------------
def _kv_stationary_kernel(info_ref, *refs, jk: Optional[int], bq: int,
                          bkv: int, scale: float, causal: bool,
                          windowed: bool, sq: int, quant: bool,
                          heads: int = 0):
    """One KV block's online-softmax update.

    ``jk=None``: single-dispatch form — the KV sweep is grid dim 1, the
    state refs are the revisited output buffers (in == out), initialized
    in-kernel at the first KV block.  ``jk=int``: per-block form — one
    call per KV block, state carried through aliased input/output pairs.
    Banding: a (KV block, q tile) pair outside the visible band updates
    nothing (the state passes through); beyond-valid KV blocks are
    additionally clamped in the index maps so they issue no DMA.
    """
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref,
         acc_in, m_in, l_in, acc_out, m_out, l_out) = refs
    else:
        q_ref, k_ref, v_ref, acc_in, m_in, l_in, acc_out, m_out, l_out = refs
        ks_ref = vs_ref = None
    if jk is None:
        jk_idx, iq = pl.program_id(1), pl.program_id(2)

        @pl.when(jk_idx == 0)
        def _init():
            acc_in[...] = jnp.zeros_like(acc_in)
            m_in[...] = jnp.full_like(m_in, NEG_INF)
            l_in[...] = jnp.zeros_like(l_in)
    else:
        jk_idx, iq = jk, pl.program_id(1)

    row = pl.program_id(0) // heads if heads else 0
    bounds = dict(bq=bq, bkv=bkv, sq=sq, causal=causal, windowed=windowed)
    lo, hi = _band_lo_hi(iq, info_ref, row=row, **bounds)
    visible = (jk_idx >= lo) & (jk_idx <= hi)

    @pl.when(visible)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k, v = _load_kv(k_ref, v_ref, ks_ref, vs_ref)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = _score_mask(iq, jk_idx, info_ref, row=row, **bounds)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_in[0][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_in[0][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_out[0] = acc_in[0] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_out[0] = jnp.broadcast_to(m_new, m_out.shape[1:])
        l_out[0] = jnp.broadcast_to(l_new, l_out.shape[1:])

    if jk is not None:
        # per-block form: an invisible pair must still carry the state
        # through its aliased output buffers
        @pl.when(~visible)
        def _carry():
            acc_out[0] = acc_in[0]
            m_out[0] = m_in[0]
            l_out[0] = l_in[0]


def _kv_single_kernel(info_ref, q_ref, k_ref, v_ref, *rest, **kw):
    if kw["quant"]:
        ks_ref, vs_ref, acc_ref, m_ref, l_ref = rest
        refs = (q_ref, k_ref, v_ref, ks_ref, vs_ref,
                acc_ref, m_ref, l_ref, acc_ref, m_ref, l_ref)
    else:
        acc_ref, m_ref, l_ref = rest
        refs = (q_ref, k_ref, v_ref,
                acc_ref, m_ref, l_ref, acc_ref, m_ref, l_ref)
    _kv_stationary_kernel(info_ref, *refs, **kw)


def _ws_block_statically_invisible(jk: int, bkv: int, sq_valid: int,
                                   skv_valid: int,
                                   window: Optional[int],
                                   traced_bounds: bool) -> bool:
    """Can the compiled per-block WS loop drop KV block ``jk`` outright?

    Only static knowledge prunes the dispatch list: with a static
    window and no traced valid length, a block whose end precedes every
    q row's window start is invisible to the whole tile range.  Traced
    bounds fall back to the in-kernel skip (the call still lowers).
    """
    if traced_bounds or window is None:
        return False
    qmin_global = skv_valid - sq_valid      # first true q row, aligned
    return (jk + 1) * bkv - 1 <= qmin_global - window


def kv_stationary_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    group: int = 1, causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, skv_valid: Optional[int] = None,
    sq_valid: Optional[int] = None,
    bq: int = 128, bkv: int = 128, interpret: bool = False,
    kv_len: Optional[jax.Array] = None,
    window_dyn: Optional[jax.Array] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """WS-anchored attention: each KV block fetched exactly once, the
    (acc, m, l) running partials round-tripping HBM once per KV block
    (the paper's WS output traffic).

    ``bq``/``bkv`` come from the caller on BOTH lowerings — the
    interpret-mode single dispatch and the compiled per-KV-block
    aliased-call loop — so when ``ops.attention`` resolves them from
    the autotuned registry spec, both anchors honor the autotuned
    block.

    Banding: the KV dimension only spans the statically-valid blocks
    (``ceil(skv_valid / bkv)``), a static window drops statically-
    invisible blocks from the compiled dispatch loop, and traced
    ``kv_len``/``window_dyn`` clamp the KV index maps (no DMA) and skip
    per-pair compute in-kernel.  int8 K/V dequantize at the block load
    via the per-position scales.

    In interpret mode — where this benchmark variant runs and is
    compared against flash attention — it lowers as ONE ``pallas_call``
    with grid (bh, gkv, gq): the state blocks, indexed by the *inner*
    q-tile dim, are revisited once per KV block and carry the partials
    between non-consecutive visits through their HBM buffers
    (initialized in-kernel at the first KV block, no zeros-init arrays),
    so per-block dispatch overhead no longer pollutes the OS/WS
    comparison.  Persisting output blocks across non-consecutive
    revisits relies on sequential grid execution — an interpret-mode
    property, not a documented Pallas TPU guarantee — so on compiled
    backends the realized lowering stays the well-defined per-KV-block
    aliased-call loop (same traffic, one dispatch per visited block).
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    gq, gkv = sq // bq, skv // bkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    skv_valid = skv if skv_valid is None else skv_valid
    sq_valid = sq if sq_valid is None else sq_valid
    windowed = window is not None or window_dyn is not None
    quant = k_scale is not None
    gkv_v = max(1, min(gkv, -(-skv_valid // bkv)))  # statically-valid blocks
    info = make_band_info(kv_len, window, window_dyn, skv_valid)
    heads = _heads_per_row(bh, info)
    kw = dict(bq=bq, bkv=bkv, scale=scale, causal=causal, windowed=windowed,
              sq=sq_valid, quant=quant, heads=heads)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
    ]

    def kv_clamp(b, j, info_ref):
        """Fetchable block for grid step ``j``: out-of-band steps alias
        the band's edge blocks — above the valid prefix AND below the
        global window start (tile 0's band) — so they re-use an
        adjacent step's index and issue no new DMA."""
        kv_valid, win = _info_pair(info_ref, b // heads if heads else 0)
        hi = jnp.maximum(0, (kv_valid + bkv - 1) // bkv - 1)
        lo = jnp.zeros_like(hi)
        if windowed:
            off = kv_valid - sq_valid
            lo = jnp.minimum(jnp.maximum(0, (off - win + 1) // bkv), hi)
        return jnp.clip(j, lo, hi)

    if interpret:
        state_spec = pl.BlockSpec((1, bq, d),
                                  lambda b, j, i, info: (b, i, 0))
        stat_spec = pl.BlockSpec((1, bq, 128),
                                 lambda b, j, i, info: (b, i, 0))
        kv_spec = pl.BlockSpec(
            (1, bkv, d),
            lambda b, j, i, info, g=group:
            (b // g, kv_clamp(b, j, info), 0))
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, j, i, info: (b, i, 0)),
            kv_spec, kv_spec,
        ]
        args = [q, k, v]
        if quant:
            sc_spec = pl.BlockSpec(
                (1, bkv, 1),
                lambda b, j, i, info, g=group:
                (b // g, kv_clamp(b, j, info), 0))
            in_specs += [sc_spec, sc_spec]
            args += [k_scale, v_scale]
        acc, m, l = pl.pallas_call(
            functools.partial(_kv_single_kernel, jk=None, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(bh, gkv_v, gq),
                in_specs=in_specs,
                out_specs=[state_spec, stat_spec, stat_spec],
            ),
            out_shape=out_shape,
            interpret=True,
        )(info, *args)
    else:
        acc = jnp.zeros((bh, sq, d), jnp.float32)
        m = jnp.full((bh, sq, 128), NEG_INF, jnp.float32)
        l = jnp.zeros((bh, sq, 128), jnp.float32)
        state_spec = pl.BlockSpec((1, bq, d), lambda b, i, info: (b, i, 0))
        stat_spec = pl.BlockSpec((1, bq, 128), lambda b, i, info: (b, i, 0))
        traced_bounds = kv_len is not None or window_dyn is not None
        for jk in range(gkv_v):
            if _ws_block_statically_invisible(jk, bkv, sq_valid, skv_valid,
                                              window, traced_bounds):
                continue                    # zero dispatch work
            kv_spec = pl.BlockSpec(
                (1, bkv, d),
                lambda b, i, info, j=jk, g=group:
                (b // g, kv_clamp(b, j, info), 0))
            in_specs = [
                pl.BlockSpec((1, bq, d), lambda b, i, info: (b, i, 0)),
                kv_spec, kv_spec,
            ]
            args = [q, k, v]
            n_in = 3
            if quant:
                sc_spec = pl.BlockSpec(
                    (1, bkv, 1),
                    lambda b, i, info, j=jk, g=group:
                    (b // g, kv_clamp(b, j, info), 0))
                in_specs += [sc_spec, sc_spec]
                args += [k_scale, v_scale]
                n_in = 5
            acc, m, l = pl.pallas_call(
                functools.partial(_kv_stationary_kernel, jk=jk, **kw),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(bh, gq),
                    in_specs=in_specs + [state_spec, stat_spec, stat_spec],
                    out_specs=[state_spec, stat_spec, stat_spec],
                ),
                out_shape=out_shape,
                input_output_aliases={n_in + 1: 0, n_in + 2: 1,
                                      n_in + 3: 2},
            )(info, *args, acc, m, l)
    lsafe = jnp.where(l[:, :, :1] == 0.0, 1.0, l[:, :, :1])
    return (acc / lsafe).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged (block-table) decode attention: one grid step per row, walking
# the row's pages in blocks fetched once for all heads.
# ---------------------------------------------------------------------------
# A block of the paged walk spans at least this many positions...
PAGED_BLOCK_POSITIONS = 128
# ...as long as its double-buffered K and V fit in this much VMEM.
PAGED_BLOCK_VMEM = 4 * 2 ** 20


def paged_pages_per_block(page: int, d: int, hkv: int, max_pages: int,
                          itemsize: int) -> int:
    """Pages per block of the paged walk, from shapes alone: enough for
    ``PAGED_BLOCK_POSITIONS`` positions, no more than two K and two V
    blocks of all ``hkv`` heads fit in ``PAGED_BLOCK_VMEM``, and no more
    than a row's block table holds."""
    want = pl.cdiv(PAGED_BLOCK_POSITIONS, page)
    fit = PAGED_BLOCK_VMEM // (4 * hkv * page * d * itemsize)
    return max(1, min(want, fit, max_pages))


def _p_dot_v(p, v, v_ok):
    """``p · v`` with float32 ``p`` and products, rows of ``v`` outside
    ``v_ok`` taken as zeros.  A bf16 ``v`` meets ``p`` split exactly into
    three bf16 parts (8 + 8 + 8 significant bits) stacked as rows of one
    matmul: exact products, float32 sums, one pass over ``v``."""
    if v.dtype != jnp.bfloat16:
        v = jnp.where(v_ok, v.astype(jnp.float32), 0.0)
        return jnp.dot(p, v, preferred_element_type=jnp.float32)
    v = jnp.where(v_ok, v, jnp.zeros_like(v))
    hi = p.astype(jnp.bfloat16)
    rest = p - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    n = p.shape[0]
    out = jnp.dot(jnp.concatenate([hi, mid, lo], axis=0), v,
                  preferred_element_type=jnp.float32)
    return out[:n] + out[n:2 * n] + out[2 * n:]


def _paged_kernel(lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, k_sems, v_sems, acc_ref, m_ref, l_ref, *,
                  page: int, ppb: int, max_pages: int, group: int,
                  scale: float, window: Optional[int]):
    row = pl.program_id(0)
    hkv, blk = k_buf.shape[1], k_buf.shape[2]
    hq = acc_ref.shape[0]
    kv_valid = lens_ref[row]
    n_pages = jnp.minimum(pl.cdiv(kv_valid, page), max_pages)
    if window is not None:
        first = jnp.minimum(jnp.maximum(0, (kv_valid - window) // page),
                            n_pages)
    else:
        first = jnp.int32(0)
    n_blocks = pl.cdiv(n_pages - first, ppb)

    def page_copies(j, slot, i):
        # one strided copy moves a page of every KV head
        pid = tables_ref[row * max_pages + first + j * ppb + i]
        dst = pl.ds(pl.multiple_of(i * page, page), page)
        return (pltpu.make_async_copy(k_hbm.at[:, pid],
                                      k_buf.at[slot, :, dst],
                                      k_sems.at[slot]),
                pltpu.make_async_copy(v_hbm.at[:, pid],
                                      v_buf.at[slot, :, dst],
                                      v_sems.at[slot]))

    def in_block(j):
        # pages of block j below the row's length; the rest stay unfetched
        return jnp.minimum(ppb, n_pages - first - j * ppb)

    def start(j, slot):
        def body(i, c):
            for cp in page_copies(j, slot, i):
                cp.start()
            return c
        jax.lax.fori_loop(0, in_block(j), body, 0)

    def wait(j, slot):
        def body(i, c):
            for cp in page_copies(j, slot, i):
                cp.wait()
            return c
        jax.lax.fori_loop(0, in_block(j), body, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_blocks > 0)
    def _prefetch():
        start(0, 0)

    q = q_ref[...]                                          # (hq, d)
    if q.dtype != k_buf.dtype:
        q = q.astype(jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // group

    def step(j, c):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            start(j + 1, 1 - slot)

        wait(j, slot)
        pos0 = (first + j * ppb) * page
        kpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        mask = kpos < kv_valid          # decode q row == position kv_valid-1
        if window is not None:
            mask &= kpos > kv_valid - 1 - window
        # positions past the row's length may sit in unfetched pages
        vpos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        v_ok = vpos < kv_valid
        # every q row against each KV head's block; a row keeps its own
        # head's scores (the group's heads share the fetch)
        s = jnp.zeros((hq, blk), jnp.float32)
        for h in range(hkv):
            k = k_buf[slot, h]                              # (blk, d)
            if k.dtype != q.dtype:
                k = k.astype(jnp.float32)
            sh = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(head == h, sh, s)
        s = jnp.where(mask, s * scale, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.zeros(acc_ref.shape, jnp.float32)
        for h in range(hkv):
            pv += _p_dot_v(jnp.where(head == h, p, 0.0), v_buf[slot, h],
                           v_ok)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return c

    jax.lax.fori_loop(0, n_blocks, step, 0)
    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_flash_attention(
    q: jax.Array,             # (R, HQ, D) decode queries, one row each
    k_pages: jax.Array,       # (HKV, P, page, D) shared page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (R, max_pages) int32 page ids per row
    kv_lens: jax.Array,       # (R,) int32 valid lengths per row
    group: int = 1,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """OS-anchored decode attention over a paged KV cache: (R, HQ, D).

    One grid step per row.  The row's queries of all ``HQ`` heads are
    the anchored operand: the output accumulator and the online-softmax
    statistics stay in VMEM for the whole walk, and the output is
    written once.  K and V stay in HBM; the step walks the row's pages
    in blocks of ``ppb`` pages with an in-kernel loop whose trip count
    comes from the row's length, so the grid does not grow with
    ``max_pages`` and a row at length 0 fetches nothing and writes
    zeros.  Each page is one strided copy of all KV heads, gathered
    from the block table (in SMEM with the lengths) into a
    double-buffered VMEM block while the previous block computes; the
    ``group`` q heads of a KV head share that fetch.

    Only pages below ``ceil(kv_len / page)`` are fetched, and with a
    static ``window`` only those from the page holding the band's first
    position on; the masks drop positions outside the band within the
    pages that are fetched.  ``ppb`` comes from shapes alone
    (:func:`paged_pages_per_block`): a block of at least
    ``PAGED_BLOCK_POSITIONS`` positions whose double-buffered K and V
    fit ``PAGED_BLOCK_VMEM``, at most ``max_pages``.  Scores and ``p·V``
    accumulate in float32.  Table slots past a row's pages are never
    read (0 by convention).  Float pools only; the int8-KV scale
    sidecar stays on the contiguous path.
    """
    rows, hq, d = q.shape
    hkv, _, page, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    if block_tables.shape[0] != rows:
        raise ValueError(
            f"{block_tables.shape[0]} block tables for {rows} rows")
    if hq != hkv * group:
        raise ValueError(
            f"{hq} q heads per row != pool heads {hkv} * group {group}"
        )
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    ppb = paged_pages_per_block(page, d, hkv, max_pages,
                                jnp.dtype(k_pages.dtype).itemsize)
    blk = ppb * page
    kernel = functools.partial(
        _paged_kernel, page=page, ppb=ppb, max_pages=max_pages,
        group=group, scale=scale, window=window,
    )
    row_spec = pl.BlockSpec((None, hq, d), lambda r, lens, tables: (r, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[
                row_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, hkv, blk, d), k_pages.dtype),
                pltpu.VMEM((2, hkv, blk, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hq, d), jnp.float32),
                pltpu.VMEM((hq, 128), jnp.float32),
                pltpu.VMEM((hq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, hq, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(kv_lens, jnp.int32).reshape(rows),
      jnp.asarray(block_tables, jnp.int32).reshape(rows * max_pages),
      q, k_pages, v_pages)
