"""Cost models: the paper's Table-I heuristics, adapted to TPU, + rooflines.

Two clearly-separated models (DESIGN.md §5.2):

1. ``table1_reduction``   — the paper's CPU/SIMD memory-instruction-reduction
   closed forms, reproduced *literally* (per additional vector variable).
   Used to validate Observations 1-5 and by ``benchmarks/bench_heuristics``.

2. ``gemm_traffic`` / ``conv_traffic`` — the TPU adaptation: HBM<->VMEM bytes
   moved by a tiled Pallas kernel under a given ``DataflowSpec`` (grid order
   + VMEM residency).  This is what the explorer ranks on.

Plus the roofline terms used by EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

from repro.core.dataflow import (
    ANCHOR_GRID_ORDER,
    AttentionProblem,
    BinaryProblem,
    ConvProblem,
    DataflowSpec,
    GemmProblem,
    Residency,
    Stationarity,
    IS,
    OS,
    WS,
)

_DTYPE_BYTES = {
    "float64": 8,
    "float32": 4,
    "bfloat16": 2,
    "float16": 2,
    "int32": 4,
    "uint32": 4,
    "int8": 1,
    "uint8": 1,
    "bool": 1,
    "binary_packed": 4,  # 32 binary channels per uint32 lane
}


def dtype_bytes(dtype: str) -> int:
    key = str(dtype)
    if key not in _DTYPE_BYTES:
        raise KeyError(f"unknown dtype {dtype!r}")
    return _DTYPE_BYTES[key]


# ---------------------------------------------------------------------------
# Hardware description (TPU v5e class; see task spec for the constants).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw: float = 50e9                # bytes/s per ICI link
    vmem_bytes: int = 16 * 1024 * 1024  # software-managed fast memory
    lane: int = 128                     # minor-dim tiling
    sublane: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"float32": 8, "bfloat16": 16, "int8": 32}
    )

    def peak_flops_for(self, dtype: str) -> float:
        # int8 runs at 2x bf16 on the MXU; fp32 at ~1/4 (v5e has no fp32 MXU,
        # fp32 matmuls decompose); binary uses the VPU xor+popcount path.
        scale = {
            "bfloat16": 1.0,
            "float16": 1.0,
            "int8": 2.0,
            "float32": 0.25,
            "binary_packed": 0.5,
        }.get(str(dtype), 1.0)
        return self.peak_flops * scale


V5E = HardwareSpec()

# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s per chip.  A TPU whose kind is missing here is an error,
# never a silent V5E.
HARDWARE_BY_KIND: Dict[str, HardwareSpec] = {"TPU v5 lite": V5E}


def hardware_for(device=None) -> HardwareSpec:
    """The hardware model of ``device`` (default ``jax.devices()[0]``).

    On a TPU it is the ``HARDWARE_BY_KIND`` entry for the device's kind,
    and an unknown kind raises ``ValueError``.  Off the chip (CPU tests,
    interpret mode) the explorer's modelled target is ``V5E``.
    """
    import jax

    device = device if device is not None else jax.devices()[0]
    if device.platform != "tpu":
        return V5E
    try:
        return HARDWARE_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware model for TPU kind {device.device_kind!r}; "
            f"known kinds: {sorted(HARDWARE_BY_KIND)}") from None


# ---------------------------------------------------------------------------
# 1. Paper Table I, literal CPU/SIMD form.
# ---------------------------------------------------------------------------
def table1_reduction(
    anchor: Stationarity,
    aux: Stationarity,
    conv: ConvProblem,
    n_aux_vars: int = 1,
) -> Tuple[float, float]:
    """(reads_saved, writes_saved) **per additional aux vector variable**.

    Literal transcription of the paper's Table I (simplified forms, as in
    the paper).  Units: memory instructions of one vector variable each.
    """
    H, R, E, s, fw, fh, ih = (
        conv.H, conv.R, conv.E, conv.s, conv.fw, conv.fh, conv.ih,
    )
    if anchor == OS:
        # "Both" aux rows: every stashed input or weight variable saves E reads.
        if aux in (IS, WS):
            return (float(E), 0.0)
    elif anchor == WS:
        if aux == IS:
            return (float(R), 0.0)
        if aux == OS:
            return (float(R), float(R))
    elif anchor == IS:
        if s == 1:
            if aux == WS:
                return (float(H), 0.0)
            if aux == OS:
                return (float(H), float(H))
        else:
            if aux == WS:
                if n_aux_vars <= fw:
                    return (H / s, 0.0)
                return (H / ((fw - s) * s), 0.0)
            if aux == OS:
                if n_aux_vars == 1:
                    g = H + H / fw
                    return (g, g)
                if n_aux_vars == 2:
                    g = (ih / max(fw - s, 1)) * (H + H / fw) + (ih / s) * max(
                        fw - s - 1, 0
                    )
                    return (g, g)
                g = (fh - s) * (fw - s) * H / R
                return (g, g)
    raise ValueError(f"no Table-I row for anchor={anchor} aux={aux} s={s}")


def paper_observations_hold(conv: ConvProblem) -> Dict[str, bool]:
    """Re-derive Observations 1-5 from Table I for a given layer (tested)."""
    obs = {}
    # Obs 1: WS gains least per aux variable.
    ws_gain = max(sum(table1_reduction(WS, a, conv)) for a in (IS, OS))
    os_gain = sum(table1_reduction(OS, WS, conv))
    is_gain = sum(table1_reduction(IS, OS, conv, n_aux_vars=1))
    obs["obs1_ws_gains_least"] = ws_gain <= min(os_gain, is_gain)
    # Obs 3: under OS, input-aux == weight-aux.
    obs["obs3_os_aux_symmetric"] = table1_reduction(
        OS, IS, conv
    ) == table1_reduction(OS, WS, conv)
    # Obs 4: under IS, output-aux >= weight-aux.
    obs["obs4_is_output_first"] = sum(
        table1_reduction(IS, OS, conv, 1)
    ) >= sum(table1_reduction(IS, WS, conv, 1))
    # Obs 5: under WS, output-aux >= input-aux.
    obs["obs5_ws_output_first"] = sum(
        table1_reduction(WS, OS, conv)
    ) >= sum(table1_reduction(WS, IS, conv))
    return obs


# ---------------------------------------------------------------------------
# 2. TPU HBM<->VMEM traffic model for tiled kernels.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Traffic:
    """Bytes moved between HBM and VMEM, per operand class."""

    reads: Dict[Stationarity, int]
    writes: Dict[Stationarity, int]
    vmem_peak: int
    feasible: bool  # fits in the VMEM budget

    @property
    def total(self) -> int:
        return sum(self.reads.values()) + sum(self.writes.values())


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Sub-byte packed weights (kernels/pack.py)
#
# Byte accounting mirrors the storage format exactly: a nibble plane of
# ceil(k/8) int32 words per column, a 1-bit high plane (bits == 5 only) of
# ceil(k/32) words per column, and an outlier sidecar of at most
# ceil(3k/256) rows (MSR coding bounds the out-of-range rows; pack.py uses
# the same capacity formula), each row one int32 index + n int32 deltas.
# ---------------------------------------------------------------------------


def packed_outlier_capacity(k: int) -> int:
    """Worst-case outlier sidecar rows for a K-dim of ``k`` (matches pack.py)."""
    return max(1, _ceil(3 * k, 256))


def packed_slab_bytes(rows: int, cols: int, weight_bits: int) -> int:
    """Bytes of the packed planes covering a ``rows x cols`` weight slab."""
    bytes_ = _ceil(rows, 8) * cols * 4  # nibble plane, int32 words
    if weight_bits == 5:
        bytes_ += _ceil(rows, 32) * cols * 4  # high-bit plane
    return bytes_


def packed_weight_bytes(k: int, n: int, weight_bits: int) -> int:
    """Total HBM bytes of a packed (k, n) weight: planes + outlier sidecar."""
    cap = packed_outlier_capacity(k)
    return packed_slab_bytes(k, n, weight_bits) + cap * (4 + n * 4)


def weight_stream_bytes(p: GemmProblem) -> int:
    """HBM bytes of one full fetch of the weight operand (packed-aware)."""
    if p.weight_bits is None:
        return p.k * p.n * dtype_bytes(p.in_dtype)
    return packed_weight_bytes(p.k, p.n, p.weight_bits)


def gemm_vmem_footprint(p: GemmProblem, spec: DataflowSpec) -> int:
    """Peak VMEM bytes claimed by the dataflow (double-buffered streams)."""
    bm, bk, bn = spec.block
    ib, ob = dtype_bytes(p.in_dtype), dtype_bytes(p.out_dtype)
    ab = dtype_bytes(p.acc_dtype)
    foot = 0
    # streamed blocks are double-buffered by the Pallas pipeline
    res_a = spec.residency(IS)
    res_b = spec.residency(WS)
    res_o = spec.residency(OS)
    foot += {
        Residency.STREAMED: 2 * bm * bk,
        Residency.STRIPE: bm * p.k,
        Residency.WHOLE: p.m * p.k,
    }[res_a] * ib
    if p.weight_bits is None:
        foot += {
            Residency.STREAMED: 2 * bk * bn,
            Residency.STRIPE: p.k * bn,
            Residency.WHOLE: p.k * p.n,
        }[res_b] * ib
    else:
        # packed planes resident per the dataflow, plus the transient
        # decompressed int8 block materialized at the stripe load
        foot += {
            Residency.STREAMED: 2 * packed_slab_bytes(bk, bn, p.weight_bits),
            Residency.STRIPE: packed_slab_bytes(p.k, bn, p.weight_bits),
            Residency.WHOLE: packed_slab_bytes(p.k, p.n, p.weight_bits),
        }[res_b]
        foot += bk * bn  # int8 decompress scratch
    foot += {
        Residency.STREAMED: 2 * bm * bn,
        Residency.STRIPE: bm * p.n if spec.anchor == IS else p.m * bn,
        Residency.WHOLE: p.m * p.n,
    }[res_o] * ob
    # scratch accumulator: OS always; basic (streamed-output) WS/IS since
    # the single-dispatch lowering accumulates in a VMEM scratch too
    if spec.anchor == OS or res_o == Residency.STREAMED:
        foot += bm * bn * ab
    elif p.in_dtype in ("int8", "uint8", "int32", "uint32", "bool"):
        # integer-input fused epilogues make the output-stripe WS/IS
        # writers accumulate in an int32 scratch of the stripe's shape
        # (kernels.matmul_df); charge it conservatively
        foot += {
            Residency.STRIPE: bm * p.n if spec.anchor == IS else p.m * bn,
            Residency.WHOLE: p.m * p.n,
        }[res_o] * ab
    return foot


def gemm_traffic(p: GemmProblem, spec: DataflowSpec) -> Traffic:
    """HBM bytes moved by the tiled kernel realizing ``spec`` on ``p``.

    Derivation (DESIGN.md §2): an operand whose block index is constant
    across consecutive grid steps is fetched once per distinct index; a
    streamed operand is re-fetched on every sweep of the grid dims its
    index does not depend on.
    """
    bm, bk, bn = spec.block
    gm, gk, gn = _ceil(p.m, bm), _ceil(p.k, bk), _ceil(p.n, bn)
    ib, ob = dtype_bytes(p.in_dtype), dtype_bytes(p.out_dtype)
    A, B, O = p.m * p.k * ib, weight_stream_bytes(p), p.m * p.n * ob

    res_a, res_b, res_o = (
        spec.residency(IS), spec.residency(WS), spec.residency(OS)
    )
    reads: Dict[Stationarity, int] = {}
    writes: Dict[Stationarity, int] = {IS: 0, WS: 0, OS: 0}

    if spec.anchor == OS:
        writes[OS] = O  # flushed once from the scratch accumulator
        reads[OS] = 0
        # Only one streamed-aux operand can own the outer grid position; the
        # aux_priority decides (paper Alg. 8: weight first).  WHOLE residency
        # removes the conflict.
        a_once = res_a == Residency.WHOLE
        b_once = res_b == Residency.WHOLE
        stripes = [
            st
            for st in spec.aux_priority
            if spec.residency(st) == Residency.STRIPE and st in (IS, WS)
        ]
        if not stripes:
            stripes = [
                st for st in (WS, IS) if spec.residency(st) == Residency.STRIPE
            ]
        if stripes:
            first = stripes[0]
            a_once = a_once or (first == IS)
            b_once = b_once or (first == WS)
            # a second stripe also sticks iff the first is WHOLE-resident
            for st in stripes[1:]:
                if (st == IS and b_once and res_b == Residency.WHOLE) or (
                    st == WS and a_once and res_a == Residency.WHOLE
                ):
                    a_once = a_once or st == IS
                    b_once = b_once or st == WS
        reads[IS] = A if a_once else gn * A
        reads[WS] = B if b_once else gm * B
    elif spec.anchor == WS:
        reads[WS] = B  # anchored: fetched exactly once
        a_once = res_a in (Residency.STRIPE, Residency.WHOLE)
        reads[IS] = A if a_once else gn * A
        if res_o in (Residency.STRIPE, Residency.WHOLE):
            reads[OS] = 0
            writes[OS] = O
        else:  # read-modify-write per reduction visit
            reads[OS] = gk * O
            writes[OS] = gk * O
    elif spec.anchor == IS:
        reads[IS] = A
        b_once = res_b == Residency.WHOLE  # stripes don't survive the m sweep
        reads[WS] = B if b_once else gm * B
        if res_o in (Residency.STRIPE, Residency.WHOLE):
            reads[OS] = 0
            writes[OS] = O
        else:
            reads[OS] = gk * O
            writes[OS] = gk * O
    else:
        raise ValueError(spec.anchor)

    foot = gemm_vmem_footprint(p, spec)
    return Traffic(
        reads=reads,
        writes=writes,
        vmem_peak=foot,
        feasible=foot <= spec.vmem_budget,
    )


def conv_traffic(p: ConvProblem, spec: DataflowSpec) -> Traffic:
    """Conv traffic via the implicit-GEMM view + window-overlap correction.

    A streamed conv input is read through overlapping windows (R/s^2 reuse
    forfeited); STRIPE/WHOLE residency recovers the unique-bytes bound —
    this is exactly the paper's input-reuse argument (Fig. 4) in bytes.
    """
    g = p.as_gemm()
    t = gemm_traffic(g, spec)
    unique_in = p.n * p.H * p.cin * dtype_bytes(p.in_dtype)
    reads = dict(t.reads)
    if spec.residency(IS) in (Residency.STRIPE, Residency.WHOLE):
        # resident input: halo rows are fetched once -> unique bytes
        refetch = reads[IS] // max(g.m * g.k * dtype_bytes(g.in_dtype), 1)
        reads[IS] = max(1, refetch) * unique_in if spec.anchor != IS else unique_in
        if spec.residency(IS) == Residency.WHOLE or spec.anchor == IS:
            reads[IS] = unique_in
    return Traffic(reads, dict(t.writes), t.vmem_peak, t.feasible)


def conv_gemm_view(p: ConvProblem, spec: DataflowSpec) -> DataflowSpec:
    """Map a conv-blocked spec to its implicit-GEMM blocking.

    A *conv-blocked* spec stores ``block = (b_oh, bc, bk)`` — the output
    row-tile height, the cin reduction panel, and the cout tile realized
    by ``kernels.conv2d_df``.  One output tile covers ``b_oh * ow`` GEMM
    rows, one reduction panel ``bc`` of the ``R * cin`` reduction, and
    one cout tile ``bk`` GEMM columns.
    """
    b_oh, bc, bk = spec.block
    return spec.with_block((max(1, b_oh) * p.ow, bc, bk))


def conv_vmem_footprint(p: ConvProblem, spec: DataflowSpec) -> int:
    """Peak VMEM bytes claimed by the realized conv kernel.

    Mirrors ``gemm_vmem_footprint`` for ``kernels.conv2d_df``'s actual
    lowering (``spec.block`` is conv-blocked, see ``conv_gemm_view``):
    the padded input image is whole-resident, one (fh, fw, C, bk) weight
    block and one (b_oh, ow, bk) output block are double-buffered, and
    the scratch accumulator holds one output tile in the acc dtype.
    """
    b_oh, bc, bk = spec.block
    ib, ob = dtype_bytes(p.in_dtype), dtype_bytes(p.out_dtype)
    ab = 4  # int32 / float32 accumulator
    cpad = _ceil(p.cin, bc) * bc
    kpad = _ceil(p.cout, bk) * bk
    b_oh = min(b_oh, p.oh)
    oh_pad = _ceil(p.oh, b_oh) * b_oh
    ih_pad = (oh_pad - 1) * p.s + p.fh + (p.s - 1)
    iw_pad = (p.ow - 1) * p.s + p.fw + (p.s - 1)
    foot = ih_pad * iw_pad * cpad * ib                # whole-resident image
    foot += 2 * p.fh * p.fw * cpad * min(bk, kpad) * ib
    foot += 2 * b_oh * p.ow * min(bk, kpad) * ob
    foot += b_oh * p.ow * min(bk, kpad) * ab
    return foot


def binary_traffic(p: BinaryProblem, spec: DataflowSpec) -> Traffic:
    """HBM bytes moved by the binary kernel realizing ``spec`` on ``p``.

    Bit-traffic accounting runs on the packed-word GEMM view
    (``BinaryProblem.as_gemm``): operands are uint32 words carrying 32
    binary channels each, so A is ``m * kp * 4`` bytes — 8x smaller than
    the int8 image of the same layer, which is the data-movement
    component of the paper's Fig. 9 speedup.  ``spec.block`` is
    ``(bm, bkp, bn)`` with the reduction blocked in packed words.
    """
    return gemm_traffic(p.as_gemm(), spec)


def binary_time_estimate(
    p: BinaryProblem, spec: DataflowSpec, hw: HardwareSpec = V5E
) -> float:
    """max(compute, memory) estimate for ranking binary dataflows.

    Compute charges ``bit_ops`` (xnor + popcount-accumulate pairs over
    the *true* reduction depth) at the VPU's ``binary_packed`` rate;
    memory comes from ``binary_traffic`` on the packed view.
    """
    t = binary_traffic(p, spec)
    tc = p.bit_ops / hw.peak_flops_for("binary_packed")
    tm = t.total / hw.hbm_bw
    penalty = 0.0 if t.feasible else float("inf")
    return max(tc, tm) + penalty


def conv_time_estimate(
    p: ConvProblem, spec: DataflowSpec, hw: HardwareSpec = V5E
) -> float:
    """max(compute, memory) estimate for ranking *conv-blocked* specs.

    Traffic comes from ``conv_traffic`` on the implicit-GEMM view of the
    blocking; feasibility from ``conv_vmem_footprint`` (the realized
    kernel's residency, not the GEMM tiling's).
    """
    t = conv_traffic(p, conv_gemm_view(p, spec))
    tc = p.flops / hw.peak_flops_for(p.in_dtype)
    tm = t.total / hw.hbm_bw
    feasible = conv_vmem_footprint(p, spec) <= spec.vmem_budget
    return max(tc, tm) + (0.0 if feasible else float("inf"))


# Attention: online-softmax statistics ride in (bq, 128)-shaped f32 lanes
# next to the (bq, d) f32 accumulator (see kernels/attention_df).
ATTN_STAT_LANES = 256   # m + l, 128 lanes each
_F32 = 4


def attention_block_clamp(sq: int, skv: int, bq: int,
                          bkv: int) -> Tuple[int, int]:
    """The ``(bq, bkv)`` the attention kernels actually realize for true
    lengths ``(sq, skv)``: blocks clamp to the 8-padded sequence, and
    ``sq == 1`` forces the single-q-row decode fast path (no q blocking).

    The single source of this rule — ``ops.attention`` applies it before
    padding and the cost model mirrors it here, so ranking and realized
    kernel can never drift apart.
    """
    bq = 1 if sq <= 1 else max(1, min(bq, -(-sq // 8) * 8))
    bkv = max(1, min(bkv, -(-max(skv, 1) // 8) * 8))
    return bq, bkv


def _attn_padded(p: AttentionProblem, spec: DataflowSpec):
    bq, bkv = attention_block_clamp(p.sq, p.skv, spec.block[0],
                                    spec.block[1])
    sqp = _ceil(p.sq, bq) * bq
    skvp = _ceil(p.skv, bkv) * bkv
    return bq, bkv, sqp, skvp


def attention_band(p: AttentionProblem, i: int, bq: int,
                   bkv: int) -> Tuple[int, int]:
    """[lo, hi] inclusive KV-block band visible to q tile ``i``.

    The single source of the banding rule: ``kernels.attention_df``
    mirrors these bounds in its index maps (with traced ``kv_len`` /
    ``window`` scalars), so the blocks the cost model charges are
    exactly the blocks the kernel fetches.  ``hi < lo`` means the tile
    sees nothing (can only happen for all-padding q tiles).

    A KV block ``j`` (positions ``[j*bkv, (j+1)*bkv)``) is visible iff
      * it starts inside the valid prefix: ``j*bkv < kv_valid``;
      * (causal) it starts at or before the tile's last q position;
      * (window) it ends after the tile's first q position minus the
        window.
    q rows are right-aligned against the valid KV length
    (``off = kv_valid - sq``), matching the kernels and the decode
    convention.
    """
    kv_valid = p.kv_valid
    off = kv_valid - p.sq
    hi = max(0, _ceil(kv_valid, bkv) - 1)          # last valid block
    if p.causal:
        qmax = min((i + 1) * bq, p.sq) - 1 + off   # tile's last true row
        hi = min(hi, max(0, qmax) // bkv)
    lo = 0
    if p.window is not None:
        qmin = i * bq + off
        lo = max(0, (qmin - p.window + 1) // bkv)
    return min(lo, hi), hi


def attention_visited_blocks(
    p: AttentionProblem, bq: int, bkv: int
) -> Tuple[int, int, int, int]:
    """(visited (q tile, KV block) pairs, distinct visited KV blocks,
    gq, gkv) under banded execution with blocks ``(bq, bkv)``.

    ``pairs`` is the number of grid steps that do DMA + compute work
    (OS re-streams one KV block per pair; WS round-trips one state
    block per pair); ``kv_blocks`` is how many distinct KV blocks are
    touched at all (WS fetches each exactly once).  With no window, a
    full valid prefix and no causal mask this degenerates to the old
    full-mask accounting (``pairs = gq * gkv``).
    """
    bq, bkv = attention_block_clamp(p.sq, p.skv, bq, bkv)
    gq = _ceil(p.sq, bq)
    gkv = _ceil(p.skv, bkv)
    pairs = 0
    seen = set()
    for i in range(gq):
        lo, hi = attention_band(p, i, bq, bkv)
        if hi < lo:
            continue
        pairs += hi - lo + 1
        seen.update(range(lo, hi + 1))
    return pairs, len(seen), gq, gkv


def attention_banded_ops(p: AttentionProblem, bq: int,
                         bkv: int) -> Tuple[int, int]:
    """(dot_flops, softmax_ops) over the *visited* score blocks only.

    Block skipping makes mask sparsity a first-class ranking term: a
    windowed prefill's compute scales with ``sq * window``-ish visited
    area, and a cached decode's with the valid KV length — the full-
    mask ``AttentionProblem.dot_flops`` stays available for rooflines.
    """
    pairs, _, _, _ = attention_visited_blocks(p, bq, bkv)
    bq, bkv = attention_block_clamp(p.sq, p.skv, bq, bkv)
    scores = pairs * bq * bkv
    return 4 * p.bh * scores * p.d, 6 * p.bh * scores


def attention_vmem_footprint(p: AttentionProblem,
                             spec: DataflowSpec) -> int:
    """Peak VMEM bytes claimed by the realized attention kernel.

    Both anchors double-buffer the streamed q and KV blocks; the
    anchor-dependent term is where the running (acc, m, l) state lives —
    VMEM scratch for the whole KV sweep under OS, a double-buffered
    revisited block under WS.
    """
    bq, bkv, _, _ = _attn_padded(p, spec)
    ib = dtype_bytes(p.dtype)
    kvib = dtype_bytes(p.kv_elem_dtype)
    state = bq * (p.d + ATTN_STAT_LANES) * _F32
    foot = 2 * bq * p.d * ib              # q block
    foot += 2 * 2 * bkv * p.d * kvib      # k and v blocks
    if p.kv_quantized:                    # int8 KV: per-position scales
        foot += 2 * 2 * bkv * _F32
    if spec.anchor == OS:
        foot += 2 * bq * p.d * ib         # output block
        foot += state                     # scratch acc + stats
    else:                                 # WS: state revisited through HBM
        foot += 2 * state
    return foot


def attention_traffic(p: AttentionProblem, spec: DataflowSpec) -> Traffic:
    """HBM bytes moved by the attention kernel realizing ``spec``.

    Operand classes: IS = Q, WS = K+V (+ per-position dequant scales
    for an int8 KV cache), OS = output / running state.

      OS (flash)          — Q and O move once; KV blocks stream once
                            per *visited* (q tile, KV block) pair.
      WS (kv-stationary)  — each *visited* KV block moves exactly once,
                            but the sweep is rectangular: for every
                            swept block ALL ``gq`` q tiles re-read
                            their q block and round-trip the (acc, m,
                            l) state (an invisible pair skips compute
                            yet still carries its state through the
                            aliased buffers — per-pair banding cannot
                            remove WS's state traffic, only whole
                            blocks leave the sweep).

    Banded accounting (PR 5): the kernels skip KV blocks beyond the
    valid ``kv_len`` and fully out-of-band causal/window blocks
    (``attention_visited_blocks``), so mask sparsity no longer cancels
    out of the OS-vs-WS ranking — OS's KV re-streaming shrinks with
    the visited *pairs* while WS shrinks only with the distinct
    visited *blocks*.  A cached decode therefore moves bytes
    proportional to the valid KV length, not the ``skv`` buffer size.
    """
    bq, bkv, sqp, skvp = _attn_padded(p, spec)
    pairs, kv_blocks, gq, gkv = attention_visited_blocks(p, bq, bkv)
    qib = dtype_bytes(p.dtype)
    kvib = dtype_bytes(p.kv_elem_dtype)
    # bytes of one KV position (K + V rows, + two f32 dequant scales
    # when the cache is int8-quantized), charged per q-head row (GQA
    # re-use is a VMEM property, not an HBM one, matching the kernels).
    kv_pos = 2 * p.d * kvib + (2 * _F32 if p.kv_quantized else 0)
    Q = p.bh * sqp * p.d * qib
    O = p.bh * sqp * p.d * qib
    reads: Dict[Stationarity, int] = {}
    writes: Dict[Stationarity, int] = {IS: 0, WS: 0, OS: 0}
    if spec.anchor == OS:
        reads[IS] = Q
        reads[WS] = p.bh * pairs * bkv * kv_pos
        reads[OS] = 0
        writes[OS] = O
    elif spec.anchor == WS:
        reads[WS] = p.bh * kv_blocks * bkv * kv_pos
        steps = kv_blocks * gq          # rectangular sweep (see above)
        reads[IS] = p.bh * steps * bq * p.d * qib
        state = p.bh * steps * bq * (p.d + ATTN_STAT_LANES) * _F32
        reads[OS] = state
        writes[OS] = state
    else:
        raise ValueError(f"attention admits OS/WS anchors, not {spec.anchor}")
    foot = attention_vmem_footprint(p, spec)
    return Traffic(reads=reads, writes=writes, vmem_peak=foot,
                   feasible=foot <= spec.vmem_budget)


def attention_rows_traffic(p: AttentionProblem, kv_lens,
                           spec: DataflowSpec) -> Traffic:
    """Per-row banded traffic for a ragged decode step (PR 8).

    ``kv_lens`` holds one valid KV length per batch row of ``p``
    (``len(kv_lens)`` rows sharing ``p.bh`` head-rows equally); each
    row is charged the banded traffic of ITS OWN valid length — the
    sum a continuous-batching step realizes — instead of charging
    every row at the batch max.  A row at 0 moves nothing (its kernel
    steps clamp onto the edge block and skip all compute).  The
    per-row problems reuse :func:`attention_traffic`, so this stays a
    pure aggregation of the one banding rule.
    """
    kv_lens = [int(kv) for kv in kv_lens]
    rows = max(len(kv_lens), 1)
    if p.bh % rows:
        raise ValueError(f"bh={p.bh} not divisible by {rows} kv_lens rows")
    heads = p.bh // rows
    reads: Dict[Stationarity, int] = {IS: 0, WS: 0, OS: 0}
    writes: Dict[Stationarity, int] = {IS: 0, WS: 0, OS: 0}
    vmem_peak, feasible = 0, True
    for kv in kv_lens:
        if kv <= 0:
            continue                       # empty row: no visited blocks
        rp = dataclasses.replace(p, bh=heads, rows=1,
                                 kv_len=min(kv, p.skv))
        t = attention_traffic(rp, spec)
        for st in (IS, WS, OS):
            reads[st] += t.reads.get(st, 0)
            writes[st] += t.writes.get(st, 0)
        vmem_peak = max(vmem_peak, t.vmem_peak)
        feasible &= t.feasible
    return Traffic(reads=reads, writes=writes, vmem_peak=vmem_peak,
                   feasible=feasible)


def attention_time_estimate(
    p: AttentionProblem, spec: DataflowSpec, hw: HardwareSpec = V5E
) -> float:
    """max(compute, memory) estimate for ranking attention dataflows.

    Compute charges the QK^T/PV dots at the MXU rate of ``p.dtype``
    plus the online-softmax per-score ops at the VPU (float32) rate,
    both over the *visited* score blocks only
    (``attention_banded_ops``); memory comes from ``attention_traffic``
    (banded, anchor-dependent KV re-streaming and state round-trips).
    """
    t = attention_traffic(p, spec)
    dot, soft = attention_banded_ops(p, spec.block[0], spec.block[1])
    tc = (dot / hw.peak_flops_for(p.dtype)
          + soft / hw.peak_flops_for("float32"))
    tm = t.total / hw.hbm_bw
    return max(tc, tm) + (0.0 if t.feasible else float("inf"))


# ---------------------------------------------------------------------------
# 3. Roofline terms (EXPERIMENTS.md §Roofline).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    t_compute: float
    t_memory: float
    t_collective: float
    chips: int
    flops: float
    hbm_bytes: float
    collective_bytes: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def compute_fraction(self) -> float:
        """Fraction of the roofline-bound time spent at peak compute."""
        if self.bound_time == 0:
            return 0.0
        return self.t_compute / self.bound_time


def roofline(
    flops: float,
    hbm_bytes: float,
    collective_bytes: float,
    chips: int = 1,
    hw: HardwareSpec = V5E,
    dtype: str = "bfloat16",
) -> RooflineTerms:
    """The three-term roofline from the task spec.

    compute    = HLO_FLOPs / (chips * peak)
    memory     = HLO_bytes / (chips * hbm_bw)
    collective = collective_bytes / (chips * link_bw)

    ``flops``/``hbm_bytes``/``collective_bytes`` are *global* (whole-step)
    quantities; per-chip values are obtained by the division.
    """
    return RooflineTerms(
        t_compute=flops / (chips * hw.peak_flops_for(dtype)),
        t_memory=hbm_bytes / (chips * hw.hbm_bw),
        t_collective=collective_bytes / (chips * hw.ici_bw),
        chips=chips,
        flops=flops,
        hbm_bytes=hbm_bytes,
        collective_bytes=collective_bytes,
    )


def model_flops(n_params: int, tokens: int, training: bool = True) -> float:
    """6*N*D for training (fwd+bwd), 2*N*D for inference forward."""
    return (6.0 if training else 2.0) * n_params * tokens


def traffic_seconds(t: Traffic, hw: HardwareSpec = V5E) -> float:
    return t.total / hw.hbm_bw


def gemm_time_estimate(
    p: GemmProblem, spec: DataflowSpec, hw: HardwareSpec = V5E
) -> float:
    """max(compute, memory) single-chip estimate used for ranking dataflows."""
    t = gemm_traffic(p, spec)
    tc = p.flops / hw.peak_flops_for(p.in_dtype)
    tm = t.total / hw.hbm_bw
    penalty = 0.0 if t.feasible else float("inf")
    return max(tc, tm) + penalty
