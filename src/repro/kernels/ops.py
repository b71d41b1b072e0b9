"""Public jit'd wrappers around the Pallas kernels.

These handle padding to tile boundaries, dataflow selection (via the
``core.autotune`` spec cache when no spec is given), backend dispatch
(Pallas on TPU, interpret-mode Pallas or the jnp oracle elsewhere), and
quantization plumbing.

``matmul_fused`` / ``int8_matmul_fused`` and ``conv2d_fused`` /
``int8_conv2d_fused`` execute the whole layer — GEMM/conv plus its
epilogue (dequant scale, bias, activation, residual) — in one kernel
dispatch: the epilogue is applied in-register before the single HBM
output write instead of as separate XLA ops re-reading the raw
accumulator from HBM.

Fault injection (runtime/health.py): each public op carries a named
site — ``kernel.matmul`` / ``kernel.conv2d`` / ``kernel.binary_matmul``
/ ``kernel.attention`` — checked at dispatch.  Since these wrappers are
jitted, an armed fault fires at trace/lowering time (once per distinct
compiled shape), which is where real lowering and interpret failures
surface; a ``nan``-kind fault bakes a NaN multiply into the trace for
float outputs (integer outputs ignore it — there is no int NaN), so
the non-finite sentinel downstream sees exactly what a numerically
broken kernel would produce.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import autotune, cost_model
from repro.core.dataflow import (
    AttentionProblem, BinaryEpilogue, BinaryProblem, ConvProblem,
    DataflowSpec, Epilogue, GemmProblem, Residency, SpecOverride,
    IS, OS, WS,
)
from repro.kernels import (
    attention_df, binary_mm, conv2d_df, matmul_df, pack, ref,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _inject(site: str) -> Optional[str]:
    from repro.runtime import health

    return health.maybe_inject(site)


def _poison(out: jax.Array, fault: Optional[str]) -> jax.Array:
    """Realize a ``nan``-kind injected fault on a float result."""
    if fault == "nan" and jnp.issubdtype(out.dtype, jnp.floating):
        return out * jnp.asarray(jnp.nan, out.dtype)
    return out


def _pad_to(x: jax.Array, mults, value=0):
    pads = []
    needs = False
    for dim, mult in zip(x.shape, mults):
        pad = (-dim) % mult
        pads.append((0, pad))
        needs |= pad > 0
    return jnp.pad(x, pads, constant_values=value) if needs else x


def _resolve_spec(spec, problem, backend: str) -> DataflowSpec:
    """Resolve a public op's ``spec`` argument to a full DataflowSpec.

    ``None`` -> the autotuned spec for ``problem``.  A ``SpecOverride``
    merges onto that autotuned base (a *complete* override — anchor and
    every block dim pinned — skips the cache lookup and realizes over
    the paper-default dataflow).  A full ``DataflowSpec`` passes
    through untouched.
    """
    if isinstance(spec, SpecOverride):
        if spec.is_complete:
            return spec.merge(DataflowSpec.optimized())
        return spec.merge(autotune.best_spec(problem, backend=backend))
    if spec is None:
        return autotune.best_spec(problem, backend=backend)
    return spec


def _gemm_problem(m: int, k: int, n: int, in_dtype, out_dtype,
                  weight_bits: Optional[int] = None) -> GemmProblem:
    integer = jnp.issubdtype(jnp.dtype(in_dtype), jnp.integer)
    if out_dtype is None:
        out = "int32" if integer else "float32"
    else:
        out = str(jnp.dtype(out_dtype))
    return GemmProblem(
        m=m, k=k, n=n, in_dtype=str(jnp.dtype(in_dtype)), out_dtype=out,
        acc_dtype="int32" if integer else "float32",
        weight_bits=weight_bits,
    )


def _conv_problem(n: int, ih: int, iw: int, fh: int, fw: int, stride: int,
                  cin: int, cout: int, in_dtype, out_dtype,
                  weight_bits: Optional[int] = None) -> ConvProblem:
    integer = jnp.issubdtype(jnp.dtype(in_dtype), jnp.integer)
    if out_dtype is None:
        out = "int32" if integer else "float32"
    else:
        out = str(jnp.dtype(out_dtype))
    return ConvProblem(
        ih=ih, iw=iw, fh=fh, fw=fw, s=stride, cin=cin, cout=cout, n=n,
        in_dtype=str(jnp.dtype(in_dtype)), out_dtype=out,
        weight_bits=weight_bits,
    )


def _conv_pad(x, w, stride: int, oh: int, ow: int, b_oh: int, bc: int,
              bk: int):
    """Lane-align channels and halo-pad the image for the window loads."""
    n, ih, iw, cin = x.shape
    fh, fw, _, cout = w.shape
    bc_ = min(bc, -(-cin // 128) * 128)
    bk_ = min(bk, -(-cout // 128) * 128)
    b_oh_ = min(b_oh, oh)
    oh_pad = -(-oh // b_oh_) * b_oh_
    # halo padding so every (t, ky) window load is in bounds
    ih_need = (oh_pad - 1) * stride + fh + (stride - 1)
    iw_need = (ow - 1) * stride + fw + (stride - 1)
    xp = _pad_to(x, (1, 1, 1, bc_))
    xp = jnp.pad(
        xp,
        ((0, 0), (0, max(0, ih_need - ih)), (0, max(0, iw_need - iw)), (0, 0)),
    )
    wp = _pad_to(w, (1, 1, bc_, bk_))
    return xp, wp, oh_pad, b_oh_, bc_, bk_


def default_matmul_spec(m: int, k: int, n: int, in_dtype="bfloat16",
                        vmem_budget: int = 16 * 2 ** 20) -> DataflowSpec:
    """Paper Alg. 8: OS anchor, aux to weights first (WHOLE if it fits,
    else STRIPE), then inputs."""
    from repro.core.cost_model import dtype_bytes

    ib = dtype_bytes(str(in_dtype))
    bm = min(512, max(128, m))
    bn = min(512, max(128, n))
    bk = min(512, max(128, k))
    aux = {}
    base = 2 * bm * bk * ib + 2 * bm * bn * 4 + bm * bn * 4
    if k * n * ib + base <= vmem_budget:
        aux[WS] = Residency.WHOLE
        if bm * k * ib + k * n * ib + base <= vmem_budget:
            aux[IS] = Residency.STRIPE
    elif k * bn * ib + base <= vmem_budget:
        aux[WS] = Residency.STRIPE
    return DataflowSpec(anchor=OS, aux=aux, aux_priority=(WS, IS),
                        block=(bm, bk, bn), vmem_budget=vmem_budget)


@functools.partial(jax.jit, static_argnames=("spec", "out_dtype", "backend"))
def matmul(
    a: jax.Array,
    b: jax.Array,
    spec: Optional[DataflowSpec] = None,
    out_dtype=None,
    backend: Optional[str] = None,   # "pallas" | "interpret" | "xla"
) -> jax.Array:
    """(M, K) @ (K, N) with automatic padding under a dataflow spec.

    With ``spec=None`` the dataflow comes from the ``core.autotune``
    cache — the explorer's candidate space is enumerated once per
    distinct (shape, dtype, hardware, backend) and memoized in-process
    and on disk.
    """
    fault = _inject("kernel.matmul")
    m, k = a.shape
    n = b.shape[1]
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        return _poison(ref.matmul_ref(a, b, out_dtype), fault)
    spec = _resolve_spec(
        spec, _gemm_problem(m, k, n, a.dtype, out_dtype), backend)
    bm, bk, bn = spec.block
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    spec = spec.with_block((min(bm, ap.shape[0]), min(bk, ap.shape[1]),
                            min(bn, bp.shape[1])))
    out = matmul_df.matmul_df(ap, bp, spec, out_dtype=out_dtype,
                              interpret=backend == "interpret")
    return _poison(out[:m, :n], fault)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "spec", "b_oh", "bc", "bk", "out_dtype",
                     "backend"),
)
def conv2d(
    x: jax.Array,      # (N, H, W, Cin)
    w: jax.Array,      # (fh, fw, Cin, Cout)
    stride: int = 1,
    spec: Optional[DataflowSpec] = None,
    b_oh: int = 8,
    bc: int = 128,
    bk: int = 128,
    out_dtype=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Direct NHWC conv (VALID padding) under a dataflow spec.

    With ``spec=None`` the dataflow (anchor AND conv blocking ``(b_oh,
    bc, bk)``) comes from the ``core.autotune`` cache keyed on the
    ``ConvProblem`` — the conv candidate space is ranked once per
    distinct (geometry, dtype, hardware, backend) and memoized.  An
    explicitly-passed ``spec`` keeps the ``b_oh``/``bc``/``bk`` keyword
    blocking (its ``block`` field is GEMM-shaped).
    """
    fault = _inject("kernel.conv2d")
    n, ih, iw, cin = x.shape
    fh, fw, _, cout = w.shape
    oh = (ih - fh) // stride + 1
    ow = (iw - fw) // stride + 1
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        return _poison(ref.conv2d_ref(x, w, stride, out_dtype), fault)
    override = spec if isinstance(spec, SpecOverride) else None
    if spec is None or override is not None:
        try:
            spec = autotune.best_spec(
                _conv_problem(n, ih, iw, fh, fw, stride, cin, cout, x.dtype,
                              out_dtype),
                backend=backend,
            )
            b_oh, bc, bk = spec.block  # conv-blocked, from the conv explorer
        except ValueError:
            # no candidate fits the analytic VMEM budget (e.g. a very
            # large whole-resident image): fall back to the paper's
            # default dataflow under the keyword blocking
            spec = DataflowSpec.optimized()
        if override is not None:
            spec = override.merge(spec.with_block((b_oh, bc, bk)))
            b_oh, bc, bk = spec.block

    xp, wp, oh_pad, b_oh_, bc_, bk_ = _conv_pad(
        x, w, stride, oh, ow, b_oh, bc, bk)
    out = conv2d_df.conv2d_df(
        xp, wp, stride, spec, oh=oh_pad, ow=ow, b_oh=b_oh_, bc=bc_, bk=bk_,
        out_dtype=out_dtype, interpret=backend == "interpret",
    )
    return _poison(out[:, :oh, :, :cout], fault)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "activation", "spec", "b_oh", "bc", "bk",
                     "out_dtype", "backend"),
)
def conv2d_fused(
    x: jax.Array,      # (N, H, W, Cin)
    w: jax.Array,      # (fh, fw, Cin, Cout)
    stride: int = 1,
    bias: Optional[jax.Array] = None,       # (Cout,) or (1, Cout) float
    scale: Optional[jax.Array] = None,      # scalar or (Cout,) dequant scale
    residual: Optional[jax.Array] = None,   # (N, oh, ow, Cout)
    activation: Optional[str] = None,       # relu | gelu | silu
    spec: Optional[DataflowSpec] = None,
    b_oh: int = 8,
    bc: int = 128,
    bk: int = 128,
    out_dtype=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Fused-epilogue conv: ``act(scale * conv(x, w) + bias) + residual``.

    One kernel dispatch per layer: the epilogue runs in-register on the
    scratch accumulator at the flush, so the raw conv result never
    round-trips HBM.  Shapes pad automatically like ``conv2d``; epilogue
    math is float32 and the default output dtype is float32.
    """
    fault = _inject("kernel.conv2d")
    n, ih, iw, cin = x.shape
    fh, fw, _, cout = w.shape
    oh = (ih - fh) // stride + 1
    ow = (iw - fw) // stride + 1
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, cout)
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32)
        if scale.size == 1:
            scale = scale.reshape(1, 1)
        elif scale.size == cout:
            scale = scale.reshape(1, cout)
        else:
            raise ValueError(
                f"scale must be scalar or per-output-channel (Cout={cout}), "
                f"got {scale.shape}"
            )
    if backend == "xla":
        return _poison(ref.conv2d_fused_ref(
            x, w, stride, bias=bias, scale=scale, residual=residual,
            activation=activation, out_dtype=out_dtype,
        ), fault)
    epi = Epilogue(
        bias=bias is not None,
        activation=activation,
        scale=scale is not None,
        residual=residual is not None,
    )
    override = spec if isinstance(spec, SpecOverride) else None
    if spec is None or override is not None:
        try:
            spec = autotune.best_spec(
                _conv_problem(n, ih, iw, fh, fw, stride, cin, cout, x.dtype,
                              out_dtype or jnp.float32),
                backend=backend,
            )
            b_oh, bc, bk = spec.block
        except ValueError:
            spec = DataflowSpec.optimized()  # see conv2d's fallback note
        if override is not None:
            spec = override.merge(spec.with_block((b_oh, bc, bk)))
            b_oh, bc, bk = spec.block
    xp, wp, oh_pad, b_oh_, bc_, bk_ = _conv_pad(
        x, w, stride, oh, ow, b_oh, bc, bk)
    kpad = wp.shape[3]
    if bias is not None:
        bias = _pad_to(bias, (1, bk_))
    if scale is not None and scale.shape != (1, 1):
        scale = _pad_to(scale, (1, bk_))
    if residual is not None:
        residual = jnp.pad(
            residual,
            ((0, 0), (0, oh_pad - oh), (0, 0), (0, kpad - cout)),
        )
    out = conv2d_df.conv2d_df(
        xp, wp, stride, spec, oh=oh_pad, ow=ow, b_oh=b_oh_, bc=bc_, bk=bk_,
        out_dtype=out_dtype or jnp.float32,
        interpret=backend == "interpret",
        epilogue=epi, scale=scale, bias=bias, residual=residual,
    )
    return _poison(out[:, :oh, :, :cout], fault)


@functools.partial(
    jax.jit, static_argnames=("stride", "activation", "spec", "backend")
)
def int8_conv2d_fused(
    xq: jax.Array, wq: jax.Array, x_scale: jax.Array, w_scale: jax.Array,
    stride: int = 1,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Quantized conv with the dequant + epilogue fused into the kernel:
    ``act((x_scale * w_scale) * conv(xq, wq) + bias) + residual`` -> f32.

    Scales must be per-tensor (scalar) or combine to per-output-channel;
    spatially-varying activation scales need the unfused path.
    """
    scale = (jnp.asarray(x_scale, jnp.float32)
             * jnp.asarray(w_scale, jnp.float32))
    cout = wq.shape[3]
    if scale.size not in (1, cout):
        raise ValueError(
            f"fused conv dequant needs scalar or per-output-channel scales, "
            f"got combined shape {scale.shape}"
        )
    return conv2d_fused(
        xq, wq, stride=stride, bias=bias, scale=scale.reshape(1, -1),
        residual=residual, activation=activation, spec=spec, backend=backend,
    )


def _attention_problem(bh: int, sq: int, skv: int, d: int, group: int,
                       causal: bool, window: Optional[int],
                       dtype, kv_dtype=None, rows: int = 1) -> AttentionProblem:
    dt = str(jnp.dtype(dtype))
    kdt = None if kv_dtype is None else str(jnp.dtype(kv_dtype))
    return AttentionProblem(
        bh=bh, sq=sq, skv=skv, d=d, group=group, causal=causal,
        window=window, dtype=dt, kv_dtype=None if kdt == dt else kdt,
        rows=rows,
    )


@functools.partial(
    jax.jit,
    static_argnames=("group", "causal", "window", "scale", "spec", "bq",
                     "bkv", "backend", "anchor"),
)
def attention(
    q: jax.Array,            # (B, Hq, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)  float, or int8 w/ scales
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,         # static sliding window
    scale: Optional[float] = None,
    spec: Optional[DataflowSpec] = None,
    bq: Optional[int] = None,
    bkv: Optional[int] = None,
    backend: Optional[str] = None,
    anchor: Optional[str] = None,  # "os" (flash) | "ws" (kv-stationary)
    group: Optional[int] = None,
    kv_len: Optional[jax.Array] = None,   # valid KV prefix (traced ok)
    window_dyn: Optional[jax.Array] = None,   # traced sliding window
    k_scale: Optional[jax.Array] = None,  # (B, Hkv, Skv, 1) int8-KV scales
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """GQA attention under a dataflow anchor. Returns (B, Hq, Sq, D).

    With ``spec=None`` the dataflow — the anchor AND the ``(bq, bkv)``
    blocking — comes from the ``core.autotune`` cache keyed on the
    ``AttentionProblem`` (keys ``v5|attn|...``): the candidate space
    {OS/flash, WS/kv-stationary} x blocks is ranked once per distinct
    (shape, mask, dtype, hardware, backend) and memoized.  An explicit
    ``anchor``/``bq``/``bkv`` overrides only that field of the resolved
    spec, so e.g. the benchmark's forced-WS variant still honors the
    autotuned block.

    Serving terms (PR 5), all handled inside the kernel grid:
      * ``kv_len`` — the filled prefix of a padded KV-cache buffer
        (traced; q rows right-align against it).  KV blocks beyond it
        are skipped — clamped index maps issue no DMA and ``pl.when``
        skips their compute — so a decode step's traffic scales with
        the *valid* cache length, not ``Skv``.  Traced lengths key the
        autotune lookup as the full-``Skv`` worst case.  A ``(B,)``
        vector bands *per batch row* (PR 8): each row's grid steps
        clamp onto its own band edge, so a ragged continuous batch
        pays each request's true cache length.
      * ``spec`` also accepts a partial :class:`SpecOverride`; its
        anchor/block fields fill whichever of ``anchor``/``bq``/``bkv``
        were not explicitly passed.
      * ``window`` (static) / ``window_dyn`` (traced) — causal sliding
        window; a static window additionally shrinks the KV grid
        dimension to the band width.
      * ``k_scale``/``v_scale`` — per-position f32 scales of an int8
        K/V cache, dequantized at the block load; the cache never
        round-trips HBM as a float copy.

    Decode (``Sq == 1``) takes a single-q-row fast path: the q side is
    neither padded nor blocked (``bq = 1``, one q tile), keeping the
    per-step cost at one kernel dispatch over the KV stream.
    """
    fault = _inject("kernel.attention")
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = group or hq // hkv
    backend = backend or ("pallas" if _on_tpu() else "xla")
    quant = k.dtype == jnp.int8
    if quant:
        if k_scale is None or v_scale is None:
            raise ValueError("int8 K/V need per-position k_scale/v_scale")
        # catch wrong scale layouts (e.g. a squeezed (B, H, S) vector or a
        # per-tensor scalar) before they broadcast silently in the kernel
        want_k, want_v = k.shape[:-1] + (1,), v.shape[:-1] + (1,)
        if k_scale.shape != want_k or v_scale.shape != want_v:
            raise ValueError(
                f"int8 K/V scales must be per-position with a trailing "
                f"singleton lane: expected k_scale {want_k} and v_scale "
                f"{want_v}, got {k_scale.shape} and {v_scale.shape}"
            )
    win_eff = window if window is not None else window_dyn
    if backend == "xla":
        return _poison(
            ref.attention_ref(q, k, v, causal=causal, window=win_eff,
                              scale=scale, kv_len=kv_len,
                              k_scale=k_scale, v_scale=v_scale), fault)
    if isinstance(spec, SpecOverride):
        # one-surface override (PR 8): unpack into the legacy per-field
        # aliases; an explicitly-passed alias kwarg wins over the
        # override's field
        if spec.anchor not in (None, OS, WS):
            raise ValueError(
                f"attention admits OS/WS anchors, not {spec.anchor!r}"
            )
        anchor = anchor if anchor is not None else spec.anchor_name
        bq = bq if bq is not None else spec.block_dim(0)
        bkv = bkv if bkv is not None else spec.block_dim(1)
        spec = None
    ragged = getattr(kv_len, "ndim", 0) == 1
    if ragged and kv_len.shape[0] != b:
        raise ValueError(
            f"per-row kv_len needs one entry per batch row "
            f"({b}), got shape {kv_len.shape}"
        )
    if spec is None and (anchor is None or bq is None or bkv is None):
        spec = autotune.best_spec(
            _attention_problem(b * hq, sq, skv, d, group, causal, window,
                               q.dtype, k.dtype, rows=b if ragged else 1),
            backend=backend,
        )
    if spec is not None:
        if spec.anchor not in (OS, WS):
            raise ValueError(
                f"attention admits OS/WS anchors, not {spec.anchor!r}"
            )
        if anchor is None:
            anchor = "os" if spec.anchor == OS else "ws"
        bq = bq if bq is not None else spec.block[0]
        bkv = bkv if bkv is not None else spec.block[1]
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    bq_, bkv_ = cost_model.attention_block_clamp(sq, skv, bq, bkv)
    if sq == 1:
        qp = qf                 # decode fast path: no q padding/blocking
    else:
        qp = _pad_to(qf, (1, bq_, 1))
    kp = _pad_to(kf, (1, bkv_, 1))
    vp = _pad_to(vf, (1, bkv_, 1))
    ksp = vsp = None
    if quant:
        ksp = _pad_to(k_scale.reshape(b * hkv, skv, 1), (1, bkv_, 1))
        vsp = _pad_to(v_scale.reshape(b * hkv, skv, 1), (1, bkv_, 1))
    fn = (attention_df.flash_attention if anchor == "os"
          else attention_df.kv_stationary_attention)
    out = fn(
        qp, kp, vp, group=group, causal=causal, window=window, scale=scale,
        skv_valid=skv, sq_valid=sq, bq=bq_, bkv=bkv_,
        interpret=backend == "interpret",
        kv_len=kv_len, window_dyn=window_dyn, k_scale=ksp, v_scale=vsp,
    )
    return _poison(out[:, :sq].reshape(b, hq, sq, d), fault)


@functools.partial(
    jax.jit, static_argnames=("group", "scale", "window", "backend"),
)
def paged_attention(
    q: jax.Array,             # (B, Hq, 1, D) decode queries
    k_pages: jax.Array,       # (Hkv, n_pages, page, D) device page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages) int32 page ids (pad with 0)
    kv_lens: jax.Array,       # (B,) int32 valid KV length per row
    scale: Optional[float] = None,
    window: Optional[int] = None,
    group: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Decode attention straight off a paged KV cache. Returns (B, Hq, 1, D).

    The kernel (``attention_df.paged_flash_attention``) runs one grid
    step per row: it walks the row's pages in blocks, each page fetched
    once for all heads by a copy gathered through the block table (see
    docs/serving.md), and stops at the row's length, so the work
    follows each row's valid length, not ``max_pages``.  The xla/oracle
    path gathers pages into a contiguous per-row cache and defers to
    ``ref.attention_ref`` with ragged ``kv_len``.  Decode-only:
    ``Sq == 1``.
    """
    fault = _inject("kernel.attention")
    b, hq, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"paged_attention is decode-only (Sq == 1), got {sq}")
    hkv, _, page, _ = k_pages.shape
    group = group or hq // hkv
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        kg = jnp.moveaxis(k_pages[:, block_tables], 1, 0).reshape(
            b, hkv, -1, d)
        vg = jnp.moveaxis(v_pages[:, block_tables], 1, 0).reshape(
            b, hkv, -1, d)
        return _poison(
            ref.attention_ref(q, kg, vg, causal=True, window=window,
                              scale=scale, kv_len=kv_lens), fault)
    out = attention_df.paged_flash_attention(
        q.reshape(b, hq, d), k_pages, v_pages, block_tables, kv_lens,
        group=group, scale=scale, window=window,
        interpret=backend == "interpret",
    )
    return _poison(out.reshape(b, hq, 1, d), fault)


def _binary_problem(m: int, kp: int, n: int, n_bits: int,
                    out_dtype="int32") -> BinaryProblem:
    return BinaryProblem(m=m, kp=kp, n=n, n_bits=n_bits,
                         out_dtype=str(jnp.dtype(out_dtype)))


@functools.partial(jax.jit, static_argnames=("n_bits", "spec", "backend"))
def binary_matmul(
    a_packed: jax.Array, b_packed: jax.Array, n_bits: int,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Packed +-1 GEMM: (M, Kp) x (Kp, N) uint32 -> (M, N) int32 dots.

    ``n_bits`` is the true pre-packing reduction depth K.  With
    ``spec=None`` the dataflow (anchor AND ``(bm, bkp, bn)`` blocking)
    comes from the ``core.autotune`` cache keyed on the
    ``BinaryProblem``.  Zero-padded packed words xor to 0 on both sides
    and drop out of the popcount, so the ``K - 2*popcount`` identity
    absorbs the tile padding with no post-correction.
    """
    fault = _inject("kernel.binary_matmul")
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        return _poison(ref.binary_matmul_ref(a_packed, b_packed, n_bits),
                       fault)
    m, kp = a_packed.shape
    n = b_packed.shape[1]
    spec = _resolve_spec(spec, _binary_problem(m, kp, n, n_bits), backend)
    bm, bkp, bn = spec.block
    ap = _pad_to(a_packed, (bm, bkp))
    bp = _pad_to(b_packed, (bkp, bn))
    spec = spec.with_block((min(bm, ap.shape[0]), min(bkp, ap.shape[1]),
                            min(bn, bp.shape[1])))
    out = binary_mm.binary_mm_df(
        ap, bp, n_bits, spec, out_dtype=jnp.int32,
        interpret=backend == "interpret",
    )
    return _poison(out[:m, :n], fault)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "binarize", "spec", "out_dtype",
                              "backend"),
)
def binary_matmul_fused(
    a_packed: jax.Array, b_packed: jax.Array, n_bits: int,
    scale: Optional[jax.Array] = None,      # scalar or (N,) folded-BN gamma
    bias: Optional[jax.Array] = None,       # (N,) folded-BN beta
    residual: Optional[jax.Array] = None,   # (M, N)
    binarize: bool = False,
    spec: Optional[DataflowSpec] = None,
    out_dtype=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Fused-epilogue binary GEMM: ``y = scale * dot + bias + residual``
    then ``sign(y)`` when ``binarize``.

    One kernel dispatch per layer: the folded batchnorm and the
    re-binarization run in-register at the accumulator flush, so chained
    binary layers emit +-1 int8 activations directly and the int32
    accumulator never round-trips HBM.  Output dtype defaults to int8
    (+-1) when ``binarize`` else float32.
    """
    fault = _inject("kernel.binary_matmul")
    m, kp = a_packed.shape
    n = b_packed.shape[1]
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32)
        if scale.size == 1:
            scale = scale.reshape(1, 1)
        elif scale.size == n:
            scale = scale.reshape(1, n)
        else:
            raise ValueError(
                f"scale must be scalar or per-output-column (N={n}), "
                f"got {scale.shape}"
            )
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, n)
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        return _poison(ref.binary_matmul_fused_ref(
            a_packed, b_packed, n_bits, scale=scale, bias=bias,
            residual=residual, binarize=binarize, out_dtype=out_dtype,
        ), fault)
    epi = BinaryEpilogue(
        scale=scale is not None, bias=bias is not None,
        residual=residual is not None, binarize=binarize,
    )
    out_dt = out_dtype or (jnp.int8 if binarize else jnp.float32)
    spec = _resolve_spec(
        spec, _binary_problem(m, kp, n, n_bits, out_dt), backend)
    bm, bkp, bn = spec.block
    ap = _pad_to(a_packed, (bm, bkp))
    bp = _pad_to(b_packed, (bkp, bn))
    if scale is not None and scale.shape != (1, 1):
        scale = _pad_to(scale, (1, bn))
    if bias is not None:
        bias = _pad_to(bias, (1, bn))
    if residual is not None:
        residual = _pad_to(residual, (bm, bn))
    spec = spec.with_block((min(bm, ap.shape[0]), min(bkp, ap.shape[1]),
                            min(bn, bp.shape[1])))
    out = binary_mm.binary_mm_df(
        ap, bp, n_bits, spec, out_dtype=out_dt,
        interpret=backend == "interpret",
        epilogue=epi, scale=scale, bias=bias, residual=residual,
    )
    return _poison(out[:m, :n], fault)


@functools.partial(
    jax.jit, static_argnames=("stride", "n_bits", "binarize", "spec",
                              "out_dtype", "backend"),
)
def binary_conv2d(
    x_packed: jax.Array,   # (N, H, W, Cp) uint32 channel-packed image
    w_packed: jax.Array,   # (fh, fw, Cp, Cout) uint32
    stride: int = 1,
    n_bits: Optional[int] = None,   # true reduction depth fh*fw*cin
    scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,   # (N, oh, ow, Cout)
    binarize: bool = False,
    spec: Optional[DataflowSpec] = None,
    out_dtype=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Binary NHWC conv (VALID padding) via the implicit-GEMM view.

    The channel-packed image is patch-extracted to the (N*oh*ow,
    fh*fw*Cp) GEMM view (XLA slices — not a kernel dispatch) and runs
    through the single-dispatch binary GEMM, optionally with the fused
    folded-BN/sign epilogue, so a binary convnet layer is ONE
    ``pallas_call`` end to end.  ``n_bits`` defaults to every packed bit
    (fh*fw*32*Cp); pass ``fh*fw*cin`` when cin doesn't fill the last
    word.  With ``spec=None`` the dataflow resolves through the
    ``core.autotune`` cache keyed on the implicit-GEMM
    ``BinaryProblem``.
    """
    nb, ih, iw, cp = x_packed.shape
    fh, fw, _, cout = w_packed.shape
    oh = (ih - fh) // stride + 1
    ow = (iw - fw) // stride + 1
    if n_bits is None:
        n_bits = fh * fw * 32 * cp
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32).reshape(1, -1)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, -1)
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if backend == "xla":
        return ref.binary_conv2d_ref(
            x_packed, w_packed, stride, n_bits=n_bits,
            scale=scale, bias=bias,
            residual=residual, binarize=binarize, out_dtype=out_dtype,
        )
    cols = ref.binary_im2col(x_packed, fh, fw, stride)
    a = cols.reshape(nb * oh * ow, fh * fw * cp)
    b = w_packed.reshape(fh * fw * cp, cout)
    res2 = (residual.reshape(nb * oh * ow, cout)
            if residual is not None else None)
    if scale is None and bias is None and res2 is None and not binarize:
        out = binary_matmul(a, b, n_bits, spec=spec, backend=backend)
        if out_dtype is not None:
            out = out.astype(out_dtype)
    else:
        out = binary_matmul_fused(
            a, b, n_bits, scale=scale, bias=bias, residual=res2,
            binarize=binarize, spec=spec, out_dtype=out_dtype,
            backend=backend,
        )
    return out.reshape(nb, oh, ow, cout)


@functools.partial(jax.jit, static_argnames=("spec", "backend"))
def int8_matmul(
    aq: jax.Array, bq: jax.Array, a_scale: jax.Array, b_scale: jax.Array,
    spec: Optional[DataflowSpec] = None, backend: Optional[str] = None,
) -> jax.Array:
    """Quantized GEMM: int8 x int8 -> int32 (MXU) -> dequantized f32."""
    acc = matmul(aq, bq, spec=spec, out_dtype=jnp.int32, backend=backend)
    return acc.astype(jnp.float32) * a_scale * b_scale


@functools.partial(
    jax.jit, static_argnames=("activation", "spec", "out_dtype", "backend")
)
def matmul_fused(
    a: jax.Array,
    b: jax.Array,
    bias: Optional[jax.Array] = None,       # (N,) or (1, N) float
    scale: Optional[jax.Array] = None,      # scalar, (N,) or (M, 1) scale
    residual: Optional[jax.Array] = None,   # (M, N)
    activation: Optional[str] = None,       # relu | gelu | silu
    spec: Optional[DataflowSpec] = None,
    out_dtype=None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Fused-epilogue GEMM: ``act(scale * (a @ b) + bias) + residual``.

    One kernel dispatch per layer: the epilogue runs in-register on the
    accumulator, so the raw GEMM result never round-trips HBM.  Shapes
    pad automatically like ``matmul``; epilogue math is float32 and the
    default output dtype is float32.

    ``scale`` may be per-tensor (scalar), per-column ((N,) / (1, N)) or
    per-row ((M, 1) — e.g. int8 per-activation-row dequant).  When
    M == N an explicit 2-D shape disambiguates; a 1-D vector defaults to
    per-column.
    """
    fault = _inject("kernel.matmul")
    m, k = a.shape
    n = b.shape[1]
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, n)
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32)
        if scale.size == 1:
            scale = scale.reshape(1, 1)
        elif scale.ndim == 2 and scale.shape == (m, 1):
            pass  # per-row, explicitly shaped
        elif scale.size == n and not (scale.ndim == 2
                                      and scale.shape[1] == 1):
            scale = scale.reshape(1, n)
        elif scale.size == m and (scale.ndim == 1
                                  or scale.shape[1] == 1):
            scale = scale.reshape(m, 1)
        else:
            raise ValueError(
                f"scale must be scalar, per-column (N={n}) or per-row "
                f"(M={m}, 1), got {scale.shape}"
            )
    if backend == "xla":
        return _poison(ref.matmul_fused_ref(
            a, b, bias=bias, scale=scale, residual=residual,
            activation=activation, out_dtype=out_dtype,
        ), fault)
    epi = Epilogue(
        bias=bias is not None,
        activation=activation,
        scale=scale is not None,
        residual=residual is not None,
    )
    spec = _resolve_spec(
        spec, _gemm_problem(m, k, n, a.dtype, out_dtype or jnp.float32),
        backend)
    bm, bk, bn = spec.block
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    mp, np_ = ap.shape[0], bp.shape[1]
    if bias is not None:
        bias = _pad_to(bias, (1, bn))
    if scale is not None and scale.shape[1] != 1:
        scale = _pad_to(scale, (1, bn))
    elif scale is not None and scale.shape[0] != 1:
        scale = _pad_to(scale, (bm, 1))  # per-row rides the M padding
    if residual is not None:
        residual = _pad_to(residual, (bm, bn))
    spec = spec.with_block((min(bm, mp), min(bk, ap.shape[1]),
                            min(bn, np_)))
    out = matmul_df.matmul_df(
        ap, bp, spec, out_dtype=out_dtype or jnp.float32,
        interpret=backend == "interpret",
        epilogue=epi, scale=scale, bias=bias, residual=residual,
    )
    return _poison(out[:m, :n], fault)


@functools.partial(jax.jit, static_argnames=("activation", "spec", "backend"))
def int8_matmul_fused(
    aq: jax.Array, bq: jax.Array, a_scale: jax.Array, b_scale: jax.Array,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Quantized GEMM with the dequant + epilogue fused into the kernel:
    ``act((a_scale * b_scale) * (aq @ bq) + bias) + residual`` -> f32.

    Scales must be per-tensor (scalar), combine to per-output-column
    (1, N), or combine to per-activation-row (M, 1); a full (M, N) scale
    grid (per-row activations x per-column weights) needs the unfused
    ``int8_matmul``.
    """
    scale = (jnp.asarray(a_scale, jnp.float32)
             * jnp.asarray(b_scale, jnp.float32))
    m, n = aq.shape[0], bq.shape[1]
    # shape-based dispatch: a per-row (M, 1) scale must not be mistaken
    # for a per-column vector even when M == N
    per_tensor = scale.size == 1
    per_column = (scale.shape == (n,)
                  or (scale.ndim == 2 and scale.shape[0] == 1
                      and scale.shape[1] == n))
    per_row = scale.ndim == 2 and scale.shape == (m, 1)
    if not (per_tensor or per_column or per_row):
        raise ValueError(
            f"fused dequant needs scalar, per-column or per-row scales, "
            f"got combined shape {scale.shape}; use int8_matmul instead"
        )
    return matmul_fused(
        aq, bq, bias=bias,
        scale=scale if per_row else scale.reshape(1, -1),
        residual=residual,
        activation=activation, spec=spec, backend=backend,
    )


# ---------------------------------------------------------------------------
# Sub-byte packed-weight GEMM / conv (kernels/pack.py).
#
# The weight never exists densely in HBM: the kernel streams the packed
# nibble/bit planes and decompresses each (bk, bn) slab to int8 lanes in
# VMEM at the stripe load.  Outlier rows (MSR sidecar) are compensated by
# a precomputed ``A[:, idx] @ delta`` term added to the accumulator at the
# epilogue flush, so the corrected int32 accumulator never round-trips
# HBM raw.  Both ops are *bit-exact* against the dequantize-then-matmul
# oracles (``ref.matmul_packed_ref`` / ``ref.conv2d_packed_ref``) when the
# epilogue is scale-only.
# ---------------------------------------------------------------------------


def _packed_gran(bits: int) -> int:
    return 32 if bits == 5 else 8


@functools.partial(jax.jit, static_argnames=("activation", "spec", "backend"))
def matmul_packed_fused(
    aq: jax.Array,                    # (M, K) int8 activations
    pw: pack.PackedWeights,
    a_scale: Optional[jax.Array] = None,    # per-tensor activation scale
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Packed-weight GEMM with in-register decompress and fused epilogue:
    ``act((a_scale * w_scale) * (aq @ W) + bias) + residual`` -> f32,
    where ``W`` is the exact int8 image of the packed weight.

    The spec resolves through the autotune cache keyed on the
    ``weight_bits``-tagged :class:`GemmProblem`, so packed and plain
    layouts rank (and cache) independently.
    """
    fault = _inject("kernel.matmul")
    m, k = aq.shape
    if k != pw.k:
        raise ValueError(f"activation K={k} != packed weight k={pw.k}")
    n = pw.n
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if a_scale is not None:
        a_scale = jnp.asarray(a_scale, jnp.float32)
        if a_scale.size != 1:
            raise ValueError(
                f"a_scale must be per-tensor (scalar), got {a_scale.shape}")
        a_scale = a_scale.reshape(1, 1)
    if backend == "xla":
        return _poison(ref.matmul_packed_ref(
            aq, pw, a_scale=a_scale, bias=bias, residual=residual,
            activation=activation,
        ), fault)
    scale = pw.scale if a_scale is None else a_scale * pw.scale  # (1, N)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, n)
    epi = Epilogue(
        scale=True, bias=bias is not None, activation=activation,
        residual=residual is not None,
    )
    spec = _resolve_spec(
        spec,
        _gemm_problem(m, k, n, aq.dtype, jnp.float32, weight_bits=pw.bits),
        backend)
    bm, bk, bn = spec.block
    gran = _packed_gran(pw.bits)
    if bk % gran:  # packed slabs decode in whole int32 words
        bk = max(gran, bk - bk % gran)
    # activations pad to the pack-time K (mult of 32), then to the block;
    # packed planes zero-pad along K/N — pad rows decode against zero
    # activation columns, pad columns are sliced off the output
    ap = _pad_to(jnp.pad(aq, ((0, 0), (0, pw.k_pad - k))), (bm, bk))
    codes = _pad_to(pw.codes, (bk // 8, bn))
    hi = (_pad_to(pw.highbits, (bk // 32, bn))
          if pw.highbits is not None else None)
    mp, kp, np_ = ap.shape[0], ap.shape[1], codes.shape[1]
    scale_p = _pad_to(scale, (1, bn))
    if bias is not None:
        bias = _pad_to(bias, (1, bn))
    if residual is not None:
        residual = _pad_to(residual, (bm, bn))
    comp = None
    if pw.outlier_idx.shape[0]:
        gathered = jnp.take(ap, pw.outlier_idx, axis=1, mode="fill",
                            fill_value=0).astype(jnp.int32)
        comp = _pad_to(
            jnp.dot(gathered, pw.outlier_delta,
                    preferred_element_type=jnp.int32),
            (bm, bn))
    spec = spec.with_block((min(bm, mp), min(bk, kp), min(bn, np_)))
    out = matmul_df.matmul_df(
        ap, codes, spec, out_dtype=jnp.float32,
        interpret=backend == "interpret",
        epilogue=epi, scale=scale_p, bias=bias, residual=residual,
        weight_bits=pw.bits, b_hi=hi, comp=comp,
    )
    return _poison(out[:m, :n], fault)


def matmul_packed(
    aq: jax.Array,
    pw: pack.PackedWeights,
    a_scale: Optional[jax.Array] = None,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Packed-weight GEMM, dequant-only epilogue:
    ``(a_scale * w_scale) * (aq @ W)`` -> f32 (bit-exact vs the oracle)."""
    return matmul_packed_fused(aq, pw, a_scale=a_scale, spec=spec,
                               backend=backend)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "activation", "spec", "b_oh", "bc", "bk",
                     "backend"),
)
def conv2d_packed_fused(
    xq: jax.Array,                    # (N, H, W, Cin) int8
    pcw: pack.PackedConvWeights,
    stride: int = 1,
    x_scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    spec: Optional[DataflowSpec] = None,
    b_oh: int = 8,
    bc: int = 128,
    bk: int = 128,
    backend: Optional[str] = None,
) -> jax.Array:
    """Packed-weight conv with in-register decompress and fused epilogue.

    Outlier compensation is materialized op-side: each sidecar slot is a
    (tap, channel) row whose activation window patch is sliced out of the
    padded image and rank-1-multiplied with the delta row; the summed
    (N, oh, ow, K) int32 term joins the accumulator at the kernel flush.
    """
    fault = _inject("kernel.conv2d")
    n, ih, iw, cin = xq.shape
    if cin != pcw.cin:
        raise ValueError(f"input channels {cin} != packed cin {pcw.cin}")
    fh, fw, kout, cp = pcw.fh, pcw.fw, pcw.kout, pcw.cin_pad
    oh = (ih - fh) // stride + 1
    ow = (iw - fw) // stride + 1
    backend = backend or ("pallas" if _on_tpu() else "xla")
    if x_scale is not None:
        x_scale = jnp.asarray(x_scale, jnp.float32)
        if x_scale.size != 1:
            raise ValueError(
                f"x_scale must be per-tensor (scalar), got {x_scale.shape}")
        x_scale = x_scale.reshape(1, 1)
    if backend == "xla":
        return _poison(ref.conv2d_packed_ref(
            xq, pcw, stride, x_scale=x_scale, bias=bias, residual=residual,
            activation=activation,
        ), fault)
    scale = pcw.scale if x_scale is None else x_scale * pcw.scale  # (1, K)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(1, kout)
    epi = Epilogue(
        scale=True, bias=bias is not None, activation=activation,
        residual=residual is not None,
    )
    override = spec if isinstance(spec, SpecOverride) else None
    if spec is None or override is not None:
        try:
            spec = autotune.best_spec(
                _conv_problem(n, ih, iw, fh, fw, stride, cin, kout,
                              xq.dtype, jnp.float32, weight_bits=pcw.bits),
                backend=backend,
            )
            b_oh, bc, bk = spec.block
        except ValueError:
            spec = DataflowSpec.optimized()  # see conv2d's fallback note
        if override is not None:
            spec = override.merge(spec.with_block((b_oh, bc, bk)))
            b_oh, bc, bk = spec.block
    gran = _packed_gran(pcw.bits)
    bc_ = min(bc, -(-cp // 128) * 128)
    if bc_ % gran:
        raise ValueError(
            f"packed conv needs a channel block divisible by {gran}, "
            f"got bc={bc_}")
    bk_ = min(bk, -(-kout // 128) * 128)
    b_oh_ = min(b_oh, oh)
    oh_pad = -(-oh // b_oh_) * b_oh_
    ih_need = (oh_pad - 1) * stride + fh + (stride - 1)
    iw_need = (ow - 1) * stride + fw + (stride - 1)
    # channels pad to the pack-time cin_pad (per-tap mult of 32) first so
    # the image and the planes agree on the lane layout, then to bc_
    xp = _pad_to(jnp.pad(xq, ((0, 0), (0, 0), (0, 0), (0, cp - cin))),
                 (1, 1, 1, bc_))
    xp = jnp.pad(
        xp,
        ((0, 0), (0, max(0, ih_need - ih)), (0, max(0, iw_need - iw)),
         (0, 0)),
    )
    codes = _pad_to(pcw.codes, (1, 1, bc_ // 8, bk_))
    hi = (_pad_to(pcw.highbits, (1, 1, bc_ // 32, bk_))
          if pcw.highbits is not None else None)
    kpad = codes.shape[3]
    scale_p = _pad_to(scale, (1, bk_))
    if bias is not None:
        bias = _pad_to(bias, (1, bk_))
    if residual is not None:
        residual = jnp.pad(
            residual,
            ((0, 0), (0, oh_pad - oh), (0, 0), (0, kpad - kout)),
        )
    comp = None
    cap = pcw.outlier_idx.shape[0]
    if cap:
        hslice = (oh_pad - 1) * stride + 1
        wslice = (ow - 1) * stride + 1
        delta_p = _pad_to(pcw.outlier_delta, (1, bk_))
        comp = jnp.zeros((n, oh_pad, ow, kpad), jnp.int32)
        for r in range(cap):
            f = pcw.outlier_idx[r]          # flat (ky*fw + kx)*cp + c
            ky = f // (fw * cp)
            kx = (f // cp) % fw
            c = f % cp
            # dynamic_slice clamps the sentinel row (ky == fh) in bounds;
            # its zero delta nullifies the garbage patch
            patch = jax.lax.dynamic_slice(
                xp, (0, ky, kx, c), (n, hslice, wslice, 1))
            patch = patch[:, ::stride, ::stride, 0].astype(jnp.int32)
            comp = comp + patch[..., None] * delta_p[r][None, None, None, :]
    out = conv2d_df.conv2d_df(
        xp, codes, stride, spec, oh=oh_pad, ow=ow, b_oh=b_oh_, bc=bc_,
        bk=bk_, out_dtype=jnp.float32, interpret=backend == "interpret",
        epilogue=epi, scale=scale_p, bias=bias, residual=residual,
        weight_bits=pcw.bits, w_hi=hi, comp=comp,
    )
    return _poison(out[:, :oh, :, :kout], fault)


def conv2d_packed(
    xq: jax.Array,
    pcw: pack.PackedConvWeights,
    stride: int = 1,
    x_scale: Optional[jax.Array] = None,
    spec: Optional[DataflowSpec] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Packed-weight conv, dequant-only epilogue (bit-exact vs oracle)."""
    return conv2d_packed_fused(xq, pcw, stride=stride, x_scale=x_scale,
                               spec=spec, backend=backend)
