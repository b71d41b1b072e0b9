"""Unified dataflow-subsystem registry + attention anchor parity (PR 4).

Covers: the problem registry's four built-in registrations and the
generic ``explore``/``autotune`` dispatch; ``AttentionProblem`` keying
(``v4|attn|...``) and cache behavior; OS(flash)/WS(kv-stationary)
anchor parity against ``ref.attention_ref`` across GQA groups,
causal/windowed masks and ragged (right-aligned padding) shapes; the
decode ``Sq=1`` single-dispatch fast path; the WS compiled-backend loop
honoring the registry spec's ``(bq, bkv)``; and the per-problem
``measure`` hooks that extend the ``REPRO_AUTOTUNE_REFINE=1`` empirical
re-rank beyond GEMM to conv, binary and attention problems.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune, cost_model, explorer
from repro.core.dataflow import (
    AttentionProblem,
    BinaryProblem,
    ConvProblem,
    DataflowSpec,
    GemmProblem,
    ProblemRegistration,
    register_problem,
    registered_kinds,
    registration_for,
    IS,
    OS,
    WS,
)
from repro.core.jaxpr_utils import count_pallas_calls, count_primitive
from repro.kernels import ops, ref

ATTN_PROBLEM = AttentionProblem(bh=8, sq=256, skv=256, d=64, group=2)
CONV_PROBLEM = ConvProblem(ih=10, iw=10, fh=3, fw=3, s=1, cin=32, cout=64,
                           n=1, in_dtype="float32", out_dtype="float32")
BIN_PROBLEM = BinaryProblem(m=64, kp=4, n=128, n_bits=128)
GEMM_PROBLEM = GemmProblem(m=128, k=128, n=128, in_dtype="float32",
                           out_dtype="float32")


# ---------------------------------------------------------------------------
# Registry mechanics.
# ---------------------------------------------------------------------------
def test_registry_covers_four_subsystems():
    kinds = registered_kinds()
    assert kinds == {
        "gemm": GemmProblem, "conv": ConvProblem, "bin": BinaryProblem,
        "attn": AttentionProblem,
    }
    for prob in (GEMM_PROBLEM, CONV_PROBLEM, BIN_PROBLEM, ATTN_PROBLEM):
        reg = registration_for(prob)
        assert reg.problem_cls is type(prob)
        assert callable(reg.enumerate) and callable(reg.time_estimate)
        assert callable(reg.vmem_footprint) and callable(reg.measure)
        # every registration's key head is pure strings
        assert all(isinstance(s, str) for s in reg.key_fields(prob))


def test_unregistered_problem_type_raises():
    with pytest.raises(TypeError, match="not a registered"):
        registration_for(object())


def test_generic_explore_dispatches_all_kinds():
    for prob in (GEMM_PROBLEM, CONV_PROBLEM, BIN_PROBLEM, ATTN_PROBLEM):
        ranked = explorer.explore(prob, top=3)
        assert ranked, prob
        assert ranked[0].est_seconds <= ranked[-1].est_seconds
        # the registration's footprint hook accepts the winning spec
        foot = registration_for(prob).vmem_footprint(prob, ranked[0].spec)
        assert foot > 0


def test_registering_new_subsystem_needs_no_autotune_edits(tmp_path,
                                                           monkeypatch):
    """The registry contract: a brand-new problem type resolves through
    best_spec with only a register_problem call (the PR-4 point)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "cache.json"))
    autotune.clear()

    @dataclasses.dataclass(frozen=True)
    class ToyProblem:
        n: int

    toy_spec = DataflowSpec.basic(OS, block=(8, 8, 8))
    register_problem(ProblemRegistration(
        kind="toy", problem_cls=ToyProblem,
        key_fields=lambda p: (str(p.n),),
        enumerate=lambda p, hw, **kw: [
            explorer.Candidate(toy_spec, 1.0, p.n, True)],
        time_estimate=lambda p, spec, hw: 1.0,
        vmem_footprint=lambda p, spec: 8,
    ))
    try:
        got = autotune.best_spec(ToyProblem(n=4), backend="interpret")
        assert got == toy_spec
        key = autotune._key(ToyProblem(n=4), cost_model.V5E, "interpret")
        assert key.startswith(f"v{autotune.CACHE_VERSION}|toy|4|")
    finally:
        from repro.core import dataflow as df
        df._REGISTRY.pop(ToyProblem, None)
        autotune.clear()


# ---------------------------------------------------------------------------
# Attention cost model + explorer.
# ---------------------------------------------------------------------------
def test_attention_traffic_os_beats_ws():
    """Flash (OS) moves less HBM than kv-stationary (WS) — the WS state
    round-trips dominate — so the explorer must rank OS first."""
    spec_os = DataflowSpec.basic(OS, block=(128, 128, 64))
    spec_ws = DataflowSpec.basic(WS, block=(128, 128, 64))
    t_os = cost_model.attention_traffic(ATTN_PROBLEM, spec_os)
    t_ws = cost_model.attention_traffic(ATTN_PROBLEM, spec_ws)
    assert t_os.total < t_ws.total
    assert t_ws.reads[OS] > 0 and t_ws.writes[OS] > t_os.writes[OS]
    best = explorer.explore(ATTN_PROBLEM, top=1)[0]
    assert best.spec.anchor == OS


def test_attention_vmem_filter_and_is_anchor_rejected():
    tiny = dataclasses.replace(cost_model.V5E, vmem_bytes=1024)
    assert explorer.enumerate_attention_candidates(ATTN_PROBLEM, tiny) == []
    with pytest.raises(ValueError, match="no feasible dataflow"):
        explorer.best_spec(ATTN_PROBLEM, tiny)
    with pytest.raises(ValueError, match="OS/WS"):
        cost_model.attention_traffic(
            ATTN_PROBLEM, DataflowSpec.basic(IS, block=(128, 128, 64)))


def test_attention_decode_candidates_single_q_row():
    dec = AttentionProblem(bh=8, sq=1, skv=512, d=64, group=2)
    for cand in explorer.explore(dec, top=5):
        assert cand.spec.block[0] == 1   # no q blocking at Sq=1


# ---------------------------------------------------------------------------
# Autotune keying + resolution.
# ---------------------------------------------------------------------------
def test_attention_autotune_keys():
    key = autotune._key(ATTN_PROBLEM, cost_model.V5E, "interpret")
    assert key.startswith(f"v{autotune.CACHE_VERSION}|attn|8|256|256|64|2|")
    variants = [
        dataclasses.replace(ATTN_PROBLEM, causal=False),
        dataclasses.replace(ATTN_PROBLEM, window=128),
        dataclasses.replace(ATTN_PROBLEM, group=1),
        dataclasses.replace(ATTN_PROBLEM, sq=1),
        dataclasses.replace(ATTN_PROBLEM, dtype="bfloat16"),
    ]
    keys = {key} | {
        autotune._key(p, cost_model.V5E, "interpret") for p in variants
    }
    assert len(keys) == 1 + len(variants)   # every field is keyed


def test_gemm_keys_carry_registry_kind_tag():
    key = autotune._key(GEMM_PROBLEM, cost_model.V5E, "interpret")
    assert key.startswith(f"v{autotune.CACHE_VERSION}|gemm|128|128|128|")


def test_attention_autotune_cache_hits():
    autotune.clear(disk=True)
    autotune.reset_stats()
    s1 = autotune.best_spec(ATTN_PROBLEM, backend="interpret")
    s2 = autotune.best_spec(ATTN_PROBLEM, backend="interpret")
    st = autotune.stats()
    assert s1 == s2
    assert (st["lookups"], st["misses"], st["hits"]) == (2, 1, 1)
    # survives an in-process drop via the disk store
    autotune.clear(disk=False)
    s3 = autotune.best_spec(ATTN_PROBLEM, backend="interpret")
    assert s3 == s1 and autotune.stats()["enumerations"] == 1


def test_ops_attention_resolves_through_autotune():
    """ops.attention(spec=None) must consult the cache keyed on the
    AttentionProblem: the trace-time lookup after a direct best_spec
    call is a cache hit, not a fresh enumeration."""
    autotune.clear(disk=True)
    autotune.reset_stats()
    prob = AttentionProblem(bh=4, sq=128, skv=128, d=64, group=2,
                            causal=True, window=None, dtype="float32")
    autotune.best_spec(prob, backend="interpret")
    assert autotune.stats()["misses"] == 1
    q = jnp.zeros((1, 4, 128, 64), jnp.float32)
    k = jnp.zeros((1, 2, 128, 64), jnp.float32)
    ops.attention(q, k, k, causal=True, backend="interpret")
    st = autotune.stats()
    assert st["misses"] == 1 and st["hits"] >= 1


# ---------------------------------------------------------------------------
# Anchor parity: GQA, masks, ragged padding (satellite).
# ---------------------------------------------------------------------------
PARITY_CASES = [
    # (b, hq, hkv, sq, skv, causal, window)
    (2, 4, 2, 256, 256, True, None),     # GQA group=2
    (1, 8, 2, 128, 128, True, None),     # GQA group=4
    (1, 4, 1, 150, 200, True, None),     # ragged: sq/skv pad, group=4
    (1, 4, 2, 100, 260, True, 64),       # ragged + sliding window
    (1, 4, 2, 256, 256, True, 128),      # windowed causal
    (2, 2, 2, 200, 200, False, None),    # bidirectional
]


@pytest.mark.parametrize("case", PARITY_CASES)
@pytest.mark.parametrize("anchor", ["os", "ws"])
def test_attention_anchor_parity(case, anchor):
    b, hq, hkv, sq, skv, causal, win = case
    rng = np.random.default_rng(hash(case) % 2 ** 31)
    q = jnp.asarray(rng.normal(size=(b, hq, sq, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, 64)), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, window=win,
                        backend="interpret", anchor=anchor)
    want = ref.attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-3)


@pytest.mark.parametrize("anchor", ["os", "ws"])
def test_attention_decode_parity(anchor):
    """The right-aligned Sq=1 decode row attends over the whole cache."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 384, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 384, 64)), jnp.float32)
    got = ops.attention(q, k, v, causal=True, backend="interpret",
                        anchor=anchor)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Decode fast path + spec-honoring lowerings (satellites).
# ---------------------------------------------------------------------------
def test_decode_fast_path_single_dispatch_no_q_padding():
    """Sq=1 must lower as ONE kernel dispatch with NO pad ops (q is
    neither padded nor blocked; skv here is already block-aligned)."""
    q = jnp.zeros((1, 8, 1, 64), jnp.float32)
    k = jnp.zeros((1, 2, 256, 64), jnp.float32)
    spec = DataflowSpec.basic(OS, block=(1, 128, 64))
    jx = jax.make_jaxpr(
        lambda q, k, v: ops.attention(q, k, v, spec=spec,
                                      backend="interpret"))(q, k, k)
    assert count_pallas_calls(jx.jaxpr) == 1
    assert count_primitive(jx.jaxpr, "pad") == 0
    # the blocked prefill path DOES pad this ragged shape (contrast)
    qp = jnp.zeros((1, 8, 100, 64), jnp.float32)
    spec_p = DataflowSpec.basic(OS, block=(128, 128, 64))
    jx_p = jax.make_jaxpr(
        lambda q, k, v: ops.attention(q, k, v, spec=spec_p,
                                      backend="interpret"))(qp, k, k)
    assert count_primitive(jx_p.jaxpr, "pad") > 0


def test_kv_stationary_compiled_loop_honors_spec_block():
    """On compiled backends WS lowers as one aliased call per KV block —
    the loop must use the registry spec's bkv, not a built-in default."""
    q = jnp.zeros((1, 4, 256, 64), jnp.float32)
    k = jnp.zeros((1, 2, 512, 64), jnp.float32)
    for bkv, calls in ((128, 4), (256, 2)):
        spec = DataflowSpec.basic(WS, block=(128, bkv, 64))
        jx = jax.make_jaxpr(
            lambda q, k, v: ops.attention(q, k, v, spec=spec,
                                          backend="pallas"))(q, k, k)
        assert count_pallas_calls(jx.jaxpr) == calls, (bkv, calls)


def test_attention_spec_blocks_flow_to_both_kernels():
    """A non-default spec block must reach both kernel lowerings through
    ops.attention (clamped by cost_model.attention_block_clamp) and
    still match the oracle."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    want = ref.attention_ref(q, k, v, causal=True)
    for anchor_st, block in ((OS, (64, 64, 64)), (WS, (64, 64, 64)),
                             (OS, (512, 512, 64))):  # 512 clamps to 256
        spec = DataflowSpec.basic(anchor_st, block=block)
        got = ops.attention(q, k, v, causal=True, spec=spec,
                            backend="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Per-problem empirical refine hooks (satellite: conv/binary re-rank).
# ---------------------------------------------------------------------------
def test_refine_measure_hook_runs_for_conv_and_binary(monkeypatch):
    """REPRO_AUTOTUNE_REFINE=1 re-ranks conv and binary misses through
    the registration's measure hook (GEMM-only before PR 4)."""
    calls = []
    modes = []

    def spy(problem, specs, interpret=True):
        calls.append(type(problem).__name__)
        modes.append(interpret)
        return [(s, float(i)) for i, s in enumerate(specs)]

    monkeypatch.setattr(explorer, "_measure_conv", spy)
    monkeypatch.setattr(explorer, "_measure_binary", spy)
    monkeypatch.setattr(explorer, "_measure_attention", spy)
    monkeypatch.setenv("REPRO_AUTOTUNE_REFINE", "1")
    autotune.clear(disk=True)
    autotune.best_spec(CONV_PROBLEM, backend="interpret")
    autotune.best_spec(BIN_PROBLEM, backend="interpret")
    autotune.best_spec(ATTN_PROBLEM, backend="interpret")
    assert calls == ["ConvProblem", "BinaryProblem", "AttentionProblem"]
    # cached: the hook does not rerun on hits
    autotune.best_spec(CONV_PROBLEM, backend="interpret")
    assert len(calls) == 3
    # the re-rank times the backend being tuned: interpret mode only
    # for backend="interpret", the compiled kernels otherwise
    autotune.best_spec(ATTN_PROBLEM, backend="pallas")
    assert modes == [True, True, True, False]
    autotune.clear(disk=True)


def test_measure_hooks_execute_and_rank(monkeypatch):
    """The real hooks run the public ops in interpret mode and return a
    sorted (spec, seconds) ranking drawn from the candidate set."""
    monkeypatch.delenv("REPRO_AUTOTUNE_REFINE", raising=False)
    for prob in (BIN_PROBLEM,
                 AttentionProblem(bh=4, sq=128, skv=128, d=64, group=2),
                 CONV_PROBLEM):
        specs = [c.spec for c in explorer.explore(prob, top=2)]
        ranked = registration_for(prob).measure(prob, specs, interpret=True)
        assert sorted(s for _, s in ranked) == [s for _, s in ranked]
        assert {spec for spec, _ in ranked} == set(specs)


# ---------------------------------------------------------------------------
# Model/serving integration.
# ---------------------------------------------------------------------------
def test_hot_attention_problems_shapes():
    import dataclasses as dc

    from repro.configs.qwen3_1_7b import CONFIG as QWEN
    from repro.models import lm

    probs = lm.hot_attention_problems(QWEN, 2, 64, max_len=256)
    assert len(probs) == 2
    prefill, decode = probs
    assert (prefill.sq, prefill.skv) == (64, 64)
    assert (decode.sq, decode.skv) == (1, 256)
    for p in probs:
        assert p.bh == 2 * QWEN.n_heads
        assert p.group == QWEN.n_heads // QWEN.n_kv_heads
        assert p.d == QWEN.d_head
        # every warmed problem must actually resolve
        explorer.best_spec(p)
    ssm_cfg = dc.replace(QWEN, n_heads=0, n_kv_heads=0, family="ssm")
    assert lm.hot_attention_problems(ssm_cfg, 2, 64) == []
