"""Property tests on the cost model (Table-I analogue + TPU traffic)."""
import hypothesis.strategies as st
from hypothesis import assume, given, settings

from repro.core import cost_model, explorer
from repro.core.dataflow import (
    ConvProblem, DataflowSpec, GemmProblem, Residency, IS, OS, WS,
)

conv_problems = st.builds(
    ConvProblem,
    ih=st.integers(12, 128), iw=st.integers(12, 128),
    fh=st.integers(2, 5), fw=st.integers(2, 5),
    s=st.integers(1, 2),
    cin=st.sampled_from([32, 64, 128]), cout=st.sampled_from([64, 128]),
)

gemm_problems = st.builds(
    GemmProblem,
    m=st.sampled_from([256, 1024, 4096]),
    k=st.sampled_from([256, 1024, 4096]),
    n=st.sampled_from([256, 1024, 4096]),
)


@given(conv_problems)
@settings(max_examples=60, deadline=None)
def test_paper_observations_hold_for_all_layers(conv):
    # The paper's Observations 1, 3, 4, 5 re-derived from Table I.
    # Hypothesis found the regime boundary of Observation 1: when the
    # output tensor is barely larger than the filter (E < 2R — e.g.
    # 12x12 input, 5x5 filter, stride 2), WS's per-variable gain (R reads
    # + R writes) exceeds OS's (E reads), inverting the observation.  The
    # paper's layer grid (56/112 inputs, 3-5 filters) always has E >> R,
    # so we assert within that stated regime and record the boundary in
    # EXPERIMENTS.md SPaper-validation.
    assume(conv.E >= 2 * conv.R)
    obs = cost_model.paper_observations_hold(conv)
    assert all(obs.values()), obs


@given(gemm_problems)
@settings(max_examples=40, deadline=None)
def test_traffic_at_least_compulsory(p):
    # No dataflow moves fewer bytes than one read of each input + one
    # write of the output (compulsory traffic).
    compulsory = (p.m * p.k + p.k * p.n) * 2 + p.m * p.n * 4
    for anchor in (OS, WS, IS):
        t = cost_model.gemm_traffic(p, DataflowSpec.basic(anchor))
        assert t.total >= compulsory


@given(gemm_problems)
@settings(max_examples=40, deadline=None)
def test_basic_os_never_worse_than_ws_is(p):
    # Paper Fig. 2: among basic dataflows OS wins (no output RMW term).
    tos = cost_model.gemm_traffic(p, DataflowSpec.basic(OS)).total
    tws = cost_model.gemm_traffic(p, DataflowSpec.basic(WS)).total
    tis = cost_model.gemm_traffic(p, DataflowSpec.basic(IS)).total
    assert tos <= tws
    assert tos <= tis


@given(gemm_problems)
@settings(max_examples=30, deadline=None)
def test_aux_stationarity_never_increases_traffic(p):
    base = cost_model.gemm_traffic(p, DataflowSpec.basic(OS)).total
    ext = cost_model.gemm_traffic(
        p, DataflowSpec(OS, {WS: Residency.STRIPE}, (WS,))).total
    assert ext <= base


def test_explorer_picks_paper_optimized_dataflow():
    # Alg. 8: the best dataflow is OS-anchored with weight-aux first.
    p = GemmProblem(m=4096, k=4096, n=4096)
    best = explorer.best_spec(p)
    assert best.anchor == OS
    assert best.residency(WS) != Residency.STREAMED


def test_explorer_all_candidates_feasible():
    p = GemmProblem(m=2048, k=2048, n=2048)
    for c in explorer.enumerate_candidates(p):
        assert c.feasible
        assert c.traffic_bytes > 0


@given(conv_problems)
@settings(max_examples=30, deadline=None)
def test_conv_traffic_resident_input_bounded_by_unique_bytes(conv):
    spec = DataflowSpec(OS, {IS: Residency.WHOLE}, (IS,))
    t = cost_model.conv_traffic(conv, spec)
    unique = conv.n * conv.H * conv.cin
    assert t.reads[IS] == unique  # whole-resident: exactly one full read
    if conv.s == 1:
        # overlapping windows (s=1): residency can only reduce traffic.
        # (for s>1 a resident input may read unused pixels the streamed
        # form skips — the paper's sparse-reuse caveat, Fig. 5)
        streamed = cost_model.conv_traffic(conv, DataflowSpec.basic(OS))
        assert t.reads[IS] <= streamed.reads[IS]


def test_roofline_terms_and_dominance():
    r = cost_model.roofline(flops=1e15, hbm_bytes=1e12, collective_bytes=1e10,
                            chips=256)
    assert r.t_compute > 0 and r.t_memory > 0 and r.t_collective > 0
    assert r.dominant in ("compute", "memory", "collective")
    assert abs(r.t_compute - 1e15 / (256 * 197e12)) < 1e-12
    assert abs(r.t_memory - 1e12 / (256 * 819e9)) < 1e-12
    assert abs(r.t_collective - 1e10 / (256 * 50e9)) < 1e-12
    assert 0 <= r.compute_fraction <= 1


def test_model_flops():
    assert cost_model.model_flops(1e9, 1e6) == 6e15
    assert cost_model.model_flops(1e9, 1e6, training=False) == 2e15


def test_hardware_model_follows_the_device_kind():
    """On a TPU the hardware model comes from the device's kind, and an
    unknown kind raises instead of defaulting to v5e; off the chip the
    explorer models v5e."""
    import types

    import pytest

    def device(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert cost_model.hardware_for(device("tpu", "TPU v5 lite")) \
        is cost_model.V5E
    assert cost_model.hardware_for(device("cpu", "cpu")) is cost_model.V5E
    assert cost_model.hardware_for() is cost_model.V5E   # CPU test run
    with pytest.raises(ValueError, match="TPU v99"):
        cost_model.hardware_for(device("tpu", "TPU v99"))
