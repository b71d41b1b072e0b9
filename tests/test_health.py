"""Unit tests for runtime/health.py: the fault-injection harness,
the step-timing/straggler monitor, the event ledger, and the
kernel-degradation policy (no model required — serve-loop integration
lives in test_fault_tolerance.py)."""
import os

import pytest

from repro.runtime import health


@pytest.fixture(autouse=True)
def _clean_fault_env():
    keys = ("REPRO_FAULT_PLAN", "REPRO_FAIL_AT_STEP", "REPRO_FAULT_HANG_S")
    saved = {k: os.environ.get(k) for k in keys}
    health.reset_faults()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    health.reset_faults()


# ---------------------------------------------------------------------------
# Fault-plan parsing.
# ---------------------------------------------------------------------------
def test_parse_fault_plan():
    specs = health.parse_fault_plan(
        "serve.prefill:0:raise, kernel.matmul:*:nan,autotune.load:2:hang")
    assert [s.site for s in specs] == [
        "serve.prefill", "kernel.matmul", "autotune.load"]
    assert specs[0].step == 0 and specs[0].kind == "raise"
    assert specs[1].step is None and specs[1].kind == "nan"
    assert specs[2].kind == "hang-timeout"   # "hang" sugar


@pytest.mark.parametrize("bad", ["bogus", "site:kind", "a:b:c:d:e",
                                 "serve.prefill:0:explode",
                                 "serve.prefill:x:raise"])
def test_parse_fault_plan_rejects_malformed(bad):
    with pytest.raises(ValueError):
        health.parse_fault_plan(bad)


def test_register_site_idempotent():
    n = len(health.INJECTION_SITES)
    health.register_site("serve.prefill")
    assert len(health.INJECTION_SITES) == n
    health.register_site("test.site")
    try:
        assert "test.site" in health.INJECTION_SITES
    finally:
        health.INJECTION_SITES.remove("test.site")


# ---------------------------------------------------------------------------
# maybe_inject semantics.
# ---------------------------------------------------------------------------
def test_inject_raise_at_hit():
    os.environ["REPRO_FAULT_PLAN"] = "x.site:1:raise"
    assert health.maybe_inject("x.site") is None       # hit 0
    with pytest.raises(health.SimulatedFailure):
        health.maybe_inject("x.site")                  # hit 1 fires
    assert health.maybe_inject("x.site") is None       # hit 2
    log = health.fault_log()
    assert [(f.site, f.hit, f.kind) for f in log] == [("x.site", 1, "raise")]


def test_inject_every_hit_and_nan_kind():
    os.environ["REPRO_FAULT_PLAN"] = "x.site:*:nan"
    assert health.maybe_inject("x.site") == "nan"
    assert health.maybe_inject("x.site") == "nan"
    assert health.maybe_inject("other.site") is None
    assert len(health.fault_log()) == 2


def test_inject_hang_sleeps():
    import time
    os.environ["REPRO_FAULT_PLAN"] = "x.site:0:hang-timeout"
    os.environ["REPRO_FAULT_HANG_S"] = "0.05"
    t0 = time.monotonic()
    assert health.maybe_inject("x.site") == "hang-timeout"
    assert time.monotonic() - t0 >= 0.05


def test_step_override_and_fail_at_step_compat():
    os.environ["REPRO_FAIL_AT_STEP"] = "6"
    for s in range(1, 6):
        health.maybe_inject_failure(s)
    with pytest.raises(health.SimulatedFailure):
        health.maybe_inject_failure(6)
    # keyed on the passed step, not the hit counter: a "restart" that
    # replays from step 4 does not re-fire before step 6
    health.reset_faults()
    health.maybe_inject_failure(4)
    health.maybe_inject_failure(5)
    with pytest.raises(health.SimulatedFailure):
        health.maybe_inject_failure(6)


def test_reset_faults_zeroes_counters():
    health.maybe_inject("x.site")
    health.maybe_inject("x.site")
    health.reset_faults()
    os.environ["REPRO_FAULT_PLAN"] = "x.site:0:nan"
    assert health.maybe_inject("x.site") == "nan"


# ---------------------------------------------------------------------------
# HealthMonitor: stragglers, hook, ledger.
# ---------------------------------------------------------------------------
def test_straggler_threshold_boundary():
    mon = health.HealthMonitor(window=16, threshold=2.0)
    for s in range(8):
        assert not mon.record(s, 0.1)
    # exactly at threshold x median is NOT a straggler (strict >)
    assert not mon.record(8, 0.2)
    assert mon.record(9, 0.21)
    assert len(mon.stragglers) == 1
    assert mon.stragglers[0].step == 9


def test_straggler_needs_history():
    mon = health.HealthMonitor(window=16, threshold=2.0)
    for s in range(7):
        mon.record(s, 0.01)
    # only 7 records of history -> no straggler call yet
    assert not mon.record(7, 10.0)
    assert mon.stragglers == []


def test_on_straggler_hook_and_ledger():
    seen = []
    mon = health.HealthMonitor(window=16, threshold=3.0,
                               on_straggler=seen.append)
    for s in range(10):
        mon.record(s, 0.1)
    mon.record(10, 1.0)
    assert len(seen) == 1 and seen[0].seconds == 1.0
    evs = mon.events_of("straggler")
    assert len(evs) == 1 and evs[0].step == 10
    rep = mon.report()
    assert rep["stragglers"] == 1
    assert rep["events"]["straggler"] == 1
    assert rep["steps"] == 11


def test_note_and_report_rollup():
    mon = health.HealthMonitor()
    mon.note("demotion", site="kernel.attention", step=3, detail="boom")
    mon.note("retry", site="serve.decode_step", step=3)
    mon.note("retry", site="serve.decode_step", step=4)
    assert len(mon.events_of("retry")) == 2
    rep = mon.report()
    assert rep["events"] == {"demotion": 1, "retry": 2}
    assert rep["median_step_seconds"] == 0.0


def test_monitor_memory_is_constant_in_the_steps_served():
    mon = health.HealthMonitor(window=16, threshold=3.0)
    stragglers = 0
    for s in range(5000):
        slow = s % 50 == 49
        stragglers += mon.record(s, 1.0 if slow else 0.1)
    assert stragglers == 100
    assert len(mon.records) == 16 and len(mon.stragglers) == 16
    assert mon.stragglers[-1].step == 4999
    rep = mon.report()
    assert rep["steps"] == 5000 and rep["stragglers"] == 100
    assert rep["median_step_seconds"] == 0.1


# ---------------------------------------------------------------------------
# DegradationPolicy.
# ---------------------------------------------------------------------------
def test_degradation_demote_cooldown_reprobe():
    mon = health.HealthMonitor()
    pol = health.DegradationPolicy(cooldown_steps=3)
    assert pol.backend_for(0, mon) == "primary"
    pol.on_failure("kernel.attention", 0, RuntimeError("lowering"), mon)
    assert pol.demoted
    assert pol.backend_for(1, mon) == "degraded"
    assert pol.backend_for(2, mon) == "degraded"
    # cooldown elapsed -> optimistic re-probe
    assert pol.backend_for(3, mon) == "primary"
    assert pol.probes == 1 and not pol.demoted
    # failing probe re-demotes for another cooldown
    pol.on_failure("kernel.attention", 3, RuntimeError("still bad"), mon)
    assert pol.backend_for(4, mon) == "degraded"
    assert pol.demotions == [("kernel.attention", 0), ("kernel.attention", 3)]
    kinds = [e.kind for e in mon.events]
    assert kinds == ["demotion", "probe", "demotion"]


def test_degradation_backoff_is_exponential():
    pol = health.DegradationPolicy(backoff_base_s=0.01)
    assert pol.backoff_seconds(0) == pytest.approx(0.01)
    assert pol.backoff_seconds(1) == pytest.approx(0.02)
    assert pol.backoff_seconds(3) == pytest.approx(0.08)
