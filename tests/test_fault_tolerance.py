"""Fault-drill integration suite: the tested invariant is that for
every registered injection site, a serve run with that site faulted
once completes all admitted requests DONE with greedy token outputs
bit-identical to the fault-free run — degradation, not failure — and
that the health ledger records exactly the injected demotions/retries.
Also: request lifecycle/admission, deadline eviction, retry
exhaustion, and autotune-cache corruption recovery.

CI runs this file as the ``fault-drill`` job."""
import dataclasses
import gc
import glob
import json
import os

import numpy as np
import pytest

import jax

from repro import configs
from repro.core import autotune, cost_model
from repro.core.dataflow import GemmProblem
from repro.models import lm
from repro.runtime import health
from repro.serve.engine import (AdmissionError, Engine, RequestState,
                                StepFailed)

CFG = configs.get_smoke("qwen3-1.7b")
MAX_LEN = 48
NEW_TOKENS = 4


@pytest.fixture(autouse=True)
def _clean_fault_env():
    keys = ("REPRO_FAULT_PLAN", "REPRO_FAIL_AT_STEP", "REPRO_FAULT_HANG_S")
    saved = {k: os.environ.get(k) for k in keys}
    for k in keys:
        os.environ.pop(k, None)
    health.reset_faults()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    health.reset_faults()


@pytest.fixture(scope="module")
def served():
    params = lm.init_model(CFG, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, CFG.vocab_size, (2, 8)).astype(np.int32)
    return params, prompts


def _serve(params, prompts, plan=None, policy=None, deadline_s=None,
           new_tokens=NEW_TOKENS):
    """Fresh engine (fresh jit traces + hit counters) under ``plan``."""
    if plan is None:
        os.environ.pop("REPRO_FAULT_PLAN", None)
    else:
        os.environ["REPRO_FAULT_PLAN"] = plan
    health.reset_faults()
    eng = Engine(CFG, params, max_len=MAX_LEN, policy=policy)
    reqs = [eng.submit(p, new_tokens, deadline_s=deadline_s)
            for p in prompts]
    eng.serve(reqs)
    toks = [list(r.out_tokens) for r in reqs]
    return eng, reqs, toks


# ---------------------------------------------------------------------------
# The fault-drill invariant, over every registered site.
# ---------------------------------------------------------------------------
def test_fault_drill_every_site_degrades_not_fails(served):
    params, prompts = served
    _, base_reqs, base = _serve(params, prompts)
    assert all(r.state == RequestState.DONE for r in base_reqs)

    failures = []
    for site in health.INJECTION_SITES:
        # nan faults only matter where float outputs flow through the
        # serve path; elsewhere one raise-kind drill per site suffices
        kinds = (("raise", "nan")
                 if site.startswith(("serve.", "layers.")) else ("raise",))
        for kind in kinds:
            plan = f"{site}:0:{kind}"
            eng, reqs, toks = _serve(params, prompts, plan=plan)
            states = [r.state.value for r in reqs]
            fired = [(f.site, f.kind) for f in health.fault_log()]
            # ledger records exactly the injected demotions/retries:
            # one demotion + one retry per fired fault that reached the
            # serve path, none otherwise
            ev = eng.monitor.report()["events"]
            expected = len(fired)
            if (toks != base
                    or any(s != "done" for s in states)
                    or ev.get("demotion", 0) != expected
                    or ev.get("retry", 0) != expected):
                failures.append((plan, states, toks, fired, ev))
    assert not failures, failures


def test_hang_fault_is_straggle_not_crash(served):
    params, prompts = served
    os.environ["REPRO_FAULT_HANG_S"] = "0.05"
    _, base_reqs, base = _serve(params, prompts, new_tokens=12)
    eng, reqs, toks = _serve(params, prompts,
                             plan="serve.decode_step:8:hang",
                             new_tokens=12)
    assert toks == base
    assert all(r.state == RequestState.DONE for r in reqs)
    assert [(f.site, f.kind) for f in health.fault_log()] == [
        ("serve.decode_step", "hang-timeout")]
    # no demotion, no retry — a hang is a straggler, not a failure
    assert eng.monitor.report()["events"].get("demotion", 0) == 0


def test_retries_exhausted_marks_requests_failed(served):
    params, prompts = served
    policy = health.DegradationPolicy(max_retries=2, backoff_base_s=0.001)
    eng, reqs, _ = _serve(params, prompts,
                          plan="serve.decode_step:*:raise", policy=policy)
    assert all(r.state == RequestState.FAILED for r in reqs)
    assert all("injected failure" in r.error for r in reqs)
    st = eng.stats()
    assert st["failed"] == 2 and st["retries"] == 2


def test_failed_steps_release_their_arguments(served):
    """A failed step's arguments are freed with the step, not kept by
    the error's traceback until a garbage collection: on the chip those
    frames held whole page pools, and failing decode steps ran the
    device out of memory."""
    params, prompts = served
    os.environ["REPRO_FAULT_PLAN"] = "serve.decode_step:*:raise"
    health.reset_faults()
    eng = Engine(CFG, params, max_len=MAX_LEN,
                 policy=health.DegradationPolicy(backoff_base_s=0.001))
    eng.drain()                          # builds the scheduler and pools
    reqs = [eng.submit(p, NEW_TOKENS) for p in prompts]
    gc.disable()
    try:
        before = len(jax.live_arrays())
        eng.drain()
        after = len(jax.live_arrays())
    finally:
        gc.enable()
    assert all(r.state == RequestState.FAILED for r in reqs)
    assert eng.stats()["demotions"] > 0
    assert after <= before


@pytest.mark.parametrize("loop", ["serve", "drain"])
def test_padded_vocab_mask_is_not_a_fault(loop):
    """The decode step masks the embedding's padding rows past
    ``vocab_size`` to -inf; the step validator checks only the real
    vocabulary, so such a config serves with no demotion (qwen3-1.7b's
    151936 rows pad to 152064)."""
    cfg = dataclasses.replace(CFG, vocab_size=500)
    assert cfg.padded_vocab > cfg.vocab_size
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    eng = Engine(cfg, params, max_len=MAX_LEN)
    reqs = [eng.submit(p, NEW_TOKENS) for p in prompts]
    eng.serve(reqs) if loop == "serve" else eng.drain()
    assert all(r.state == RequestState.DONE for r in reqs)
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out_tokens)
    st = eng.stats()
    assert st["demotions"] == st["retries"] == st["degraded_steps"] == 0


def test_generate_raises_on_failed_batch(served):
    params, prompts = served
    os.environ["REPRO_FAULT_PLAN"] = "serve.prefill:*:raise"
    eng = Engine(CFG, params, max_len=MAX_LEN,
                 policy=health.DegradationPolicy(backoff_base_s=0.001))
    with pytest.raises(StepFailed):
        eng.generate(prompts, NEW_TOKENS)


def test_degradation_cooldown_reprobes_primary(served):
    params, prompts = served
    policy = health.DegradationPolicy(cooldown_steps=2,
                                      backoff_base_s=0.001)
    eng, reqs, toks = _serve(params, prompts,
                             plan="serve.decode_step:1:raise",
                             policy=policy, new_tokens=8)
    _, _, base = _serve(params, prompts, new_tokens=8)
    assert toks == base
    assert all(r.state == RequestState.DONE for r in reqs)
    # demoted at decode step 2, degraded through cooldown, then a
    # healthy re-probe promotes back to the primary path
    assert policy.probes >= 1 and not policy.demoted
    kinds = [e.kind for e in eng.monitor.events]
    assert "probe" in kinds
    assert eng.stats()["degraded_steps"] >= 1


# ---------------------------------------------------------------------------
# Request lifecycle: validation, admission, deadlines, budgets.
# ---------------------------------------------------------------------------
def test_submit_validation_errors(served):
    params, _ = served
    eng = Engine(CFG, params, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.array([], np.int32), 4)
    with pytest.raises(ValueError, match="leaves no decode room"):
        eng.submit(np.zeros(MAX_LEN, np.int32), 4)
    with pytest.raises(ValueError, match="dtype must be integer"):
        eng.submit(np.ones(8, np.float32), 4)
    with pytest.raises(ValueError, match="rank-1"):
        eng.submit(np.zeros((2, 8), np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(8, np.int32), 0)
    st = eng.stats()
    assert st["rejected"] == 5 and st["admitted"] == 0
    assert st["health"]["events"]["admission-reject"] == 5


def test_vmem_admission_control(served):
    params, prompts = served
    tiny = dataclasses.replace(cost_model.V5E, vmem_bytes=1024,
                               name="tiny-vmem")
    eng = Engine(CFG, params, max_len=MAX_LEN, hw=tiny)
    with pytest.raises(AdmissionError, match="VMEM-feasible"):
        eng.submit(prompts[0], 4)
    # AdmissionError is a ValueError: callers can catch either
    assert issubclass(AdmissionError, ValueError)
    eng2 = Engine(CFG, params, max_len=MAX_LEN)
    req = eng2.submit(prompts[0], 4)
    assert req.state == RequestState.QUEUED


def test_budget_clamped_to_cache_capacity(served):
    params, prompts = served
    eng = Engine(CFG, params, max_len=MAX_LEN)
    req = eng.submit(prompts[0], 10_000)
    assert req.max_new_tokens == MAX_LEN - len(prompts[0])
    assert eng.stats()["budget_clamped"] == 1
    assert eng.monitor.events_of("backpressure")


def test_deadline_evicts_instead_of_stalling(served):
    params, prompts = served
    eng, reqs, _ = _serve(params, prompts, deadline_s=0.0)
    assert all(r.state == RequestState.EVICTED for r in reqs)
    assert all("deadline" in r.error for r in reqs)
    st = eng.stats()
    assert st["evicted"] == 2 and st["completed"] == 0
    assert eng.monitor.events_of("evicted")


def test_mixed_length_batch_served_continuously(served):
    # PR 8: mixed prompt lengths no longer raise — serve() routes the
    # ragged batch through the continuous scheduler (per-row banding)
    params, prompts = served
    eng = Engine(CFG, params, max_len=MAX_LEN)
    r1 = eng.submit(np.zeros(8, np.int32), 2)
    r2 = eng.submit(np.zeros(9, np.int32), 2)
    eng.serve([r1, r2])
    assert r1.state == RequestState.DONE and r2.state == RequestState.DONE
    assert len(r1.out_tokens) == 2 and len(r2.out_tokens) == 2
    assert eng.scheduler_report()["max_batch"] >= 1


# ---------------------------------------------------------------------------
# Autotune-cache corruption recovery.
# ---------------------------------------------------------------------------
@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.clear()
    autotune.reset_stats()
    yield path
    autotune.clear()
    autotune.reset_stats()


def _seed_cache(n=2):
    probs = [GemmProblem(m=128 * (i + 1), k=128, n=128) for i in range(n)]
    specs = [autotune.best_spec(p) for p in probs]
    autotune._save_disk()
    return probs, specs


def test_garbage_cache_file_quarantined_not_fatal(cache_file):
    with open(cache_file, "w") as f:
        f.write("{ truncated garbage !!!")
    autotune._load_disk()           # must not raise
    st = autotune.stats()
    assert st["files_quarantined"] == 1
    assert glob.glob(cache_file + ".corrupt-*")
    assert not os.path.exists(cache_file)
    # serving-path lookups still work after quarantine
    assert autotune.best_spec(GemmProblem(m=128, k=128, n=128)) is not None


def test_partially_corrupt_cache_keeps_good_entries(cache_file):
    probs, specs = _seed_cache(2)
    with open(cache_file) as f:
        d = json.load(f)
    keys = sorted(d["entries"])
    d["entries"][keys[0]] = {"spec": "not-a-dict", "sum": 0}
    with open(cache_file, "w") as f:
        json.dump(d, f)
    autotune.clear()
    autotune.reset_stats()
    autotune._load_disk()
    st = autotune.stats()
    assert st["entries_loaded"] == 1
    assert st["entries_skipped"] == 1
    # the surviving entry round-trips to the same spec
    loaded = [autotune.best_spec(p) for p in probs]
    assert specs[0] in loaded or specs[1] in loaded


def test_checksum_mismatch_skipped(cache_file):
    _seed_cache(1)
    with open(cache_file) as f:
        d = json.load(f)
    (k0,) = d["entries"]
    d["entries"][k0]["sum"] = 123456789
    with open(cache_file, "w") as f:
        json.dump(d, f)
    autotune.clear()
    autotune.reset_stats()
    autotune._load_disk()
    st = autotune.stats()
    assert st["entries_loaded"] == 0 and st["entries_skipped"] == 1


def test_midwrite_kill_leaves_original_intact(cache_file):
    _seed_cache(1)
    before = open(cache_file).read()
    os.environ["REPRO_FAULT_PLAN"] = "autotune.save:0:raise"
    health.reset_faults()
    autotune._save_disk()           # injected kill; must not raise
    assert open(cache_file).read() == before
    assert autotune.stats()["save_errors"] == 1
    assert not glob.glob(os.path.join(os.path.dirname(cache_file), "*.tmp"))
    os.environ.pop("REPRO_FAULT_PLAN")
    # next save (fault disarmed) goes through atomically
    autotune.best_spec(GemmProblem(m=384, k=128, n=128))
    autotune._save_disk()
    with open(cache_file) as f:
        assert len(json.load(f)["entries"]) == 2


def test_load_fault_degrades_to_empty_cache(cache_file):
    _seed_cache(1)
    os.environ["REPRO_FAULT_PLAN"] = "autotune.load:0:raise"
    health.reset_faults()
    autotune.clear()
    autotune.reset_stats()
    autotune._load_disk()           # must not raise
    st = autotune.stats()
    assert st["load_errors"] == 1 and st["entries_loaded"] == 0
    # a failed load latches (no per-lookup retries against a broken
    # disk); the file is untouched, so clear() + reload recovers it
    os.environ.pop("REPRO_FAULT_PLAN")
    autotune.clear()
    autotune.reset_stats()
    autotune._load_disk()
    assert autotune.stats()["entries_loaded"] == 1
