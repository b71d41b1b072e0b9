"""Memory-pressure drills (PR 10): spill, preempt, backpressure.

The page pool is the continuous path's real decode datapath, so a pool
sized to roughly *half* the batch's aggregate KV working set forces the
pressure ladder — watermark admission deferral, host spill of cold
requests, preemption with deterministic recompute — and the contract
is that none of it changes a single emitted token: the constrained run
must match the unconstrained run bit-for-bit with zero FAILED requests.

The subprocess drill SIGKILLs the engine *mid-spill* (the
``pool.spill`` kill site) and asserts the PR-7 journal recovers every
request with nothing lost and nothing duplicated — spilling is
journal-invisible by design, so cold replay re-prefills and never needs
the half-written host buffers.

Run standalone (the pressure-drill CI job):

    PYTHONPATH=src python -m pytest -x -q tests/test_pressure.py
"""
import json
import os
import subprocess
import sys
import textwrap
import time
import types

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs
from repro.models import lm
from repro.runtime import health
from repro.serve.engine import Engine, RequestState
from repro.serve.journal import RequestJournal
from repro.serve.paged_cache import PagedKVCache, pages_for
from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = configs.get_smoke("qwen3-1.7b")
MAX_LEN = 48
NEW_TOKENS = 20                 # long decodes: rows grow while coresident
LENS = [7, 12, 2, 23]
PAGE = 8
# aggregate working set: pages_for(len + NEW_TOKENS, PAGE) per request
# = 4 + 4 + 3 + 6 = 17 pages; the constrained pool holds ~a third, so
# decode-time page growth must collide while requests are coresident —
# watermark deferral alone cannot serve it, the ladder has to fire.
# 6 pages = the largest single reach: every request is individually
# feasible (anything smaller is rejected rather than livelocked)
TINY_POOL = 6
BIG_POOL = 24                   # > the full working set: no pressure


@pytest.fixture(scope="module")
def eng():
    params = lm.init_model(CFG, jax.random.PRNGKey(0))
    return Engine(CFG, params, max_len=MAX_LEN)


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in LENS]


def _drain(eng, n_pages, prompts=None, new_tokens=NEW_TOKENS, **sckw):
    reqs = [eng.submit(p, new_tokens) for p in prompts or _prompts()]
    sched = ContinuousScheduler(eng, SchedulerConfig(
        max_batch=4, page_size=PAGE, n_pages=n_pages, **sckw))
    for r in reqs:
        sched.enqueue(r)
    sched.drain()
    eng._check_replay(reqs)
    return reqs, sched


# ---------------------------------------------------------------------------
# The in-process pressure drill: half the working set, identical tokens.
# ---------------------------------------------------------------------------
def test_pressure_drill_bit_identical_tokens(eng):
    base_reqs, base_sched = _drain(eng, BIG_POOL)
    assert all(r.state == RequestState.DONE for r in base_reqs)
    assert base_sched.use_paged           # the pool is the datapath

    before = dict(eng._counters)
    tiny_reqs, tiny_sched = _drain(eng, TINY_POOL)
    delta = {k: eng._counters[k] - before[k] for k in eng._counters}

    assert all(r.state == RequestState.DONE for r in tiny_reqs), [
        (r.rid, r.state, r.error) for r in tiny_reqs]
    assert delta["failed"] == 0
    for b, t in zip(base_reqs, tiny_reqs):
        assert t.out_tokens == b.out_tokens, (t.rid, t.out_tokens,
                                              b.out_tokens)
    # half the working set cannot be served without the ladder firing
    assert delta["spills"] + delta["preemptions"] > 0, delta
    assert delta["replay_divergence"] == 0, delta
    rep = tiny_sched.report()
    assert rep["paged_decode"] is True
    for key in ("occupancy", "above_high", "below_low", "spills"):
        assert key in rep["pages"], rep


def test_stats_surface_pressure_counters(eng):
    stats = eng.stats()
    for key in ("spills", "spilled_pages", "unspills", "preemptions",
                "backpressure"):
        assert key in stats, sorted(stats)


# ---------------------------------------------------------------------------
# Watermark backpressure: queued-with-reason, never silent.
# ---------------------------------------------------------------------------
def test_watermark_defers_admission_with_reason(eng):
    rng = np.random.default_rng(1)
    big = rng.integers(0, CFG.vocab_size, (30,)).astype(np.int32)
    small = rng.integers(0, CFG.vocab_size, (4,)).astype(np.int32)
    r1 = eng.submit(big, 10)              # reach 40 -> all 5 pages
    r2 = eng.submit(small, 2)
    before = eng._counters["backpressure"]
    sched = ContinuousScheduler(eng, SchedulerConfig(
        max_batch=4, page_size=PAGE, n_pages=5))
    sched.enqueue(r1)
    for _ in range(6):                    # decode until growth fills pool
        sched.step()
        if sched.paged.above_high():
            break
    assert sched.paged.above_high()
    assert r1.state == RequestState.DECODING
    sched.enqueue(r2)
    sched.step()
    assert r2.state == RequestState.QUEUED
    assert r2.queue_reason is not None
    assert "watermark" in r2.queue_reason
    assert eng._counters["backpressure"] == before + 1
    sched.drain()                         # r1 finishes -> pages free -> r2
    assert r1.state == RequestState.DONE
    assert r2.state == RequestState.DONE
    assert r2.queue_reason is None        # cleared at admission


def test_oversized_prompt_fails_loudly_when_pool_is_empty(eng):
    rng = np.random.default_rng(2)
    big = rng.integers(0, CFG.vocab_size, (40,)).astype(np.int32)
    req = eng.submit(big, 2)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        max_batch=4, page_size=PAGE, n_pages=2))
    sched.enqueue(req)
    sched.drain()
    assert req.state == RequestState.FAILED
    assert "page pool cannot hold" in req.error


# ---------------------------------------------------------------------------
# Spill tier unit: bit-exact round trip, shared pages pinned.
# ---------------------------------------------------------------------------
def _mk_pool(n_pages=8, ps=4):
    cfg = types.SimpleNamespace(n_layers=2, n_kv_heads=2, d_head=4,
                                kv_cache_dtype="auto")
    return PagedKVCache(cfg, n_pages, ps, dtype="float32")


def test_spill_unspill_round_trip_bit_exact():
    pool = _mk_pool()
    pages = pool.alloc(3)
    rng = np.random.default_rng(3)
    payload_k = rng.standard_normal((2, 2, 3, 4, 4)).astype(np.float32)
    payload_v = rng.standard_normal((2, 2, 3, 4, 4)).astype(np.float32)
    import jax.numpy as jnp
    idx = jnp.asarray(pages, jnp.int32)
    pool.k_pages = pool.k_pages.at[:, :, idx].set(payload_k)
    pool.v_pages = pool.v_pages.at[:, :, idx].set(payload_v)
    pool.refs[pages[1]] += 1              # pages[1] shared with another req

    free_before = pool.free_pages
    entries = pool.spill(pages)
    assert [e[0] for e in entries] == ["host", "resident", "host"]
    assert entries[1][1] == pages[1]      # pinned in place
    assert pool.refs[pages[1]] == 2       # the spiller keeps its ref
    assert pool.free_pages == free_before + 2
    assert pool.stats["spilled_pages"] == 2

    back = pool.unspill(entries)
    assert back is not None and len(back) == 3
    assert back[1] == pages[1]
    got_k = np.asarray(pool.k_pages[:, :, jnp.asarray(back, jnp.int32)])
    got_v = np.asarray(pool.v_pages[:, :, jnp.asarray(back, jnp.int32)])
    np.testing.assert_array_equal(got_k[:, :, [0, 2]],
                                  payload_k[:, :, [0, 2]])
    np.testing.assert_array_equal(got_v[:, :, [0, 2]],
                                  payload_v[:, :, [0, 2]])


def test_unspill_returns_none_when_pool_full_entries_untouched():
    pool = _mk_pool(n_pages=4)
    pages = pool.alloc(2)
    entries = pool.spill(pages)
    pool.alloc(4)                         # exhaust the pool
    assert pool.unspill(entries) is None
    assert len(entries) == 2              # retryable later


# ---------------------------------------------------------------------------
# Refcount underflow: counted, fatal under REPRO_STRICT_POOL=1.
# ---------------------------------------------------------------------------
def test_release_underflow_counted_not_fatal_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_STRICT_POOL", raising=False)
    pool = _mk_pool()
    pages = pool.alloc(1)
    pool.release(pages)
    pool.release(pages)                   # double free
    assert pool.stats["ref_underflows"] == 1
    assert pool.free_pages == pool.n_pages


def test_release_underflow_raises_under_strict_pool(monkeypatch):
    monkeypatch.setenv("REPRO_STRICT_POOL", "1")
    pool = _mk_pool()
    pages = pool.alloc(1)
    pool.release(pages)
    with pytest.raises(RuntimeError, match="double free"):
        pool.release(pages)


def test_pool_alloc_fault_site_is_a_simulated_oom(monkeypatch):
    monkeypatch.setenv("REPRO_FAULT_PLAN", "pool.alloc:0:raise")
    health.reset_faults()
    pool = _mk_pool()
    assert pool.alloc(1) is None          # injected OOM, absorbed
    assert pool.stats["oom_rejects"] == 1
    assert pool.alloc(1) is not None      # next hit is clean


# ---------------------------------------------------------------------------
# Property: the pool conserves pages under any op sequence.
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 3), min_size=1, max_size=50))
def test_pool_conserves_pages_under_any_op_sequence(ops):
    import jax.numpy as jnp
    pool = _mk_pool(n_pages=8, ps=4)
    ps = pool.page_size
    prompt = list(range(2 * ps + 1))      # 2 full pages + partial tail
    k_row = jnp.zeros((2, 2, len(prompt), 4))
    v_row = jnp.zeros((2, 2, len(prompt), 4))
    holders = []                          # page lists we own one ref on
    for op in ops:
        if op == 0:
            got = pool.alloc(1)
            if got is not None:
                holders.append(got)
        elif op == 1:
            if holders:
                pool.release(holders.pop(0))
        elif op == 2:
            reuse, covered = pool.lookup_prefix(prompt)
            new = pool.alloc(pages_for(len(prompt), ps) - len(reuse))
            if new is None:
                pool.release(reuse)
            else:
                pages = reuse + new
                pool.store(prompt, pages, covered, k_row, v_row)
                holders.append(pages)
        elif op == 3:
            reuse, _ = pool.lookup_prefix(prompt)
            if reuse:
                holders.append(reuse)
        # invariant: free + live == total, after every single op
        live = int(np.sum(pool.refs > 0))
        assert pool.free_pages + live == pool.n_pages
        # prefix chain only references live pages, bijectively
        for pid, key in pool._page_key.items():
            assert pool.refs[pid] > 0
            assert pool._prefix.get(key) == pid
        assert len(pool._prefix) == len(pool._page_key)
    assert pool.stats["ref_underflows"] == 0


# ---------------------------------------------------------------------------
# Satellites: chunked-prefill deadlines, drain stall.
# ---------------------------------------------------------------------------
def test_chunked_prefill_checks_deadline_at_chunk_boundary(eng):
    rng = np.random.default_rng(4)
    long = rng.integers(0, CFG.vocab_size, (23,)).astype(np.int32)
    req = eng.submit(long, 2, deadline_s=0.0)
    before = eng._counters["evicted"]
    sched = ContinuousScheduler(eng, SchedulerConfig(
        max_batch=2, page_size=PAGE, n_pages=8, prefill_chunk=4))
    sched.enqueue(req)
    time.sleep(0.01)
    sched.drain()
    assert req.state == RequestState.EVICTED
    assert "chunked prefill" in req.error
    assert eng._counters["evicted"] == before + 1
    # the reserved pages were returned — nothing leaked
    assert sched.paged.free_pages == sched.paged.n_pages


def test_drain_stall_fails_stranded_requests_loudly(eng):
    rng = np.random.default_rng(5)
    req = eng.submit(rng.integers(0, CFG.vocab_size, (6,)).astype(
        np.int32), 2)
    sched = ContinuousScheduler(eng, SchedulerConfig(
        max_batch=2, page_size=PAGE, n_pages=8))
    sched.enqueue(req)
    sched._admit = lambda: False          # wedge the scheduler
    sched._decode = lambda: False
    before = len(eng.monitor.events_of("scheduler.stall"))
    sched.drain()
    assert req.state == RequestState.FAILED
    assert "stalled" in req.error
    assert len(eng.monitor.events_of("scheduler.stall")) == before + 1
    assert not sched.has_work             # nothing silently stranded


# ---------------------------------------------------------------------------
# SIGKILL mid-spill: journal recovery, zero lost, zero duplicated.
# ---------------------------------------------------------------------------
DRIVER = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine
    from repro.serve.scheduler import SchedulerConfig

    mode, jdir, out = sys.argv[1], sys.argv[2], sys.argv[3]
    cfg = configs.get_smoke("qwen3-1.7b")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    sc = SchedulerConfig(max_batch=4, page_size=%(page)d,
                         n_pages=%(pool)d)
    eng = Engine(cfg, params, max_len=%(max_len)d, journal_dir=jdir,
                 scheduler_config=sc)
    if mode == "resume":
        reqs = eng.restore()
        eng.serve(reqs)
    else:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in %(lens)r]
        reqs = [eng.submit(p, %(new_tokens)d) for p in prompts]
        eng.serve(reqs)           # ragged: continuous scheduler, tiny pool
    stats = {k: v for k, v in eng.stats().items() if isinstance(v, int)}
    json.dump({"tokens": {str(r.rid): list(r.out_tokens) for r in reqs},
               "states": {str(r.rid): r.state.value for r in reqs},
               "stats": stats}, open(out, "w"))
""" % {"max_len": MAX_LEN, "new_tokens": NEW_TOKENS, "lens": LENS,
       "page": PAGE, "pool": TINY_POOL})


def _run_driver(script, mode, jdir, out, plan=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("REPRO_FAULT_PLAN", None)
    if plan is not None:
        env["REPRO_FAULT_PLAN"] = plan
    return subprocess.run(
        [sys.executable, script, mode, str(jdir), str(out)],
        env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_sigkill_mid_spill_recovers_via_journal(tmp_path):
    script = tmp_path / "driver.py"
    script.write_text(DRIVER)

    # the clean constrained run: what recovery must reproduce
    out0 = tmp_path / "out0.json"
    proc = _run_driver(script, "run", tmp_path / "j0", out0)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    base = json.load(open(out0))
    assert all(s == "done" for s in base["states"].values()), base
    assert base["stats"]["spills"] + base["stats"]["preemptions"] > 0, \
        base["stats"]       # the tiny pool must actually exercise spill

    # SIGKILL at the first spill: no finally blocks, no flushes
    jdir = tmp_path / "journal"
    out1, out2 = tmp_path / "out1.json", tmp_path / "out2.json"
    proc = _run_driver(script, "run", jdir, out1, plan="pool.spill:0:kill")
    assert proc.returncode == -9, proc.stderr.decode()[-2000:]
    assert not out1.exists()

    recs = RequestJournal(str(jdir)).scan()
    owed = sorted(r["rid"] for r in recs if r["kind"] == "submit")
    assert owed == sorted(int(r) for r in base["tokens"])

    proc = _run_driver(script, "resume", jdir, out2)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    result = json.load(open(out2))
    got = {int(rid): toks for rid, toks in result["tokens"].items()}
    assert sorted(got) == owed, result    # zero lost, zero invented
    for rid in owed:
        assert result["states"][str(rid)] == "done", result
        assert got[rid] == base["tokens"][str(rid)], (rid, result)
    assert result["stats"]["failed"] == 0
    assert result["stats"]["replay_divergence"] == 0
