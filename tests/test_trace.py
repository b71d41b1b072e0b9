"""runtime/trace.py: spans in a ring on the monotonic clock and in the
profiler's trace, the compile and gc sources, and the span tree one
continuous-engine tick records."""
import gc
import glob
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import autotune
from repro.models import lm
from repro.runtime import trace
from repro.serve.engine import Engine, RequestState
from repro.serve.scheduler import SchedulerConfig

CFG = configs.get_smoke("qwen3-1.7b")
MAX_LEN = 48
DECODE_CHILDREN = ("serve.prepare", "serve.wait", "serve.validate",
                   "serve.fetch", "serve.emit")


@pytest.fixture(scope="module")
def params():
    return lm.init_model(CFG, jax.random.PRNGKey(0))


def _engine(params):
    return Engine(CFG, params, max_len=MAX_LEN, scheduler_config=(
        SchedulerConfig(max_batch=2, page_size=16, prefix_reuse=False)))


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=int(s)).astype(np.int32)
            for s in rng.integers(5, 20, size=n)]


def _inside(outer, spans):
    return [s for s in spans if s is not outer
            and outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns]


def test_spans_nest_with_parent_and_attrs():
    since = time.monotonic_ns()
    with trace.span("t.outer", rid=3):
        with trace.span("t.inner", rid=3) as sp:
            sp.set(pages=2)
    got = {s.name: s for s in trace.spans(since) if s.name.startswith("t.")}
    outer, inner = got["t.outer"], got["t.inner"]
    assert outer.parent is None and inner.parent == "t.outer"
    assert outer.attrs == {"rid": 3} and inner.attrs == {"rid": 3,
                                                         "pages": 2}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert sp.seconds == (inner.end_ns - inner.start_ns) / 1e9
    # a span that raises still closes, and its parent is restored
    with pytest.raises(KeyError):
        with trace.span("t.raises"):
            raise KeyError("x")
    with trace.span("t.after"):
        pass
    after = [s for s in trace.spans(since) if s.name == "t.after"]
    assert after[0].parent is None


def test_ring_holds_its_capacity_and_counts_drops(monkeypatch):
    ring = trace.Ring(capacity=4)
    for k in range(1, 7):
        ring.append(("x", None, {}, 10 * k - 5, 10 * k))
    assert [s.end_ns for s in ring.spans()] == [30, 40, 50, 60]
    assert ring.dropped == 2 and ring.lost_until_ns == 20
    assert ring.complete_since(20) and not ring.complete_since(19)
    assert [s.end_ns for s in ring.spans(35, 50)] == [40, 50]
    # spans go to the process's ring, whatever it holds
    monkeypatch.setattr(trace, "RING", trace.Ring(capacity=3))
    for _ in range(5):
        with trace.span("t.x"):
            pass
    assert len(trace.spans()) == 3 and trace.RING.dropped == 2
    assert trace.report()["spans_dropped"] == 2
    summary = trace.summary()["t.x"]
    assert summary["count"] == 3
    assert 0 <= summary["p50_s"] <= summary["p99_s"] <= summary["total_s"]


def _triple_plus_one(x):
    return x * 3 + 1


def test_one_compile_span_on_a_new_program_and_none_on_a_repeat():
    x = jnp.ones(7)
    x.block_until_ready()
    f = jax.jit(_triple_plus_one)
    before = trace.RING.compiles
    since = time.monotonic_ns()
    with trace.span("t.call"):
        f(x).block_until_ready()
    mine = [s for s in trace.spans(since) if s.name == "jax.compile"
            and "triple_plus_one" in s.attrs["program"]]
    assert len(mine) == 1 and mine[0].parent == "t.call"
    assert trace.RING.compiles > before
    since = time.monotonic_ns()
    f(x).block_until_ready()
    assert not [s for s in trace.spans(since) if s.name == "jax.compile"]


def test_a_forced_collection_is_a_gc_span():
    before = trace.RING.collections
    since = time.monotonic_ns()
    with trace.span("t.collect"):
        gc.collect()
    mine = [s for s in trace.spans(since) if s.name == "py.gc"]
    assert mine and mine[-1].attrs == {"gen": 2}
    assert mine[-1].parent == "t.collect"
    assert trace.RING.collections > before


def test_autotune_warm_span_only_for_a_new_key(params, monkeypatch):
    import dataclasses

    eng = Engine(dataclasses.replace(CFG, use_pallas_kernels=True), params,
                 max_len=MAX_LEN)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(autotune, "warm", lambda problems: None)
    since = time.monotonic_ns()
    eng._warm_autotune(1, 12)
    eng._warm_autotune(1, 12)
    warm = [s for s in trace.spans(since) if s.name == "serve.autotune_warm"]
    assert len(warm) == 1 and warm[0].attrs == {"seq": 12}


def test_every_tick_records_the_span_tree(params):
    eng = _engine(params)
    since = time.monotonic_ns()
    handles = [eng.submit(p, 3) for p in _prompts(3)]
    ticks = 0
    while not all(h.state in (RequestState.DONE, RequestState.FAILED,
                              RequestState.EVICTED) for h in handles):
        eng.step()
        ticks += 1
    assert all(h.state == RequestState.DONE for h in handles)
    spans = trace.spans(since)
    submits = [s for s in spans if s.name == "serve.submit"]
    assert [s.attrs["rid"] for s in submits] == [h.rid for h in handles]
    assert all(s.parent is None for s in submits)
    checks = [s for s in spans if s.name == "serve.admission_check"]
    assert checks and all(s.parent == "serve.submit" for s in checks)
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == ticks
    assert [s.attrs["step_num"] for s in steps] == list(
        range(steps[0].attrs["step_num"], steps[0].attrs["step_num"] + ticks))
    decoded, admitted = 0, {}
    for step in steps:
        under = _inside(step, spans)
        decode = [s for s in under if s.name == "serve.decode"]
        assert len(decode) == 1 and decode[0].parent == "serve.step"
        if decode[0].attrs["rows"]:
            decoded += 1
            names = [s.name for s in _inside(decode[0], under)
                     if s.parent == "serve.decode"
                     and s.name.startswith("serve.")]
            assert sorted(names) == sorted(DECODE_CHILDREN), names
        for admit in (s for s in under if s.name == "serve.admit"):
            assert admit.parent == "serve.step"
            rid = admit.attrs["rid"]
            inner = {s.name: s for s in _inside(admit, under)
                     if s.parent == "serve.admit"}
            assert {"serve.prefill", "serve.store", "serve.emit"} <= set(inner)
            assert inner["serve.prefill"].attrs["rid"] == rid
            assert inner["serve.store"].attrs["rid"] == rid
            assert inner["serve.store"].attrs["pages"] >= 1
            assert inner["serve.prefill"].attrs["plen"] == len(
                handles[rid - handles[0].rid].prompt)
            admitted[rid] = admit
    assert sorted(admitted) == [h.rid for h in handles]
    # the health monitor's steps are the decode spans that decoded rows
    assert eng.monitor.report()["steps"] == decoded
    stats = eng.stats()["trace"]
    assert stats["summary"]["serve.step"]["count"] >= ticks
    assert set(stats) >= {"summary", "spans_dropped", "compiles",
                          "compile_s"}


def test_admission_time_survives_a_request_done_in_one_tick(params):
    eng = _engine(params)
    h = eng.submit(_prompts(1, seed=4)[0], 1)
    before = time.monotonic()
    eng.step()
    assert h.state == RequestState.DONE and len(h.out_tokens) == 1
    assert eng._scheduler.t_start.get(h.rid) is None     # popped on finish
    assert h.admitted_s is not None and before <= h.admitted_s
    assert h.admitted_s <= time.monotonic()


def test_ring_and_profiler_agree_after_one_offset(params, tmp_path):
    eng = _engine(params)
    prompts = _prompts(2, seed=1)
    eng.submit(prompts[0], 2)
    eng.step()                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    lo = time.monotonic_ns()
    try:
        for p in prompts:
            eng.submit(p, 3)
        for _ in range(5):
            eng.step()
    finally:
        hi = time.monotonic_ns()
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    traced = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("serve."):
                start = int(ev.start_ns)
                traced.setdefault(ev.name, []).append(
                    (start, start + int(ev.duration_ns)))
    ring = {}
    for s in trace.spans(lo, hi):
        if s.name.startswith("serve.") and lo <= s.start_ns \
                and s.end_ns <= hi:
            ring.setdefault(s.name, []).append((s.start_ns, s.end_ns))
    assert set(traced) == set(ring) >= {"serve.step", "serve.decode",
                                        "serve.submit", "serve.admit"}
    offset = statistics.median(
        t[0] - r[0] for t, r in zip(sorted(traced["serve.step"]),
                                    sorted(ring["serve.step"])))
    for name in ring:
        assert len(traced[name]) == len(ring[name]), name
        for t, r in zip(sorted(traced[name]), sorted(ring[name])):
            assert abs(t[0] - (r[0] + offset)) < 50_000, name
            assert abs(t[1] - (r[1] + offset)) < 50_000, name
