"""The readers of the program's spans on a synthetic run with a known
answer: a one-second window of two ticks, the program's spans in a ring
of their own, the harness's spans and the device's busy time on a trace
clock 99 s behind the monotonic clock (and 2 us behind each step's
``begin``)."""
import sys

import pytest

import serve_bench_tiny as tiny
import catalog
import program_spans
import run
import serving
import tracing

from repro import runtime
from repro.runtime import trace as ring_mod

MS = 1_000_000
LO = 100_000 * MS                    # the window starts at 100 s
OFF = -99_000 * MS + 2_000           # trace clock less monotonic clock
CHAT, CODE = "chat_backlog", "code_open"


def _spans():
    """(name, parent, attrs, start ms, end ms) on the monotonic clock,
    relative to the window's start."""
    out = [("serve.step", None, {}, -600, -300),       # before the window
           ("serve.store", "serve.admit", {}, -500, -490)]
    for k, (t, emit, store) in enumerate(((100, 20, 8), (500, 30, 12))):
        out += [
            ("serve.step", None, {"step_num": k}, t + .003, t + 300),
            ("serve.admit", "serve.step", {"rid": k}, t + 1, t + 40),
            ("serve.store", "serve.admit", {"rid": k}, t + 20, t + 20 + store),
            ("serve.decode", "serve.step", {"rows": 4}, t + 45, t + 296),
            ("serve.prepare", "serve.decode", {}, t + 45, t + 50),
            ("serve.wait", "serve.decode", {}, t + 50, t + 250),
            ("serve.validate", "serve.decode", {}, t + 250, t + 260),
            ("serve.fetch", "serve.decode", {}, t + 260, t + 265),
            ("serve.emit", "serve.decode", {"rows": 4}, t + 265,
             t + 265 + emit),
        ]
    out.append(("serve.submit", None, {"rid": 9}, 450, 460))
    return out


def _ring(capacity=1024):
    ring = ring_mod.Ring(capacity)
    for name, parent, attrs, s, t in sorted(_spans(), key=lambda e: e[4]):
        ring.append((name, parent, attrs, LO + int(s * MS), LO + int(t * MS)))
    return ring


def _trace():
    """Busy 150-350 and 550-750 ms of a 1000 ms window (trace clock)."""
    w0 = LO + OFF - 2_000

    def ev(name, s, t):
        return tracing.Event(name, w0 + int(s * MS), w0 + int(t * MS))

    ops = [ev("paged_attention", 150, 350), ev("paged_attention", 550, 750)]
    spans = [ev("bench.window", 0, 1000)] + [
        tracing.Event("bench.step", LO + int(t * MS) + OFF,
                      LO + int((t + 300.1) * MS) + OFF)
        for t in (100, 500)] + [ev("bench.submit", 449, 461)]
    return tracing.Trace(window=(w0, w0 + 1000 * MS), modules=[[]],
                         ops=[ops], spans=spans)


def _view(kind, steps=2, traced=True):
    begins = [100.1, 100.5, 100.9][:steps]
    return run.RunView(
        {}, tiny.mix(kind), {}, [],
        [serving.Step(b, b + 0.3, None, [], 0.0)
         for b in begins], 100.0, 101.0, _trace() if traced else None)


def _read(name, view):
    return catalog.metric_reader(name).read(view)


@pytest.fixture
def ring(monkeypatch):
    ring = _ring()
    monkeypatch.setattr(ring_mod, "RING", ring)
    return ring


def test_offset_and_spans_on_the_trace_clock(ring):
    view = _view(CHAT)
    assert program_spans.offset_ns(view) == OFF
    mapped = program_spans.on_trace_clock(view)
    assert len(mapped) == len(_spans()) - 2          # two before the window
    step = next(m for m in mapped if m.name == "serve.step")
    assert step.start == view.trace.spans[1].start + 3_000


@pytest.mark.parametrize("kind", [CHAT, CODE])
def test_host_idle_share(ring, kind):
    view = _view(kind)
    # idle inside the ticks: 50 ms at each end of each, less the 3 us
    # the tick starts after its begin, plus the 10 ms submit
    want = 100.0 * (4 * 50 - 2 * 0.003 + 10) / 1000
    share = _read(f"host_idle_share.{kind[:4]}", view)
    assert share == pytest.approx(want, abs=1e-6)
    assert share <= _read(f"idle_share.{kind[:4]}", view) == pytest.approx(60)


def test_idle_by_span(ring):
    by = program_spans.idle_by_span(_view(CHAT))
    # each tick's admission (39 ms) and store (8, 12 ms) fall in the
    # idle time before its decode; validate, fetch and emit after it
    assert by["serve.store"] == pytest.approx(0.020)
    assert by["serve.admit"] == pytest.approx(0.078 - 0.020)
    assert by["serve.validate"] == pytest.approx(0.020)
    assert by["serve.fetch"] == pytest.approx(0.010)
    assert by["serve.emit"] == pytest.approx(0.050)
    assert by["serve.submit"] == pytest.approx(0.010)
    assert by["bench.submit"] == pytest.approx(0.002)
    assert by["serve.wait"] < 1e-5
    assert sum(by.values()) == pytest.approx(0.6)


@pytest.mark.parametrize("kind", [CHAT, CODE])
def test_logits_host_ms_and_store_ms_per_admit(ring, kind):
    view = _view(kind)
    # ticks of 10 + 5 + 20 and 10 + 5 + 30 ms: median 40
    assert _read(f"logits_host_ms.{kind[:4]}", view) == pytest.approx(40.0)
    # stores of 8 and 12 ms in the window; the one before it is left out
    assert _read(f"store_ms_per_admit.{kind[:4]}", view) == pytest.approx(
        10.0)


NAMES = ["host_idle_share.chat", "host_idle_share.code",
         "logits_host_ms.chat", "logits_host_ms.code",
         "store_ms_per_admit.chat", "store_ms_per_admit.code"]


@pytest.mark.parametrize("name", NAMES)
def test_none_when_the_ring_lost_a_span_of_the_window(monkeypatch, name):
    full = _ring()
    # room for all but one span of the window: the oldest one goes
    monkeypatch.setattr(ring_mod, "RING", _ring(len(full.entries) - 3))
    assert not ring_mod.complete_since(LO)
    assert _read(name, _view(CHAT if "chat" in name else CODE)) is None
    # losing only spans from before the window is fine
    monkeypatch.setattr(ring_mod, "RING", _ring(len(full.entries) - 2))
    assert _read(name, _view(CHAT if "chat" in name else CODE)) is not None


@pytest.mark.parametrize("name", NAMES)
def test_none_when_ticks_and_steps_disagree(ring, name):
    assert _read(name, _view(CHAT if "chat" in name else CODE,
                             steps=3)) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_from_a_program_without_spans(monkeypatch, name):
    monkeypatch.setattr(ring_mod, "RING", _ring())
    monkeypatch.delattr(runtime, "trace")
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    assert _read(name, _view(CHAT if "chat" in name else CODE)) is None


def test_host_idle_share_needs_a_trace(ring):
    assert _read("host_idle_share.chat", _view(CHAT, traced=False)) is None
    assert _read("logits_host_ms.chat", _view(CHAT, traced=False)) == \
        pytest.approx(40.0)


def test_attribution_of_the_window(ring):
    import attribute

    got = attribute.attribute(_view(CHAT))
    assert got["idle_s"] == pytest.approx(0.6)
    assert [g["ms"] for g in got["longest_gaps"]] == pytest.approx(
        [250.0, 200.0, 150.0])
    last = got["longest_gaps"][0]["held_by_ms"]
    assert last["serve.emit"] == pytest.approx(30.0)
    assert sum(got["idle_share_by_span"].values()) == pytest.approx(60.0)
    assert got["spans"]["serve.step"]["count"] == 2
    assert got["ticks_over_1s"] == [] and got["gc_and_compiles"] == []
