"""The trace reduction on a short trace recorded on the chip: qwen3-1.7b
under chat_backlog on one TPU v5e, a window of a few decode steps
(``data/chat_trace.xplane.pb``, 412 KB).  Each number is checked against a
computation of its own straight from the profiler's events."""
import json
from pathlib import Path

import jax
import pytest

from serve_bench_tiny import SERVE
import catalog
import run
import serving
import tracing
import traffic
import work

FIXTURE = Path(__file__).parent / "data" / "chat_trace.xplane.pb"
QWEN = json.loads((SERVE / "configs" / "qwen3-1.7b.json").read_text())


@pytest.fixture(scope="module")
def trace():
    return tracing.reduce(str(FIXTURE))


@pytest.fixture(scope="module")
def raw():
    """(device op events, module events, window) read directly."""
    data = jax.profiler.ProfileData.from_file(str(FIXTURE))
    dev = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {line.name: list(line.events) for line in dev.lines}
    host = next(p for p in data.planes if p.name == "/host:CPU")
    window = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
              for line in host.lines for e in line.events
              if e.name == "bench.window"]
    ops = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
           for e in lines["XLA Ops"]]
    mods = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in lines["XLA Modules"]]
    return ops, mods, window[0]


def test_the_window_and_the_programs(trace, raw):
    _, mods, window = raw
    assert trace.window == window
    assert len(trace.ops) == 1
    steps = [m for m in mods if m[0].startswith("jit__step(")]
    runs = tracing.program_runs(trace, tracing.DECODE_PROGRAM)
    assert len(runs) == len(steps) >= 1
    assert sum(runs) == pytest.approx(
        sum(e - s for _, s, e in steps) / 1e9, rel=1e-12)


def test_busy_time_is_the_union_of_op_intervals(trace, raw):
    ops, _, (lo, hi) = raw
    # a sweep over +1/-1 edges: busy wherever some op is running
    edges = sorted([(max(s, lo), 1) for _, s, e in ops if min(e, hi) > max(s, lo)]
                   + [(min(e, hi), -1) for _, s, e in ops
                      if min(e, hi) > max(s, lo)])
    busy, depth, since = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert trace.busy_s() == pytest.approx(busy / 1e9, rel=1e-12)
    assert 0 < trace.busy_s() < trace.window_s
    gaps = sum(s for _, s in trace.idle_gaps())
    assert gaps == pytest.approx(trace.window_s - trace.busy_s(), rel=1e-9)
    assert {name for name, _ in trace.idle_gaps()} <= {
        "bench.step", "bench.submit", "bench.poll", "outside bench spans"}


def test_kernel_time_inside_the_decode_programs(trace, raw):
    ops, mods, _ = raw
    steps = [(s, e) for name, s, e in mods if name.startswith("jit__step(")]
    want = sum(e - s for name, s, e in ops
               if name.startswith("%paged_attention.")
               and "tpu_custom_call" in name
               and any(a <= s < b for a, b in steps))
    got = tracing.kernel_seconds(trace, tracing.DECODE_PROGRAM,
                                 tracing.PAGED_ATTENTION_KERNEL)
    assert got == pytest.approx(want / 1e9, rel=1e-12) and got > 0
    assert tracing.kernel_seconds(trace, tracing.PREFILL_PROGRAM,
                                  tracing.MATMUL_KERNEL) == 0
    top = trace.op_seconds()[0]
    assert top[0] == "jit__step/paged_attention"
    assert all(not name.endswith("/while") for name, _ in trace.op_seconds())


def test_metric_readers_on_the_recorded_trace(trace):
    contexts = [400 + i for i in range(16)]
    steps = [serving.Step(0.0, 0.3, None, contexts, 0.2)
             for _ in tracing.program_runs(trace, tracing.DECODE_PROGRAM)]
    mix = traffic.load(catalog.traffic_file("chat_backlog"), "chat_backlog")
    view = run.RunView(QWEN, mix, work.peaks("TPU v5 lite"), [], steps,
                       0.0, trace.window_s, trace)

    def read(name):
        return catalog.metric_reader(name).read(view)

    runs = tracing.program_runs(trace, tracing.DECODE_PROGRAM)
    assert read("decode_step_ms.chat") == pytest.approx(
        1e3 * sum(runs) / len(runs))
    assert read("idle_share.chat") == pytest.approx(
        100 * (1 - trace.busy_s() / trace.window_s))
    flops, nbytes = work.paged_attention_call(QWEN, contexts)  # 28 layers
    least = len(steps) * max(flops / 197e12, nbytes / 819e9)
    kernel = tracing.kernel_seconds(trace, tracing.DECODE_PROGRAM,
                                    tracing.PAGED_ATTENTION_KERNEL)
    share = read("paged_attention_roofline.chat")
    assert share == pytest.approx(100 * least / kernel)
    assert 0 < share < 100
    assert read("pool_occupancy_max.chat") == pytest.approx(20.0)
    # no prefill in this window: the prefill readers find nothing
    assert read("prefill_ms_per_ktok.code") is None
    assert read("matmul_prefill_roofline.code") is None
