"""Cost of one program span (``repro.runtime.trace``) with the profiler
off: 10^5 spans, best of 7 passes, on the CPU that runs this.

us_per_call = microseconds per span; derived = the share (%) of one
373 ms decode tick (qwen3-1.7b chat_backlog on one TPU v5e, the
benchmark's ledger) that the spans of one tick take.
"""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.runtime import trace

N = 100_000
TICK_S = 0.373
SPANS_PER_TICK = 8       # a decode tick: step, decode and its five parts,
#                          plus an admission every tenth tick or so


def _per_span_us(attrs: bool) -> float:
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        if attrs:
            for i in range(N):
                with trace.span("bench.span", rid=i):
                    pass
        else:
            for _ in range(N):
                with trace.span("bench.span"):
                    pass
        best = min(best, (time.perf_counter() - t0) / N * 1e6)
    return best


def run() -> None:
    for attrs in (False, True):
        us = _per_span_us(attrs)
        share = 100.0 * SPANS_PER_TICK * us * 1e-6 / TICK_S
        emit(f"trace/span{'_rid' if attrs else ''}_profiler_off", us,
             f"{share:.5f}")
