"""The program's own spans in a run's window, and where the device's idle
time falls among them.

The program records its spans (``repro.runtime.trace``) in memory on the
host's monotonic clock, the clock of ``RunView.t0``/``t1`` and of each
``Step``.  ``in_window`` returns the window's spans, or None when the
ring lost one of them, when they and the run's steps disagree on how
many ticks there were, or when the program records no spans at all.
For a traced run ``on_trace_clock`` moves them onto the profiler's clock
by one offset: the median, over the window's steps, of the start of the
harness's ``bench.step`` span in the trace less that step's ``begin``.
"""
from __future__ import annotations

import bisect
import collections
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import tracing

TICK = "serve.step"
ROOTS = ("serve.step", "serve.submit")     # the program's entry points
OUTSIDE = "outside any span"

Interval = Tuple[int, int]


class Mapped(NamedTuple):
    """A span on the trace's clock (ns)."""
    name: str
    start: int
    end: int


def _ring():
    try:
        from repro.runtime import trace
    except ImportError:          # a program that records no spans
        return None
    return trace


def window_ns(run) -> Interval:
    return int(run.t0 * 1e9), int(run.t1 * 1e9)


def in_window(run) -> Optional[list]:
    """The program's spans that overlap the window, by start time."""
    ring = _ring()
    if ring is None:
        return None
    lo, hi = window_ns(run)
    if not ring.complete_since(lo):
        return None
    spans = ring.spans(lo, hi)
    ticks = sum(1 for s in spans if s.name == TICK and s.start_ns >= lo)
    return spans if ticks == len(run.steps) else None


def offset_ns(run) -> Optional[int]:
    """Trace clock less monotonic clock, from the window's steps."""
    trace = run.trace
    if trace is None or not run.steps:
        return None
    lo, hi = trace.window
    starts = sorted(e.start for e in trace.spans
                    if e.name == "bench.step" and lo <= e.start < hi)
    if len(starts) != len(run.steps):
        return None
    return int(statistics.median(
        t - round(s.begin * 1e9) for t, s in zip(starts, run.steps)))


def on_trace_clock(run) -> Optional[List[Mapped]]:
    spans = in_window(run)
    off = offset_ns(run)
    if spans is None or off is None:
        return None
    return [Mapped(s.name, s.start_ns + off, s.end_ns + off)
            for s in spans]


def starting_in_window(run, spans, name: str) -> list:
    lo, hi = window_ns(run)
    return [s for s in spans if s.name == name and lo <= s.start_ns < hi]


def children(parents: Sequence, spans: Sequence, names: Sequence[str]
             ) -> List[list]:
    """For each parent, the spans named ``names`` whose parent it is
    and that lie inside it (``spans`` by start time)."""
    kids = [s for s in spans if s.name in names]
    starts = [s.start_ns for s in kids]
    out = []
    for p in parents:
        i = bisect.bisect_left(starts, p.start_ns)
        j = bisect.bisect_right(starts, p.end_ns)
        out.append([k for k in kids[i:j] if k.parent == p.name
                    and k.end_ns <= p.end_ns])
    return out


# -- device idle time ------------------------------------------------------
def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        elif t > s:
            out.append((s, t))
    return out


def idle_intervals(trace: tracing.Trace, chip: int) -> List[Interval]:
    """The window less the union of the device's busy intervals."""
    lo, hi = trace.window
    out, at = [], lo
    for s, t in trace.busy_intervals(chip):
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            total += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(run, idle: Optional[Sequence[Interval]] = None
                 ) -> Optional[Dict[str, float]]:
    """Device-idle seconds of the window on chip 0 (or of the ``idle``
    intervals given) under the innermost span open on the host: the
    program's spans, else the harness's, else ``OUTSIDE``."""
    mapped = on_trace_clock(run)
    if mapped is None:
        return None
    named = mapped + [Mapped(e.name, e.start, e.end) for e in run.trace.spans
                      if e.name != tracing.WINDOW_SPAN]
    if idle is None:
        idle = idle_intervals(run.trace, 0)
    events = [(s, 1, i) for i, (_, s, _) in enumerate(named)]
    events += [(t, 0, i) for i, (_, _, t) in enumerate(named)]
    events += [(s, 1, -1) for s, _ in idle] + [(t, 0, -1) for _, t in idle]
    events.sort()
    out: Dict[str, int] = collections.Counter()
    stack: List[int] = []
    idle_open, prev = 0, None
    for t, starts, i in events:
        if idle_open and prev is not None and t > prev:
            out[named[stack[-1]][0] if stack else OUTSIDE] += t - prev
        prev = t
        if i < 0:
            idle_open += 1 if starts else -1
        elif starts:
            stack.append(i)
        elif i in stack:
            del stack[len(stack) - 1 - stack[::-1].index(i)]
    return {k: v / 1e9
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
