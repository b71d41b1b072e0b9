"""Spans of the serving step, on the profiler's clock and in memory.

``span(name, **attrs)`` marks one piece of host work.  While the JAX
profiler runs, the span enters ``jax.profiler.TraceAnnotation`` so it
lands in the profiler's own trace, on the clock of the device's
operations; always, it appends ``(name, parent, attrs, start_ns,
end_ns)`` to a fixed-capacity ring on ``time.monotonic_ns()``, the clock
the engine's admission stamps and deadlines use.  With the profiler off
a span costs two clock reads and one append.  Spans are host-side only:
none is placed inside a jitted or traced function.

Two sources the program does not call feed the same ring:

  * ``jax.compile`` spans, one per backend compile, from the
    ``/jax/core/compile/backend_compile_duration`` monitoring event,
    recorded as ``[now - d, now]`` under the span that was open;
  * ``py.gc`` spans, one per collection of the oldest generation, from
    ``gc.callbacks``; every collection is counted.

The ring is process-wide, as the profiler, the compile listener and the
collector are.  When it drops its oldest entry ``dropped`` goes up, and
``complete_since(t)`` tells a reader whether any span that ended after
``t`` was lost.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional

import jax
import numpy as np

CAPACITY = 65536           # several minutes of serving at ~20 spans a tick
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    parent: Optional[str]      # the span that was open around this one
    attrs: Dict[str, Any]
    start_ns: int
    end_ns: int


class Ring:
    """The last ``capacity`` spans, in the order they ended.  Spans are
    recorded from the one thread that drives the engine (and the gc and
    compile callbacks, which run in it); ``dropped`` and
    ``lost_until_ns`` are exact for that thread."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self.entries: Deque[tuple] = collections.deque(maxlen=self.capacity)
        self.dropped = 0
        self.lost_until_ns = 0              # end of the newest entry lost
        self.compiles = 0
        self.compile_ns = 0
        self.collections = 0

    def append(self, entry: tuple) -> None:
        # ``entry`` is built before the check: nothing from here on
        # allocates, so no gc callback can append in between
        entries = self.entries
        if len(entries) == self.capacity:
            self.dropped += 1
            self.lost_until_ns = entries[0][4]
        entries.append(entry)

    def complete_since(self, since_ns: int) -> bool:
        """True when no span that ended after ``since_ns`` was lost."""
        return self.dropped == 0 or self.lost_until_ns <= since_ns

    def spans(self, since_ns: int = 0,
              until_ns: Optional[int] = None) -> List[Span]:
        """The retained spans that overlap ``[since_ns, until_ns)``,
        by start time."""
        hi = until_ns if until_ns is not None else float("inf")
        out = [Span._make(r) for r in list(self.entries)
               if r[4] > since_ns and r[3] < hi]
        out.sort(key=lambda s: s.start_ns)
        return out


RING = Ring()


_now = time.monotonic_ns
_profiling = jax.profiler.TraceAnnotation.is_enabled   # a profiler is on
_top: Optional[str] = None       # the innermost open span


class span:
    """``with span("serve.store", rid=7) as sp: ...``; ``sp.set(k=v)``
    adds attributes known only inside the span, ``sp.seconds`` is its
    length once it has closed.  Spans nest in the thread that drives
    the engine; a parent is the name of the span open around it."""

    __slots__ = ("name", "attrs", "parent", "start_ns", "end_ns", "_ann")
    annotation = jax.profiler.TraceAnnotation

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        global _top
        if _profiling():
            self._ann = self.annotation(self.name, **self.attrs)
            self._ann.__enter__()
        else:
            self._ann = None
        self.parent = _top
        _top = self.name
        self.start_ns = _now()
        return self

    def __exit__(self, typ, val, tb) -> None:
        global _top
        self.end_ns = end = _now()
        _top = self.parent
        if self._ann is not None:
            self._ann.__exit__(typ, val, tb)
        RING.append((self.name, self.parent, self.attrs, self.start_ns,
                     end))

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class step_span(span):
    """A span the profiler also counts as a step (``step_num``)."""

    __slots__ = ()
    annotation = jax.profiler.StepTraceAnnotation


def spans(since_ns: int = 0, until_ns: Optional[int] = None) -> List[Span]:
    return RING.spans(since_ns, until_ns)


def complete_since(since_ns: int) -> bool:
    return RING.complete_since(since_ns)


def summary() -> Dict[str, Dict[str, float]]:
    """Per span name in the ring: count, total, p50 and p99 seconds."""
    by_name: Dict[str, List[int]] = {}
    for s in RING.spans():
        by_name.setdefault(s.name, []).append(s.end_ns - s.start_ns)
    out = {}
    for name, ns in sorted(by_name.items()):
        x = np.asarray(ns, np.float64) / 1e9
        out[name] = {"count": int(x.size), "total_s": float(x.sum()),
                     "p50_s": float(np.percentile(x, 50)),
                     "p99_s": float(np.percentile(x, 99))}
    return out


def report() -> Dict[str, Any]:
    """What ``Engine.stats()["trace"]`` shows an operator."""
    return {"summary": summary(), "spans_dropped": RING.dropped,
            "compiles": RING.compiles, "compile_s": RING.compile_ns / 1e9,
            "gc_collections": RING.collections}


# -- sources the program does not call --------------------------------------
def _on_compile(event: str, secs: float, **kw: Any) -> None:
    if event != COMPILE_EVENT:
        return
    end = time.monotonic_ns()
    d = int(secs * 1e9)
    RING.compiles += 1
    RING.compile_ns += d
    RING.append(("jax.compile", _top, {"program": kw.get("fun_name", "")},
                 end - d, end))


_gc_start_ns = 0


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_start_ns
    if phase == "start":
        _gc_start_ns = time.monotonic_ns()
        return
    RING.collections += 1
    gen = info.get("generation", 0)
    if gen == 2:
        RING.append(("py.gc", _top, {"gen": gen}, _gc_start_ns,
                     time.monotonic_ns()))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
gc.callbacks.append(_on_gc)
