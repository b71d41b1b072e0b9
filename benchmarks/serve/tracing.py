"""Host spans, the profiler's trace, and its reduction to intervals.

The harness marks what the host does with spans of its own
(``bench.window`` around the measured window, ``bench.submit``,
``bench.step`` and ``bench.poll`` inside it).  A traced run records them
with the device's operations in one profiler trace; ``reduce`` turns the
trace into plain intervals that the metric readers and the breakdown use.
The device lines are keyed on the names the profiler gives them: one
line of XLA modules (the jitted programs) and one of XLA ops (the
operations and Pallas kernels inside them).
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterator, List, Optional, Tuple

import jax

Interval = Tuple[int, int]                     # [start, end) in ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

# The program's jitted steps and Pallas kernels, by the names they carry
# in the trace today: the engine's prefill is a jitted lambda, the
# scheduler's paged decode step a jitted ``_step``; the kernels are
# custom calls named after the ``ops`` entry that launched them.
PREFILL_PROGRAM = re.compile(r"^jit__lambda$")
DECODE_PROGRAM = re.compile(r"^jit__step$")
PAGED_ATTENTION_KERNEL = re.compile(r"^paged_attention$")
MATMUL_KERNEL = re.compile(r"^matmul_fused$")
# ops that only hold other ops (a scan's loop): busy, but no one op's time
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def op_name(hlo: str) -> str:
    """``%matmul_fused.18 = f32[...] custom-call(...)`` -> ``matmul_fused``."""
    return _SUFFIX.sub("", hlo.split(" = ", 1)[0].lstrip("%"))


def module_name(name: str) -> str:
    """``jit__step(7126483992085272608)`` -> ``jit__step``."""
    return _MODULE_ID.sub("", name)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A host span; recorded only while the profiler runs."""
    with jax.profiler.TraceAnnotation(name):
        yield


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    module: str = ""             # the program an op ran in
    kernel: bool = False         # a Pallas kernel (tpu_custom_call)


@dataclasses.dataclass
class Trace:
    window: Interval             # the bench.window span
    modules: List[List[Event]]   # per chip, the jitted programs run
    ops: List[List[Event]]       # per chip, the operations inside them
    spans: List[Event]           # the harness's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def clipped(self, events: List[Event]) -> List[Event]:
        lo, hi = self.window
        return [e for e in events if e.end > lo and e.start < hi]

    def busy_intervals(self, chip: int) -> List[Interval]:
        """The union of the intervals in which an op ran, in the window."""
        lo, hi = self.window
        merged: List[Interval] = []
        for e in sorted(self.ops[chip], key=lambda e: e.start):
            s, t = max(e.start, lo), min(e.end, hi)
            if t <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], t))
            else:
                merged.append((s, t))
        return merged

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips traced."""
        per_chip = [sum(t - s for s, t in self.busy_intervals(c))
                    for c in range(len(self.ops))]
        return sum(per_chip) / len(per_chip) / 1e9

    def idle_gaps(self, chip: int = 0) -> List[Tuple[str, float]]:
        """Every gap between busy intervals in the window, longest first,
        named by the innermost harness span around its midpoint."""
        lo, hi = self.window
        edges = [lo] + [x for iv in self.busy_intervals(chip) for x in iv]
        edges.append(hi)
        gaps = []
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps.append((self.host_span_at((s + t) // 2), (t - s) / 1e9))
        return sorted(gaps, key=lambda g: -g[1])

    def host_span_at(self, ns: int) -> str:
        inside = [e for e in self.spans if e.start <= ns < e.end
                  and e.name != WINDOW_SPAN]
        if not inside:
            return "outside bench spans"
        return min(inside, key=lambda e: e.end - e.start).name

    def op_seconds(self, chip: int = 0) -> List[Tuple[str, float]]:
        """Device time per op, named ``program/op``, in the window,
        largest first; ops that only hold others are left out."""
        total: Dict[str, float] = collections.Counter()
        for e in self.clipped(self.ops[chip]):
            if e.name not in CONTAINERS:
                total[f"{e.module}/{e.name}"] += (e.end - e.start) / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])


def _in_modules(ops: List[Event], modules: List[Event]) -> None:
    """Name each op's program: the module run whose interval holds it."""
    modules = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i].end:
            op.module = modules[i].name


def reduce(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    data = jax.profiler.ProfileData.from_file(path)
    chips: Dict[int, Tuple[List[Event], List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            modules, ops = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if line.name == OPS_LINE:
                        ops.append(Event(op_name(ev.name), start, end,
                                         kernel="tpu_custom_call" in ev.name))
                    else:
                        modules.append(Event(module_name(ev.name), start,
                                             end))
            _in_modules(ops, modules)
            chips[int(m.group(1))] = (modules, ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append(Event(ev.name, start,
                                           start + int(ev.duration_ns)))
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if not chips or not windows:
        raise ValueError(f"{path}: no device plane or no {WINDOW_SPAN} span "
                         f"(planes: {[p.name for p in data.planes]})")
    window = max(windows, key=lambda e: e.end - e.start)
    order = sorted(chips)
    return Trace(window=(window.start, window.end),
                 modules=[chips[c][0] for c in order],
                 ops=[chips[c][1] for c in order], spans=spans)


class Profiler:
    """Starts and stops the profiler around the window, in a fixed
    directory that each traced run of the cell clears first."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self) -> Optional[str]:
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def program_runs(trace: Trace, program: "re.Pattern") -> List[float]:
    """Device seconds of each run of a program in the window (chip 0)."""
    return [(e.end - e.start) / 1e9 for e in trace.clipped(trace.modules[0])
            if program.search(e.name)]


def kernel_seconds(trace: Trace, program: "re.Pattern",
                   kernel: "re.Pattern") -> float:
    """Device seconds of a Pallas kernel inside a program, in the window."""
    return sum((e.end - e.start) / 1e9 for e in trace.clipped(trace.ops[0])
               if e.kernel and kernel.search(e.name)
               and program.search(e.module))
