"""Drives the program's ``Engine`` with a traffic stream and records what
a user would see.

Requests go in through ``Engine.submit`` when they fall due, between
calls to ``Engine.step()``; every token is stamped with the host clock
when the step that emitted it returns.  Admission times come from the
scheduler's own timestamp of each admitted request.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

import tracing
import traffic


@dataclasses.dataclass
class Flow:
    """One request as the user saw it (host monotonic clock, seconds)."""
    request: traffic.Request
    handle: object
    due: float
    submitted: float
    admitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    seen: int = 0

    @property
    def plen(self) -> int:
        return int(len(self.request.prompt))

    @property
    def state(self) -> str:
        return self.handle.state.value


@dataclasses.dataclass
class Step:
    begin: float
    end: float
    prefill_plen: Optional[int]        # the prompt admitted in this step
    contexts: List[int]                # positions each decoded row attended
    occupancy: float                   # page-pool occupancy after the step


TERMINAL = ("done", "failed", "evicted")
FAILED = ("failed", "evicted")


class Driver:
    def __init__(self, engine, max_batch: int,
                 clock: Callable[[], float] = time.monotonic):
        self.engine = engine
        self.max_batch = max_batch
        self.clock = clock
        self.flows: List[Flow] = []
        self.live: List[Flow] = []
        self.steps: List[Step] = []
        self.rejected = 0
        self.resets = 0                  # requests whose tokens restarted

    # -- submitting ------------------------------------------------------
    def submit(self, req: traffic.Request, due: float) -> Optional[Flow]:
        with tracing.span("bench.submit"):
            now = self.clock()
            try:
                handle = self.engine.submit(req.prompt, req.max_new_tokens)
            except ValueError:
                self.rejected += 1
                return None
        flow = Flow(request=req, handle=handle, due=due, submitted=now)
        self.flows.append(flow)
        self.live.append(flow)
        return flow

    def queued(self) -> int:
        return sum(f.state == "queued" for f in self.live)

    def active(self) -> int:
        return sum(f.state not in ("queued",) + TERMINAL for f in self.live)

    # -- stepping --------------------------------------------------------
    def step(self) -> Step:
        begin = self.clock()
        with tracing.span("bench.step"):
            self.engine.step()
        end = self.clock()
        sched = self.engine._scheduler
        prefill, contexts = None, []
        still = []
        for f in self.live:
            h = f.handle
            n = len(h.out_tokens)
            if n < f.seen:               # preempted: tokens start again
                self.resets += 1
                f.seen = 0
            if f.admitted is None and f.state != "queued":
                f.admitted = sched.t_start.get(h.rid, begin)
                prefill = f.plen
            for i in range(f.seen, n):
                if i >= 1:               # token i attended plen + i positions
                    contexts.append(f.plen + i)
            f.token_times.extend([end] * (n - f.seen))
            f.seen = n
            if f.state not in TERMINAL:
                still.append(f)
        self.live = still
        pages = (self.engine.scheduler_report() or {}).get("pages", {})
        step = Step(begin, end, prefill, contexts,
                    float(pages.get("occupancy", 0.0)))
        self.steps.append(step)
        return step

    def drain(self) -> None:
        while self.live:
            self.step()

    # -- phases ----------------------------------------------------------
    def warm(self, prompts: List[np.ndarray]) -> None:
        """Serve one short request per prompt: compiles each length's
        prefill and page store, the decode step, and the autotune keys.
        What it served is then forgotten."""
        for prompt in prompts:
            self.submit(traffic.Request(-1, 0.0, prompt, 2), self.clock())
            self.drain()
        self.flows.clear()
        self.steps.clear()

    def fill(self, source: Iterator[traffic.Request], depth: int) -> None:
        """Backlog: queue ``depth`` requests and step until every row
        decodes."""
        self.top_up(source, depth)
        while self.active() < self.max_batch:
            self.step()
            self.top_up(source, depth)

    def top_up(self, source: Iterator[traffic.Request], depth: int) -> None:
        while self.queued() < depth:
            self.submit(next(source), self.clock())

    def backlog_window(self, source, depth: int, seconds: float,
                       start: float) -> float:
        end = start + seconds
        while self.clock() < end:
            self.top_up(source, depth)
            self.step()
        return self.clock()

    def open_window(self, source: Iterator[traffic.Request], seconds: float,
                    start: float) -> float:
        """Open loop: submit each request when due, step while there is
        work, wait for the next arrival when there is none."""
        end = start + seconds
        nxt = next(source)
        while True:
            now = self.clock()
            if now >= end:
                return now
            while start + nxt.due_s <= now:
                self.submit(nxt, start + nxt.due_s)
                nxt = next(source)
            if self.live:
                self.step()
            else:
                with tracing.span("bench.poll"):
                    time.sleep(max(0.0, min(start + nxt.due_s, end) - now))

    def finish_first_tokens(self, due_before: float, limit_s: float) -> None:
        """After the window: step, with nothing new, until every request
        due in it has its first token, or ``limit_s`` has passed."""
        stop = self.clock() + limit_s
        while self.clock() < stop and any(
                not f.token_times and f.due < due_before
                and f.state not in TERMINAL for f in self.live):
            self.step()


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def window_tokens(flows: List[Flow], t0: float, t1: float) -> int:
    return sum(1 for f in flows for t in f.token_times if t0 < t <= t1)


def inter_token_gaps(flows: List[Flow], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of a request whose later
    token came in the window."""
    gaps = []
    for f in flows:
        ts = f.token_times
        gaps += [b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
    return gaps


def due_in(flows: List[Flow], t0: float, t1: float) -> List[Flow]:
    return [f for f in flows if t0 <= f.due < t1]


def ttfts(flows: List[Flow], t0: float, t1: float) -> List[float]:
    """Time to first token, from the due time, of every request due in
    the window; one that never got its first token counts as endless."""
    return [(f.token_times[0] - f.due) if f.token_times else float("inf")
            for f in due_in(flows, t0, t1)]


def summary(flows: List[Flow]) -> Dict[str, int]:
    out = {"requests": len(flows), "failed": 0, "done": 0}
    for f in flows:
        out["failed"] += f.state in FAILED
        out["done"] += f.state == "done"
    return out
