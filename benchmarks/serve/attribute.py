#!/usr/bin/env python3
"""One traced run of a cell, then where the device's idle time went.

    python3 benchmarks/serve/attribute.py --workload qwen3-1.7b.chat_backlog \
        --seed 7 --seconds 51 --out chiprun_out/attribution.json

Makes ``run.py``'s traced run (its result line is printed as ``run.py``
prints it) and keeps the run's view.  Then it prints one more JSON line,
and writes it to ``--out``: the device-idle seconds of the window under
the innermost span open on the host (the program's spans, else the
harness's), the longest idle gaps with what held each, every tick over
one second with the spans inside it, the program's spans in the window
by name, and the ``py.gc`` and ``jax.compile`` spans among them.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import run  # noqa: E402  (puts src and this directory on the path)
import program_spans  # noqa: E402

LONG_TICK_NS = 1_000_000_000


def _capture() -> Dict[str, Any]:
    """Keep the view ``run_cell`` hands its per-layer readers."""
    kept: Dict[str, Any] = {}
    per_layer = run.per_layer

    def keep(cell, view):
        kept["view"] = view
        return per_layer(cell, view)

    run.per_layer = keep
    return kept


def _by_name(spans) -> Dict[str, Dict[str, float]]:
    out: Dict[str, List[int]] = {}
    for s in spans:
        out.setdefault(s.name, []).append(s.end_ns - s.start_ns)
    return {name: {"count": len(ns), "total_s": sum(ns) / 1e9,
                   "p50_ms": float(np.percentile(ns, 50)) / 1e6,
                   "p99_ms": float(np.percentile(ns, 99)) / 1e6,
                   "max_ms": max(ns) / 1e6}
            for name, ns in sorted(out.items(), key=lambda kv: -sum(kv[1]))}


def attribute(view) -> Optional[Dict[str, Any]]:
    spans = program_spans.in_window(view)
    if spans is None or view.trace is None:
        return None
    window_s = view.trace.window_s
    by_span = program_spans.idle_by_span(view)
    if by_span is None:
        return None
    idle = program_spans.idle_intervals(view.trace, 0)
    gaps = []
    for s, t in sorted(idle, key=lambda iv: iv[0] - iv[1])[:5]:
        held = program_spans.idle_by_span(view, idle=[(s, t)])
        gaps.append({"ms": (t - s) / 1e6, "at_s": (s - view.trace.window[0])
                     / 1e9, "held_by_ms": {k: v * 1e3
                                           for k, v in held.items()}})
    lo, _ = program_spans.window_ns(view)
    long_ticks = []
    for tick in program_spans.starting_in_window(view, spans, "serve.step"):
        if tick.end_ns - tick.start_ns < LONG_TICK_NS:
            continue
        inside = [s for s in spans if s is not tick
                  and tick.start_ns <= s.start_ns <= tick.end_ns]
        long_ticks.append({"s": (tick.end_ns - tick.start_ns) / 1e9,
                           "at_s": (tick.start_ns - lo) / 1e9,
                           "spans": _by_name(inside)})
    return {
        "window_s": window_s,
        "idle_s": sum(t - s for s, t in idle) / 1e9,
        "idle_share_by_span": {k: 100.0 * v / window_s
                               for k, v in by_span.items()},
        "longest_gaps": gaps,
        "ticks_over_1s": long_ticks,
        "spans": _by_name(spans),
        "gc_and_compiles": [
            {"name": s.name, "at_s": (s.start_ns - lo) / 1e9,
             "ms": (s.end_ns - s.start_ns) / 1e6, "parent": s.parent,
             **s.attrs}
            for s in spans if s.name in ("py.gc", "jax.compile")],
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None)
    args, rest = ap.parse_known_args(argv)
    kept = _capture()
    code = run.main(rest + ["--trace", "1"])
    if code != 0 or "view" not in kept:
        return code or 1
    found = attribute(kept["view"])
    line = json.dumps({"attribution": found})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0 if found is not None else 1


if __name__ == "__main__":
    sys.exit(main())
