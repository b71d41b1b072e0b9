"""Sampling: host time a decode tick spends on its logits after the
device has finished (ms): the finiteness check (``serve.validate``),
the copy to the host (``serve.fetch``) and sampling, journaling and
finishing (``serve.emit``), summed inside each ``serve.decode`` span
that decoded rows, median over the window's decode ticks."""
import numpy as np

import program_spans

PARTS = ("serve.validate", "serve.fetch", "serve.emit")


def read(run):
    spans = program_spans.in_window(run)
    if spans is None:
        return None
    ticks = [s for s in program_spans.starting_in_window(
        run, spans, "serve.decode") if s.attrs.get("rows")]
    if not ticks:
        return None
    per_tick = [sum(k.end_ns - k.start_ns for k in kids) for kids in
                program_spans.children(ticks, spans, PARTS)]
    return float(np.median(per_tick)) / 1e6
