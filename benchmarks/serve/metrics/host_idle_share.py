"""Host step: the share of the traced window (%) in which the device was
idle while the host was inside the program: inside a ``serve.step`` or
``serve.submit`` span, on the trace's clock (``program_spans``).
``idle_share`` less this is the idle time outside the program: the
harness, and waiting for arrivals.  Averaged over the chips."""
import program_spans


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    mapped = program_spans.on_trace_clock(run)
    if mapped is None:
        return None
    roots = program_spans.merge([(m.start, m.end) for m in mapped
                                 if m.name in program_spans.ROOTS])
    chips = range(len(run.trace.ops))
    idle = [program_spans.overlap_ns(
        program_spans.idle_intervals(run.trace, c), roots) for c in chips]
    return 100.0 * sum(idle) / len(idle) / 1e9 / run.trace.window_s
