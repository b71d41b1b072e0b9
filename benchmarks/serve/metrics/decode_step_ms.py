"""Model step: device time of the paged decode program per decode step
(ms), from the trace."""
import tracing


def read(run):
    if run.trace is None:
        return None
    runs = tracing.program_runs(run.trace, tracing.DECODE_PROGRAM)
    return sum(runs) / len(runs) * 1e3 if runs else None
