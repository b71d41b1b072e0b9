"""Model step: device time of the prefill programs per 1000 prompt
tokens prefilled in the window (ms), from the trace."""
import tracing


def read(run):
    if run.trace is None:
        return None
    runs = tracing.program_runs(run.trace, tracing.PREFILL_PROGRAM)
    tokens = sum(s.prefill_plen for s in run.steps if s.prefill_plen)
    if not runs or not tokens or len(runs) != sum(
            1 for s in run.steps if s.prefill_plen):
        return None
    return sum(runs) * 1e3 / (tokens / 1000.0)
