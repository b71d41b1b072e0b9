"""Scheduler: 90th percentile of due time to admission (ms), from the
scheduler's own admission timestamp, over requests due in the window."""
import numpy as np


def read(run):
    waits = [f.admitted - f.due for f in run.flows
             if run.t0 <= f.due < run.t1 and f.admitted is not None]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
