"""Load generator: how late the latest submit ran after its due time (ms),
over the requests due in the window.  Submits happen only between
scheduler steps, so this is at least one step when the engine is busy."""


def read(run):
    if run.mix.arrival != "poisson":
        return None
    lags = [f.submitted - f.due for f in run.flows
            if run.t0 <= f.due < run.t1]
    return max(lags) * 1e3 if lags else None
