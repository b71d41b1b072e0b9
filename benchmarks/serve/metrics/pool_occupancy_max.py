"""Page pool: the highest share of pages in use after any step of the
window (%), from the scheduler's report."""


def read(run):
    occ = [s.occupancy for s in run.steps]
    return max(occ) * 100.0 if occ and max(occ) > 0 else None
