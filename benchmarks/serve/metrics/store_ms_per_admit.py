"""Page pool: host time of one prompt's store into its pages (ms), the
mean length of the ``serve.store`` spans that start in the window.  The
store is eager: this is the host dispatching it, not the device's
scatter."""
import program_spans


def read(run):
    spans = program_spans.in_window(run)
    if spans is None:
        return None
    stores = program_spans.starting_in_window(run, spans, "serve.store")
    if not stores:
        return None
    return sum(s.end_ns - s.start_ns for s in stores) / len(stores) / 1e6
