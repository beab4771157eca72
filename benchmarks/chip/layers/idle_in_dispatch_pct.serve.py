"""Share of the traced window in which chip 0 runs no operation while the
host is inside the pipeline's `repro.dispatch` span (%): device idle that
the batch dispatch (tracing and enqueueing a batch's solve) holds, apart
from idle spent waiting for arrivals, admitting or planning."""
from harness import named

named.install()   # before the run: the trace is gone when `read` is called


def read(run):
    n = named.of(run)
    if run.kind != "serve" or n is None or n.window_s <= 0:
        return None
    s = n.idle_inside.get("repro.dispatch")
    if s is None and "repro.dispatch" not in n.spans:
        return None
    return 100.0 * (s or 0.0) / n.window_s
