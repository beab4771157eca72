"""Host time of `solve()`'s `launch` stage per re-plan (ms, mean): the self
time of the program's `repro.solve.launch` spans in the traced part of the
window, on the device trace's clock. The stage is the call that traces and
enqueues the compiled solve."""
from harness import named

named.install()   # before the run: the trace is gone when `read` is called


def read(run):
    n = named.of(run)
    got = n.spans.get("repro.solve.launch") if run.kind == "replan" and n \
        else None
    return 1e3 * got[1] / got[0] if got else None
