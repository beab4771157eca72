"""Host time of `solve()`'s `result` stage per re-plan (ms, mean): the self
time of the program's `repro.solve.result` spans in the traced part of the
window, on the device trace's clock. The stage is the assembly of the
result from the solve's device arrays (on the mesh path, the per-shard
statistics too)."""
from harness import named

named.install()   # before the run: the trace is gone when `read` is called


def read(run):
    n = named.of(run)
    got = n.spans.get("repro.solve.result") if run.kind == "replan" and n \
        else None
    return 1e3 * got[1] / got[0] if got else None
