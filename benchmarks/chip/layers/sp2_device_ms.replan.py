"""Device time of SP2 per re-plan (ms, per chip): the self time of every
XLA op whose name stack holds the solver's `sp2` scope (the rate floor,
the dual search over mu and its inner Newton search on B), in the traced
part of the window, over the chips and the re-plans traced."""
from harness import named

named.install()   # before the run: the trace is gone when `read` is called


def read(run):
    n = named.of(run)
    if run.kind != "replan" or n is None or not run.traced_steps:
        return None
    s = n.scope_s.get("sp2")
    return 1e3 * s / n.chips / run.traced_steps if s else None
