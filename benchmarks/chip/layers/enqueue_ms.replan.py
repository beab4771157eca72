"""Host time from the `solve()` call to its return, before blocking: the
program's dispatch of one re-plan (ms, mean over the window)."""
import numpy as np


def read(run):
    if run.kind != "replan" or run.enqueue_s is None or not run.enqueue_s.size:
        return None
    return 1e3 * float(np.mean(run.enqueue_s))
