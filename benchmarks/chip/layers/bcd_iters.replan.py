"""BCD iterations per re-plan: the loop's trip count, the largest over the
fleet's cells (the vmapped loop runs to the slowest cell), averaged over
the window's re-plans. From the program's `SolveCounters`."""
import numpy as np


def read(run):
    if run.kind != "replan" or run.counters is None or not run.counters.size:
        return None
    col = run.counter_columns.index("bcd_iters")
    return float(np.mean(np.max(run.counters[:, :, col], axis=1)))
