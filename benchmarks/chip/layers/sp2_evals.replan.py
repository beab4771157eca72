"""SP2 dual evaluations per re-plan, summed over the fleet's cells and
averaged over the window's re-plans. From the program's `SolveCounters`."""
import numpy as np


def read(run):
    if run.kind != "replan" or run.counters is None or not run.counters.size:
        return None
    col = run.counter_columns.index("sp2_evals")
    return float(np.mean(np.sum(run.counters[:, :, col], axis=1)))
