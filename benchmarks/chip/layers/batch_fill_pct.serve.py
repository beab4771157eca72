"""Real cells per dispatched batch over the batch's cells (%), mean over
the batches dispatched in the window."""
import numpy as np


def read(run):
    f = run.batch_fill if run.kind == "serve" else None
    if f is None or not f.size:
        return None
    return 100.0 * float(np.mean(f))
