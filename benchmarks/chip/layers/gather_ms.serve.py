"""Host time to gather one batch's results into responses (ms, mean): the
pipeline's `StageClocks` gather samples of the window."""
import numpy as np


def read(run):
    s = run.stage_s.get("gather") if run.kind == "serve" else None
    return 1e3 * float(np.mean(s)) if s else None
