"""Host time to plan one batch (pad, stack, warm start) (ms, mean): the
pipeline's `StageClocks` plan samples of the window."""
import numpy as np


def read(run):
    s = run.stage_s.get("plan") if run.kind == "serve" else None
    return 1e3 * float(np.mean(s)) if s else None
