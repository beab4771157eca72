"""Admission queue wait per request (ms, mean): the pipeline's
`StageClocks` queue_wait samples of the window."""
import numpy as np


def read(run):
    s = run.stage_s.get("queue_wait") if run.kind == "serve" else None
    return 1e3 * float(np.mean(s)) if s else None
