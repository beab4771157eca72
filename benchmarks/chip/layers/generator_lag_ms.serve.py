"""How late the load generator sent requests: 95th percentile of send time
less due time (ms), over the requests due in the window."""
import numpy as np


def read(run):
    if run.kind != "serve" or run.lag_s is None or not run.lag_s.size:
        return None
    return 1e3 * float(np.percentile(run.lag_s, 95))
