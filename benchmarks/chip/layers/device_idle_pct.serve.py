"""Share of the traced window in which no operation ran on the device
(%), averaged over the chips used: 1 - busy / window, busy being the union
of the device's op intervals."""


def read(run):
    t = run.trace
    if run.kind != "serve" or t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_mean_s / t.window_s)
