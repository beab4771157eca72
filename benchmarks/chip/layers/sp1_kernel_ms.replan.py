"""Device time of the `sp1_lambda_sum` kernel per re-plan (ms, per chip),
summed over its custom-call events in the traced part of the window."""


def read(run):
    t = run.trace
    if run.kind != "replan" or t is None or not run.traced_steps:
        return None
    calls = t.kernel_calls("sp1_lambda_sum")
    if not calls:
        return None
    return 1e3 * sum(c.seconds for c in calls) / t.chips / run.traced_steps
