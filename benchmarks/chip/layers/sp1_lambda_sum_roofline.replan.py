"""Share of its roofline that `sp1_lambda_sum` reaches (%): the least time
its calls could take on this chip (operations over peak FLOP/s or bytes
over peak HBM bandwidth, whichever is larger, from each call's shapes)
over the time they took in the trace."""
from harness import roofline


def read(run):
    t = run.trace
    if run.kind != "replan" or t is None:
        return None
    calls = t.kernel_calls("sp1_lambda_sum")
    took = sum(c.seconds for c in calls)
    if not calls or took <= 0:
        return None
    peak = roofline.peaks(run.device_kind)
    least = sum(roofline.least_time_s(
        *roofline.sp1_lambda_sum_cost(c.out_shape, c.operand_shapes),
        peak)[0] for c in calls)
    return 100.0 * least / took
