"""Device milliseconds per training step: the device time of the
train-step programs over their count (both summed over the chips, so a
chip's mean)."""
from bench import trace
from bench.flops_train import STEP_PROGRAM as MODULE


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "step_flops" not in ctx:
        return None
    n = trace.count_of(tr, MODULE)
    if not n:
        return None
    return 1e3 * trace.time_of(tr, MODULE, "modules") / n
