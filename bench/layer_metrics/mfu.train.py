"""The whole training step's share of the chips' bf16 peak: the model
FLOPs of the steps in the trace (``bench/flops_train.py``) over the device
time of their programs times the peak.  Device time and step count are
summed over the chips, so the FLOPs of a step (the whole global batch's)
are set against its time on every chip."""
from bench import trace
from bench.flops_train import STEP_PROGRAM as MODULE


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "step_flops" not in ctx or ctx.get("peaks") is None:
        return None
    n = trace.count_of(tr, MODULE)
    busy = trace.time_of(tr, MODULE, "modules")
    if not n or busy <= 0:
        return None
    steps = n / len(tr.devices)
    return 100.0 * steps * ctx["step_flops"] / (busy * ctx["peaks"]["bf16_flops"])
