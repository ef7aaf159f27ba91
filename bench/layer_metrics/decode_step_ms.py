"""Device milliseconds per decode step: the device time of the engine's
fused decode-scan programs over the steps they ran (programs x
``decode_steps``)."""
from bench import trace

MODULE = r"decode_scan"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    n = trace.count_of(tr, MODULE)
    if not n:
        return None
    return 1e3 * trace.time_of(tr, MODULE, "modules") / (
        n * ctx["decode_steps"])
