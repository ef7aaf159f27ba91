"""Model FLOPs of the prompts prefilled in the traced window, over the
device time of the engine's prefill programs (named ``jit_wrapped`` after
its compile-counting wrapper) times the chip's bf16 peak: the whole
prefill step's share of the peak, which bounds what the prefill kernel's
share of its roofline can give."""
from bench import flops, trace

MODULE = r"jit_wrapped"


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    tr = ctx.get("trace")
    if tr is None or not ctx["prefills"]:
        return None
    busy = trace.time_of(tr, MODULE, "modules")
    if busy <= 0:
        return None
    work = sum(flops.prefill(ctx["model"], n) for n in ctx["prefills"])
    return 100.0 * work / (busy * ctx["peaks"]["bf16_flops"])
