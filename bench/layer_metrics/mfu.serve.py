"""Model FLOPs of the prompts prefilled and the tokens decoded in the
traced window, over the device time of the engine's model programs times
the chip's bf16 peak.  The programs are the fused decode scans, the
speculative verify and the prefills, which the engine jits through its
compile-counting wrapper (so their programs are named ``jit_wrapped``)."""
from bench import flops, trace

MODULES = r"decode_scan|verify_step|jit_wrapped"


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    tr = ctx.get("trace")
    if tr is None or not (ctx["prefills"] or ctx["decoded"]):
        return None
    busy = trace.time_of(tr, MODULES, "modules")
    if busy <= 0:
        return None
    k = ctx["model"]
    work = sum(flops.prefill(k, n) for n in ctx["prefills"])
    work += sum(flops.decode_token(k, c) for c in ctx["decoded"])
    return 100.0 * work / (busy * ctx["peaks"]["bf16_flops"])
