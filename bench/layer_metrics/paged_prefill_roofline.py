"""The paged chunked-prefill kernel's share of its roofline: the least
time the prompts prefilled in the window need (causal scores and values,
their K and V pages, queries in and outputs out), over the kernel's time
inside the engine's prefill programs (named ``jit_wrapped`` after the
engine's compile-counting wrapper; the kernel's calls from the speculative
verify are left out on both sides)."""
from bench import trace

KERNEL = r"paged_prefill"
MODULE = r"jit_wrapped"


def kernel_work(k: dict, n: int, kv_bytes: int, act_bytes: int = 2):
    """(FLOPs, bytes) of one n-token prompt from position 0, all layers."""
    flops = k["L"] * 4.0 * k["H"] * k["hd"] * n * (n + 1) / 2
    nbytes = k["L"] * n * (2.0 * k["KV"] * k["hd"] * kv_bytes
                           + 2.0 * k["H"] * k["hd"] * act_bytes)
    return flops, nbytes


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    tr = ctx.get("trace")
    if tr is None or not ctx["prefills"]:
        return None
    t = trace.time_of(tr, KERNEL, within=MODULE)
    if t <= 0:
        return None
    p = ctx["peaks"]
    least = 0.0
    for n in ctx["prefills"]:
        f, b = kernel_work(ctx["model"], n, ctx["kv_bytes"])
        least += max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"])
    return 100.0 * least / t
