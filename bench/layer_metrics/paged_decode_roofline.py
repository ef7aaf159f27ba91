"""The paged decode kernel's share of its roofline: the least time its
calls need for the keys and values held, over its time in the trace.

Work is counted from below: the decode steps' tokens are the decoded
tokens less every speculative proposal (the verify forward emits the
accepted ones), each attending the mean context of the tokens decoded in
the window.
"""
from bench import trace

KERNEL = r"paged_attention"


def kernel_work(k: dict, tokens: float, context: float, kv_bytes: int):
    """(FLOPs, bytes) of ``tokens`` decode queries over ``context`` cached
    keys each, all layers: scores and values, the K and V pages read."""
    flops = tokens * k["L"] * 4.0 * k["H"] * k["hd"] * context
    nbytes = tokens * k["L"] * 2.0 * k["KV"] * k["hd"] * kv_bytes * context
    return flops, nbytes


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    tr, c = ctx.get("trace"), ctx.get("counts")
    if tr is None or not ctx["decoded"]:
        return None
    t = trace.time_of(tr, KERNEL)
    tokens = c["decode_tokens"] - c["accepted_tokens"] - c["drafted_tokens"]
    if t <= 0 or tokens <= 0:
        return None
    context = sum(ctx["decoded"]) / len(ctx["decoded"])
    f, b = kernel_work(ctx["model"], tokens, context, ctx["kv_bytes"])
    p = ctx["peaks"]
    return 100.0 * max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"]) / t
