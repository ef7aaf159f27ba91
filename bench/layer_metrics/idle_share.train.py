"""Share of the traced training window in which no op ran on a chip,
averaged over the chips."""
from bench import trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "step_flops" not in ctx:
        return None
    return trace.idle_share(tr)
