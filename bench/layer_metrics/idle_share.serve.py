"""Share of the traced serving window in which no op ran on the chip."""
from bench import trace


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else trace.idle_share(tr)
