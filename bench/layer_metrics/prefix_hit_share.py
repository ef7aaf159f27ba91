"""Share of prompt tokens served from the prefix cache's pages in the
traced window (``ServingMetrics`` counts)."""


def read(ctx):
    c = ctx.get("counts")
    if not c:
        return None
    prompt = c["prefill_tokens"] + c["prefix_hit_tokens"]
    return 100.0 * c["prefix_hit_tokens"] / prompt if prompt else None
