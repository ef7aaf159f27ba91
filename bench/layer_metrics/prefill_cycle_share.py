"""Share of the engine cycles in the traced window that decoded and also
dispatched prompt tokens: the ``step.submit`` spans with ``decode_rows`` >
0 that carry ``prefill_tokens`` > 0.  In such a cycle the prefill runs
ahead of the decode scan, so its tokens come later."""
from bench import program_record


def read(ctx):
    decoding = [e[5] for e in program_record.events("X", "step.submit")
                if e[5] and e[5]["decode_rows"] > 0]
    if not decoding:
        return None
    mixed = sum(1 for a in decoding if a["prefill_tokens"] > 0)
    return 100.0 * mixed / len(decoding)
