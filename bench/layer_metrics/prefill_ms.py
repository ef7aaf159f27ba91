"""Mean time from a request's admission to its first token handed to the
stream, for first tokens in the traced window: its prefill and the wait
until the cycle that carried it retired (the ``prefill_s`` of the engine's
``request.first_token`` events)."""
from bench import program_record


def read(ctx):
    times = [e[5]["prefill_s"]
             for e in program_record.events("i", "request.first_token")]
    return 1e3 * sum(times) / len(times) if times else None
