"""Mean time a request admitted in the traced window waited in the
engine's queue, from its submit (or its requeue after a preemption) to its
admission: the ``queue_s`` of the engine's ``request.admit`` events."""
from bench import program_record


def read(ctx):
    waits = [e[5]["queue_s"]
             for e in program_record.events("i", "request.admit")]
    return 1e3 * sum(waits) / len(waits) if waits else None
