"""Share of the traced window the engine loop spent on host work: its
``step`` spans less the ``retire.wait`` spans in which it blocked on the
device.  Once the device gets faster, this work paces the chip."""
from bench import program_record, trace


def read(ctx):
    tr = ctx.get("trace")
    steps = program_record.events("X", "step")
    if tr is None or not steps or trace.window_s(tr) <= 0:
        return None
    waited = sum(e[4] for e in program_record.events("X", "retire.wait"))
    busy_ns = sum(e[4] for e in steps) - waited
    return 100.0 * busy_ns / 1e9 / trace.window_s(tr)
