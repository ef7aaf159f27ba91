"""What the program itself recorded while the profiler session ran: the
events of ``repro.obs.profiled()``, as ``(ph, name, track, ts_ns, value,
args)`` tuples on ``time.perf_counter_ns()`` (a span's value is its
duration in ns).  A program without that record gives no events, so its
readers find nothing and return None."""
from __future__ import annotations

from typing import List


def events(ph: str, name: str) -> List[tuple]:
    """The recorded events of kind ``ph`` ("X" span, "i" instant, "C"
    counter) named ``name``."""
    try:
        from repro.obs import profiled
    except ImportError:
        return []
    return [e for e in profiled().events if e[0] == ph and e[1] == name]
