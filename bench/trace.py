"""Reduction of a JAX profiler trace to the numbers the per-layer readers
take: device busy time, idle gaps and what the host did in them, time per
kernel or program.

A trace is read into a ``Trace``: per device, its op events and its
program (module) events as (name, start_ns, end_ns); on the host, the
benchmark's own ``bench.*`` spans.  Everything else works on those lists,
so the tests can build a ``Trace`` by hand.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

@dataclass
class Device:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Event]                     # the benchmark's bench.* spans
    window: Tuple[float, float]           # the traced window, in ns


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------

def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(line) -> List[Event]:
    """(name, start, end) of a line's events; an op's name is its HLO
    instruction name (``%paged_attention.12``), without its signature."""
    return [(e.name.split(" = ", 1)[0], float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def read(log_dir: str, window_span: str = "bench.trace_window") -> Trace:
    """Load the newest trace under ``log_dir``.  The window is the host
    span ``window_span`` (the whole trace when it is absent)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(log_dir))
    devices: Dict[int, Device] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops.extend(_events(line))
                elif line.name == "XLA Modules":
                    dev.modules.extend(_events(line))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[0].startswith("bench."))
    win = [e for e in host if e[0] == window_span]
    if win:
        window = (win[0][1], win[0][2])
    else:
        every = [e for d in devices.values() for e in d.ops] or host
        window = (min(e[1] for e in every), max(e[2] for e in every))
    return Trace(devices, host, window)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by any event."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of the window [lo, hi) between busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# ---------------------------------------------------------------------------
# The numbers
# ---------------------------------------------------------------------------

def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices."""
    lo, hi = tr.window
    vals = [covered(union(clip(d.ops, lo, hi))) for d in tr.devices.values()]
    return sum(vals) / max(len(vals), 1) / 1e9


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def idle_share(tr: Trace) -> Optional[float]:
    """1 - busy / window, in percent; None without device events."""
    if not tr.devices or window_s(tr) <= 0:
        return None
    return 100.0 * (1.0 - busy_s(tr) / window_s(tr))


def time_of(tr: Trace, pattern: str, kind: str = "ops",
            within: Optional[str] = None) -> float:
    """Seconds of the events whose name matches ``pattern`` (a regular
    expression), summed over devices and clipped to the window; with
    ``within``, only ops that lie inside a program whose name matches it."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    total = 0.0
    for d in tr.devices.values():
        evs = [e for e in clip(getattr(d, kind), lo, hi) if rx.search(e[0])]
        if within is not None:
            mods = union(m for m in d.modules if re.search(within, m[0]))
            evs = [e for e in evs if _inside(e, mods)]
        total += sum(e - s for _, s, e in evs)
    return total / 1e9


def _inside(ev: Event, spans: Sequence[Tuple[float, float]]) -> bool:
    mid = (ev[1] + ev[2]) / 2
    i = bisect.bisect_right([s for s, _ in spans], mid) - 1
    return i >= 0 and spans[i][0] <= mid < spans[i][1]


def count_of(tr: Trace, pattern: str, kind: str = "modules") -> int:
    """Events whose name matches ``pattern`` that start in the window,
    over all devices."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return sum(1 for d in tr.devices.values()
               for n, s, _ in getattr(d, kind) if lo <= s < hi and rx.search(n))


def _top(tr: Trace, kind: str, n: int) -> List[list]:
    lo, hi = tr.window
    tot: Dict[str, float] = {}
    for d in tr.devices.values():
        for name, s, e in clip(getattr(d, kind), lo, hi):
            tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(tr.devices), 1)
    ranked = sorted(tot.items(), key=lambda x: -x[1])[:n]
    return [[name, t / k / 1e9] for name, t in ranked]


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """The device ops that took most time (summed by name, per device
    averaged)."""
    return _top(tr, "ops", n)


def top_modules(tr: Trace, n: int = 10) -> List[list]:
    """The programs that took most device time (summed by name, per device
    averaged)."""
    return _top(tr, "modules", n)


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """The longest idle gaps of device 0 (lowest id), each named by the
    innermost ``bench.*`` host span that covers most of it."""
    if not tr.devices:
        return []
    lo, hi = tr.window
    dev = tr.devices[min(tr.devices)]
    idle = gaps(union(clip(dev.ops, lo, hi)), lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in idle[:n]:
        # the span covering most of the gap; of equals, the innermost
        cands = [(min(e, he) - max(s, hs), hs - he, name)
                 for name, hs, he in tr.host
                 if name != "bench.trace_window" and min(e, he) > max(s, hs)]
        out.append([max(cands)[2] if cands else "none", (e - s) / 1e9])
    return out


def breakdown(tr: Trace) -> dict:
    return {"device_ops": top_ops(tr), "idle_gaps": idle_gaps(tr)}
