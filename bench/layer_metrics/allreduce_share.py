"""Share of the training step's device time spent in collectives that no
other op overlaps: on each chip, the intervals of its all-reduce,
reduce-scatter and all-gather ops less those of its other ops (loops and
conditionals, which span their bodies, excepted), over the device time of
the step programs; summed over the chips.  An op is known by its HLO
instruction name, which is XLA's opcode (``all-reduce.3``) or, for a
collective jax emitted, its primitive's (``psum.150``)."""
import re

from bench import trace
from bench.flops_train import STEP_PROGRAM as MODULE
COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather|psum|"
                        r"reduce_scatter|all_gather")
CONTAINER = re.compile(r"while|conditional|call")


def _exposed(coll, others, lo, hi) -> float:
    """Length of the union of ``coll`` outside the union of ``others``."""
    free = trace.gaps(trace.union(others), lo, hi)
    total, j = 0.0, 0
    for s, e in trace.union(coll):
        while j < len(free) and free[j][1] <= s:
            j += 1
        k = j
        while k < len(free) and free[k][0] < e:
            total += min(e, free[k][1]) - max(s, free[k][0])
            k += 1
    return total


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "step_flops" not in ctx:
        return None
    step = trace.time_of(tr, MODULE, "modules")
    if step <= 0:
        return None
    lo, hi = tr.window
    exposed = 0.0
    for d in tr.devices.values():
        ops = trace.clip(d.ops, lo, hi)
        coll = [o for o in ops if COLLECTIVE.search(o[0])]
        others = [o for o in ops if not COLLECTIVE.search(o[0])
                  and not CONTAINER.search(o[0])]
        exposed += _exposed(coll, others, lo, hi)
    return 100.0 * exposed / 1e9 / step
