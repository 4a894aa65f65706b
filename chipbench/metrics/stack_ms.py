"""Cohort and stack: host time on the critical path from the end of a
round's plan to the dispatch of its round step, ms per round.

Covers ``fl/runtime._prepare`` (the minibatch draw, padding and transfer)
and the runtime's bookkeeping between the two: the part of each stretch
from a ``chipbench.plan`` span's end to the next ``chipbench.round_step``
span's start during which no operation runs on the device.
"""
import bisect

from chipbench import tracefile

LAYER = "cohort and stack"
UNIT = "ms"
MOVES = "round_s"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tracefile.window(tr)
    ends = sorted(s + d for _, s, d, _ in tracefile.spans(tr, "plan")
                  if lo <= s < hi)
    stretches = []
    for _, s, _, _ in tracefile.spans(tr, "round_step"):
        i = bisect.bisect_right(ends, s)
        if lo <= s < hi and i:
            stretches.append((ends[i - 1], s))
    ns = tracefile.mean_over_planes(
        tr, lambda p: tracefile.idle_within(tr, p, stretches))
    return None if ns is None else ns / 1e6 / ctx["window"]["rounds"]
