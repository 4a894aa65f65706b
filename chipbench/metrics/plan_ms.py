"""Planner: the planner's time on the critical path, ms per round.

The time inside the benchmark's ``chipbench.plan`` spans (around
``Policy.round``: the straggler draw and B3 batch sizes of
``core/baselines``, ``core/straggler``) during which no operation runs on
the device, summed over the window, averaged over chips, per round. The
span itself also covers the wait for the previous round's step, which
costs nothing.
"""
from chipbench import tracefile

LAYER = "planner"
UNIT = "ms"
MOVES = "round_s"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tracefile.window(tr)
    spans = [(s, s + d) for _, s, d, _ in tracefile.spans(tr, "plan")
             if lo <= s < hi]
    ns = tracefile.mean_over_planes(
        tr, lambda p: tracefile.idle_within(tr, p, spans))
    return None if ns is None else ns / 1e6 / ctx["window"]["rounds"]
