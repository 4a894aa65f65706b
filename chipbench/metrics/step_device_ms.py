"""Round step: device time of the round-step program, ms per round.

The round step (``fl/backends``: local training of every client,
``models/transformer``, and the Eq. 5 fold of ``core/aggregation``) is
the program run once per round that takes the most device time; its
events are summed over the window, averaged over the chips.
"""
from chipbench import tracefile

LAYER = "round step"
UNIT = "ms"
MOVES = "round_s"


def step_ns(ctx):
    """Device ns of the round step over the window, mean over chips, or
    None where no program ran once per round."""
    tr, n = ctx["trace"], ctx["window"]["rounds"]
    tot = []
    if not tracefile.planes(tr):
        return None
    for plane in tracefile.planes(tr):
        name = tracefile.step_program(tr, plane, n)
        if name is None:
            return None
        tot.append(tracefile.module_totals(tr, plane)[name][1])
    return sum(tot) / len(tot)


def read(ctx):
    ns = step_ns(ctx)
    return None if ns is None else ns / 1e6 / ctx["window"]["rounds"]
