"""Device: share of the traced window in which no operation runs on the
chip, in %: 1 - (union of device op intervals) / window, mean over chips.
The window is the benchmark's ``chipbench.window`` span, from the first
plan to the last round's weights ready."""
from chipbench import tracefile

LAYER = "device"
UNIT = "%"
MOVES = "round_s"


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tracefile.window(tr)
    busy = tracefile.mean_over_planes(tr, lambda p: tracefile.busy_ns(tr, p))
    return None if busy is None else 100.0 * (1.0 - busy / (hi - lo))
