"""Collective: the part of the all-reduce's time during which no other
operation runs on that chip (the exchange that compute does not hide),
ms per round, mean over the chips."""
from chipbench import tracefile

LAYER = "collective"
UNIT = "ms"
MOVES = "round_s"
OPCODE = "all-reduce"


def read(ctx):
    tr = ctx["trace"]

    def exposed(p):
        iv = tracefile.collective_intervals(tr, p, OPCODE)
        return tracefile.exposed_ns(tr, p, iv, OPCODE) if iv else None

    per = [exposed(p) for p in tracefile.planes(tr)]
    if not per or None in per:
        return None
    return sum(per) / len(per) / 1e6 / ctx["window"]["rounds"]
