"""Collective: time an all-reduce is in flight on a chip (the
``ShardMapBackend``'s psum of the clients' folded deltas), ms per round,
mean over the chips: the union of the device trace's ops whose opcode is
``all-reduce`` (named ``%psum.N`` after the JAX op), synchronous or from
start to done."""
from chipbench import tracefile

LAYER = "collective"
UNIT = "ms"
MOVES = "round_s"
OPCODE = "all-reduce"


def read(ctx):
    tr = ctx["trace"]
    ns = tracefile.mean_over_planes(tr, lambda p: sum(
        e - s for s, e in tracefile.collective_intervals(tr, p, OPCODE)))
    return None if not ns else ns / 1e6 / ctx["window"]["rounds"]
