"""Eval: device time of the eval program (``fl/tasks.lm_eval_metrics``),
ms per round of the window (eval runs every ``eval_every`` rounds).

The eval program is the one, other than the round step, that runs once per
eval batch of the window and takes the most device time.
"""
import math

from chipbench import tracefile

LAYER = "eval"
UNIT = "ms"
MOVES = "round_s"
BATCH = 64        # rows per call of the program's eval step


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    calls = w["evals"] * math.ceil(ctx["traffic"]["eval_rows"] / BATCH)
    if not calls or calls == w["rounds"]:
        return None
    tot = []
    if not tracefile.planes(tr):
        return None
    for plane in tracefile.planes(tr):
        step = tracefile.step_program(tr, plane, w["rounds"])
        mods = [(t, n) for n, (c, t) in
                tracefile.module_totals(tr, plane).items()
                if c == calls and n != step]
        if not mods:
            return None
        tot.append(max(mods)[0])
    return sum(tot) / len(tot) / 1e6 / w["rounds"]
