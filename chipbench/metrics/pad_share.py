"""Cohort and stack: share of the round step's client rows that are
padding, in %: 1 - (sum over rounds and active clients of
min(S_t^u, s_max)) / (rounds x U_pad x s_max). Read from the plans the
benchmark's policy wrapper recorded."""

LAYER = "cohort and stack"
UNIT = "%"
MOVES = "client_tokens_per_s"


def read(ctx):
    w = ctx["window"]
    return 100.0 * (1.0 - w["real_rows"] / w["padded_rows"])
