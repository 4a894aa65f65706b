"""Round step: model FLOPs of the window's rounds over the round step's
device time at the chips' bf16 peak, in %.

Model FLOPs come from ``chipbench/flops.round_flops`` (real tokens only;
backward work counted only through the layers each client's mask keeps),
so a step that skips discarded work cannot read above what was done.
"""
import importlib.util
from pathlib import Path

LAYER = "round step"
UNIT = "%"
MOVES = "client_tokens_per_s"

_spec = importlib.util.spec_from_file_location(
    "chipbench_step_device_ms", Path(__file__).with_name("step_device_ms.py"))
_step = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_step)


def read(ctx):
    ns = _step.step_ns(ctx)
    if not ns:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * sum(ctx["window"]["flops"]) / (ns / 1e9 * peak)
