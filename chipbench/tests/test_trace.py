"""The reduction from a trace to the per-layer metrics, on a small trace
written out by hand (times in ns)."""
import pytest

from chipbench import tracefile
from chipbench.harness import reader

PLANE = "/device:TPU:0"
TRACE = {
    "host": [["chipbench.window", 0, 1000, {}],
             ["chipbench.plan", 0, 50, {}],
             ["chipbench.round_step", 80, 20, {}],
             ["chipbench.plan", 100, 30, {}],
             ["chipbench.round_step", 150, 10, {}],
             ["chipbench.eval", 170, 600, {}],
             ["chipbench.plan", 1200, 5, {}]],
    "devices": {PLANE: {
        "modules": [["jit_step", 100, 300], ["jit_step", 400, 300],
                    ["jit_stats", 700, 50], ["jit_add", 750, 10]],
        "ops": [["%while.1", 100, 300],        # holds the two below
                ["fusion.1", 100, 250], ["fusion.2", 360, 40],
                ["fusion.1", 400, 300], ["dot", 700, 50],
                ["add", 750, 10]]}},
    "lines": {},
}
CTX = {"trace": TRACE, "window": {"rounds": 2, "evals": 1, "real_rows": 6,
                                   "padded_rows": 8, "flops": [1e3, 2e3]},
       "traffic": {"eval_rows": 64}, "peaks": {"bf16_flops_per_s": 5e12},
       "chips": 1}


def test_busy_idle_and_gaps():
    assert tracefile.window(TRACE) == (0.0, 1000.0)
    assert tracefile.busy_ns(TRACE, PLANE) == 650.0
    assert tracefile.idle_gaps(TRACE, PLANE) == [(0.0, 100.0), (350.0, 360.0),
                                                 (760.0, 1000.0)]
    # the gap at 760 falls in the eval span; the one at 0 in the first plan;
    # the one at 350 inside the round step
    assert tracefile.what_ran(TRACE, PLANE, 760.0) == "eval"
    assert tracefile.what_ran(TRACE, PLANE, 0.0) == "plan"
    assert tracefile.what_ran(TRACE, PLANE, 350.0) == "inside jit_step"
    assert tracefile.open_span(TRACE, 900.0) == "host between calls"
    # host time with no program running: [0, 50) of the first plan, none of
    # the second (the step runs)
    assert tracefile.idle_within(TRACE, PLANE, [(0, 50), (100, 130)]) == 50


def test_programs():
    assert tracefile.step_program(TRACE, PLANE, 2) == "jit_step"
    assert tracefile.module_totals(TRACE, PLANE)["jit_step"] == (2, 600.0)
    assert tracefile.op_totals(TRACE, PLANE)["fusion.1"] == 550.0
    assert "%while.1" not in tracefile.op_totals(TRACE, PLANE, leaves=True)


@pytest.mark.parametrize("metric,value", [
    ("device_idle_share", 35.0),
    ("plan_ms", 50 / 2 / 1e6),            # the plan past the window is out
    ("stack_ms", 30 / 2 / 1e6),           # [50, 80); [130, 150) the step ran
    ("pad_share", 25.0),
    ("step_device_ms", 600 / 2 / 1e6),
    ("step_mfu", 100 * 3e3 / (600e-9 * 5e12)),
    ("eval_ms", 50 / 2 / 1e6),
])
def test_readers(metric, value):
    assert reader(metric).read(CTX) == pytest.approx(value, rel=1e-12)


def test_readers_without_a_device():
    bare = dict(CTX, trace=dict(TRACE, devices={}))
    for metric in ("device_idle_share", "step_device_ms", "step_mfu",
                   "eval_ms", "plan_ms", "stack_ms"):
        assert reader(metric).read(bare) is None


def _recorded():
    import gzip
    import json
    from conftest import DATA
    with gzip.open(DATA / "trace_qwen_round.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_round():
    """One round of the qwen1.5-4b.u8s8 window as a v5e recorded it (the
    first round step and the eval that follows it in the queue), reduced
    by the code and again by brute force on a 1 us grid."""
    import numpy as np
    tr = _recorded()
    (plane,) = tracefile.planes(tr)
    lo, hi = tracefile.window(tr)
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in tracefile.leaf_ops(tr, plane):
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    busy = tracefile.busy_ns(tr, plane)
    assert busy == pytest.approx(grid.sum() * 1000, rel=2e-3)
    step = tracefile.step_program(tr, plane, 1)
    assert step.startswith("jit_step")
    c, t = tracefile.module_totals(tr, plane)[step]
    assert c == 1 and 0.5e9 < t < 1.0e9
    ctx = {"trace": tr, "window": {"rounds": 1, "evals": 1, "flops": [0.0],
                                   "real_rows": 1, "padded_rows": 1},
           "traffic": {"eval_rows": 64}, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1.0}}
    idle = reader("device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    # host time with no program running is part of the idle time
    host = reader("plan_ms").read(ctx) + reader("stack_ms").read(ctx)
    assert 0 < host * 1e6 <= (hi - lo) - busy


def test_allreduce_readers():
    """Two chips; the psum's ops are known by their opcode, not their name;
    on chip 0 the all-reduce is asynchronous and a fusion overlaps its
    first 10 ns."""
    lo = {"modules": [["jit_step", 0, 100]],
          "collectives": {"%psum.7": "all-reduce",
                          "%all-reduce": "all-reduce"}}
    tr = {"host": [["chipbench.window", 0, 100, {}]],
          "devices": {PLANE: dict(lo, ops=[["%fusion.1", 0, 60]],
                                  **{"async": [["%psum.7", 50, 30]]}),
                      "/device:TPU:1": dict(lo, **{"async": []},
                                            ops=[["%fusion.1", 0, 50],
                                                 ["%psum.7", 50, 30],
                                                 ["%all-reduce", 90, 5]])}}
    ctx = {"trace": tr, "window": {"rounds": 2}}
    assert reader("allreduce_ms").read(ctx) == pytest.approx(
        (30 + 35) / 2 / 2 / 1e6)
    assert reader("allreduce_exposed_ms").read(ctx) == pytest.approx(
        (20 + 35) / 2 / 2 / 1e6)
