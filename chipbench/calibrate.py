#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]

In one process, for every seed: the program's checked rounds (as a run
makes them, at the cell's own sizes) compared with the reference; for the
control seeds also the control (the reference in the program's place,
every matmul operand in float8 e4m3), and for the fault seeds the fault
of a batch cut in half (the reference in the program's place, each client
training on half its rows, the mean taken over them). A step that returns
its weights unchanged reads 1 on the gap measures and needs no run.
Each reading is one JSON line on standard output.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None, *, allow_cpu: bool = False, cell_files=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench.run import device_info
    cell = harness.load_cell(args.workload, *(cell_files or ()))
    device = device_info(cell.chips, allow_cpu=allow_cpu)
    from repro.launch.compile_cache import use_compile_cache
    import jax
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = harness.Bench(cell)
    wire = bench.spec.compression.mode
    out = []
    for seed in sorted(set(args.seeds + args.control_seeds
                           + args.fault_seeds)):
        t0 = time.perf_counter()
        bench.start(seed)
        rec = bench.checked_rounds()
        eval_rows = bench.eval_rows_host
        bench.free()
        runs = [("program", rec, {})] if seed in args.seeds else []
        if seed in args.control_seeds:
            runs.append(("control", None, {"precision": "fp8"}))
        if seed in args.fault_seeds:
            runs.append(("half_batch", None, {"keep_rows": 0.5}))
        ref = harness.reference_numbers(cell.arch, seed, rec, bench.s_max,
                                        eval_rows, wire=wire)
        for kind, got, kw in runs:
            if got is None:
                got = harness.reference_numbers(
                    cell.arch, seed, rec, bench.s_max, eval_rows, wire=wire,
                    **kw)
            row = {"cell": cell.name, "seed": seed, "kind": kind,
                   **harness.compare(got, ref), "device": device,
                   "s": time.perf_counter() - t0}
            out.append(row)
            print(json.dumps(row), flush=True)
    return out


if __name__ == "__main__":
    main()
