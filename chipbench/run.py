#!/usr/bin/env python3
"""The chip benchmark of the ADEL-FL round runtime: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. Set-up, the timed window and the check are described in
``harness.py``. The last line of standard output is one JSON object:
``correct``, ``attempted`` and ``failed`` (rounds), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics and a
``breakdown``), ``device``, and last ``compared``: each number the check
compares, beside its limit. The same numbers end standard error.

Exits non-zero without a result where JAX finds no TPU or fewer chips than
the cell asks for, where the window ends on the plan rather than the
clock, or where anything compiles inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def fail(msg: str) -> None:
    raise SystemExit(f"chipbench: {msg}")


def device_info(chips: int, *, allow_cpu: bool = False) -> dict:
    """Platform, kind and count of JAX's devices; fails without a TPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not allow_cpu:
        fail(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def per_layer(cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell, as its reader reads it from
    ``ctx``: ``trace`` (``tracefile.load``), ``window`` (``Bench.window``'s
    record: rounds, evals, real and padded rows, model FLOPs per round),
    ``arch``, ``traffic``, ``peaks``, ``chips``, ``s_max``, ``U_pad`` and
    ``n_params``. A reader that finds nothing returns None, and the metric
    is left out."""
    from chipbench.harness import reader
    out = {}
    for m in cell.per_layer:
        v = reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(tr: dict) -> dict:
    from chipbench import tracefile
    if not tracefile.planes(tr):
        return {"device_ops": [], "idle_gaps": []}
    plane = tracefile.planes(tr)[0]
    ops = sorted(tracefile.op_totals(tr, plane, leaves=True).items(),
                 key=lambda x: -x[1])
    gaps = sorted(tracefile.idle_gaps(tr, plane), key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, t / 1e9] for n, t in ops[:10]],
            "idle_gaps": [[tracefile.what_ran(tr, plane, s), (e - s) / 1e9]
                          for s, e in gaps[:10]]}


def main(argv=None, *, allow_cpu: bool = False, cell_files=None) -> dict:
    """One run; ``allow_cpu`` and ``cell_files`` (``load_cell``'s
    ``bench_file`` and ``data_dir``) serve the self-checks alone."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, tracefile
    from chipbench.peaks import peaks
    cell = harness.load_cell(args.workload, *(cell_files or ()))
    device = device_info(cell.chips, allow_cpu=allow_cpu)
    pk = peaks(device["kind"]) if not allow_cpu else peaks("TPU v5 lite")
    from repro.launch.compile_cache import use_compile_cache
    import jax
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.note(f"device {json.dumps(device)}; compile cache {cache}")

    stages = {"start": time.perf_counter() - T_START}
    bench = harness.Bench(cell)
    stages["plan_solve"] = time.perf_counter() - T_START
    bench.start(args.seed)
    stages["inputs"] = time.perf_counter() - T_START
    rec = bench.checked_rounds()
    stages["checked_rounds"] = time.perf_counter() - T_START
    harness.note("set-up stages, s from process start: " + ", ".join(
        f"{k} {v:.3f}" for k, v in stages.items()))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        win = bench.window(args.seconds, trace_dir)
        setup_s = win["t0"] - T_START
        harness.note(f"window {win['seconds']:.6f} s, {win['rounds']} "
                     f"rounds, {win['evals']} evals, {win['compiles']} "
                     f"compiles; set-up {setup_s:.6f} s")
        if not win["stopped_on_clock"]:
            fail("the window ended on the plan's T_max or its last round, "
                 "not on the clock")
        if win["compiles"]:
            fail(f"{win['compiles']} programs compiled inside the window")
        tr = tracefile.load(trace_dir) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = bench.compiled_peak()
    device["memory_peak_bytes"] = peak
    harness.note(f"platform {device['platform']}, device_kind "
                 f"{device['kind']}, {device['count']} chips; compiled peak "
                 f"of the round step {peak} B")
    eval_rows = bench.eval_rows_host
    s_max = bench.s_max
    if tr is not None:
        ctx = {"trace": tr, "window": win, "arch": cell.arch,
               "traffic": cell.traffic, "peaks": pk, "chips": cell.chips,
               "s_max": s_max, "U_pad": bench.U_pad,
               "n_params": bench.n_params}
        metrics = per_layer(cell, ctx)
        planes = tracefile.planes(tr)
        lo, hi = tracefile.window(tr)
        busy = tracefile.mean_over_planes(
            tr, lambda p: tracefile.busy_ns(tr, p))
        device["busy_s"] = None if busy is None else busy / 1e9
        device["window_s"] = (hi - lo) / 1e9
        harness.note(f"trace planes {planes}; lines {tr['lines']}")
    else:
        metrics = {
            "round_s": {"value": win["seconds"] / win["rounds"], "unit": "s"},
            "client_tokens_per_s": {"value": win["tokens"] / win["seconds"],
                                    "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    wire = bench.spec.compression.mode
    bench.free()
    del bench
    ref = harness.reference_numbers(cell.arch, args.seed, rec,
                                    s_max, eval_rows, wire=wire)
    cmp = harness.compare(rec, ref)
    compared = {k: {"value": cmp[k], "limit": cell.limits.get(k)}
                for k in harness.CHECKS}
    bad = [k for k, v in compared.items()
           if v["limit"] is None or not v["value"] <= v["limit"]]
    k = cell.traffic["check_rounds"]
    result = {"correct": not bad, "attempted": win["rounds"] + k,
              "failed": k if bad else 0, "metrics": metrics,
              "device": device}
    if tr is not None:
        result["breakdown"] = breakdown(tr)
    result["compared"] = compared
    harness.note(f"eval-loss gap {cmp['loss_gap']!r}; worst leaves: "
                 f"{cmp['worst_leaf_grad1']} (first round), "
                 f"{cmp['worst_leaf_change']} (after {k}); "
                 f"{cmp['leaves_compared']} of {cmp['leaves']} compared; "
                 f"losses program {rec['loss']} reference {ref['loss']}")
    for name, v in compared.items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
