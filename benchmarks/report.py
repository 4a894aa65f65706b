"""Render EXPERIMENTS.md tables from experiments/*.json records.

    PYTHONPATH=src python -m benchmarks.report [--section dryrun|roofline|repro]

Prints GitHub-flavored markdown; EXPERIMENTS.md embeds the output.
"""
from __future__ import annotations

import argparse
import glob
import json
import os


RESULTS = os.path.join(os.path.dirname(__file__), "..", "experiments")


def _load_dryrun():
    recs = []
    for d in ("dryrun", "dryrun_multipod"):
        for fn in sorted(glob.glob(os.path.join(RESULTS, d, "*.json"))):
            with open(fn) as f:
                recs.append(json.load(f))
    return recs


def _fmt_bytes(b):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PiB"


def section_dryrun() -> str:
    rows = ["| arch | shape | mesh | step | compile_s | bytes/dev (args+tmp) | HLO flops/dev | coll bytes/dev |",
            "|---|---|---|---|---|---|---|---|"]
    for r in _load_dryrun():
        if "error" in r:
            rows.append(f"| {r['arch']} | {r['shape']} | {r.get('mesh','?')} "
                        f"| SKIP | — | {r['error'][:60]} | | |")
            continue
        mem = r.get("memory", {})
        args_b = mem.get("argument_size_in_bytes", 0)
        tmp_b = mem.get("temp_size_in_bytes", 0)
        coll = r.get("collective_bytes_per_device_total")
        if coll is None:
            coll = r["collective_bytes_per_device"]["total"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['step']} "
            f"| {r['compile_s']} | {_fmt_bytes(args_b)}+{_fmt_bytes(tmp_b)} "
            f"| {r['flops_per_device']:.3g} | {coll:.3g} |")
    return "\n".join(rows)


def section_roofline() -> str:
    from benchmarks.roofline import table
    rows = table()
    out = ["| arch | shape | mesh | compute_s | memory_s | collective_s "
           "| dominant | model TFLOPs | useful % | bound step_s |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["mesh"], r["arch"], r["shape"])):
        if "error" in r:
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['dominant'].removesuffix('_s')} "
            f"| {r['model_flops'] / 1e12:.1f} | {100 * r['useful_ratio']:.1f} "
            f"| {r['step_s_bound']:.3e} |")
    return "\n".join(out)


def section_backend_sweep() -> str:
    """Seconds/round for the four execution backends (fl.backends) plus
    the donation memory comparison."""
    fn = os.path.join(RESULTS, "results", "backend_sweep.json")
    if not os.path.exists(fn):
        return ""
    with open(fn) as f:
        res = json.load(f)
    out = ["### backend_sweep (s/round)\n",
           "| cohort | dense | chunked | shard_map | temporal | devices |",
           "|---|---|---|---|---|---|"]
    cohorts = {k: v for k, v in res.items() if k.startswith("cohort_")}
    for setting, row in sorted(cohorts.items(),
                               key=lambda kv: int(kv[0].split("_")[-1])):
        if not isinstance(row, dict):
            continue
        cells = []
        for b in ("dense", "chunked", "shard_map", "temporal"):
            d = row.get(b)
            cells.append(f"{d['wall_per_round_s']:.3f}"
                         if isinstance(d, dict) else "—")
        dev = next((d.get("devices") for d in row.values()
                    if isinstance(d, dict)), "?")
        out.append(f"| {setting.removeprefix('cohort_')} | "
                   + " | ".join(cells) + f" | {dev} |")
    comp = res.get("compression")
    if isinstance(comp, dict) and comp:
        out += ["", "compressed client->server payloads "
                    "(reduced LM arch, dense backend; "
                    "bytes are analytic/deterministic):", "",
                "| mode | wire bytes/round | vs dense f32 | s/round "
                "| final acc |",
                "|---|---|---|---|---|"]
        for mode in ("none", "int8", "topk8"):
            d = comp.get(mode)
            if not isinstance(d, dict):
                continue
            ratio = (f"{d['wire_ratio']:.2f}x"
                     if d.get("wire_ratio") else "—")
            acc = (f"{d['final_acc']:.4f}"
                   if isinstance(d.get("final_acc"), (int, float)) else "—")
            out.append(f"| {mode} | "
                       f"{_fmt_bytes(d['bytes_per_round_wire'])} | {ratio} "
                       f"| {d['wall_per_round_s']:.3f} | {acc} |")
    don = res.get("donation")
    if isinstance(don, dict) and don:
        out += ["", "donated params buffers (compiled peak bytes, "
                    "donated / undonated):", ""]
        for name, row in sorted(don.items()):
            if isinstance(row, dict) and "peak_ratio" in row:
                out.append(f"* {name}: {row['donated_peak_bytes']:,} / "
                           f"{row['undonated_peak_bytes']:,} "
                           f"(x{row['peak_ratio']})")
    out.append("")
    return "\n".join(out)


def section_lm_smoke() -> str:
    """The federated LM driver on the unified runtime, per backend."""
    fn = os.path.join(RESULTS, "results", "lm_smoke.json")
    if not os.path.exists(fn):
        return ""
    with open(fn) as f:
        res = json.load(f)
    out = ["### lm_smoke (reduced-arch federated LM on RoundRuntime)\n",
           "| backend | arch | rounds | token loss | token acc | s/round |",
           "|---|---|---|---|---|---|"]
    for backend, d in sorted(res.items()):
        if not isinstance(d, dict):
            continue
        num = lambda k: (f"{d[k]:.4f}"
                         if isinstance(d.get(k), (int, float)) else "—")
        out.append(f"| {backend} | {d.get('arch', '?')} | "
                   f"{d.get('rounds', '?')} | {num('final_loss')} "
                   f"| {num('final_acc')} "
                   f"| {num('wall_per_round_s')} |")
    out.append("")
    return "\n".join(out)


def section_replan_sweep() -> str:
    """Static offline schedule vs online re-planning triggers
    (repro.core.replan) under the same T_max."""
    fn = os.path.join(RESULTS, "results", "replan_sweep.json")
    if not os.path.exists(fn):
        return ""
    with open(fn) as f:
        res = json.load(f)
    out = ["### replan_sweep (final accuracy under the same T_max)\n",
           "| scenario | never | every-k | drift | re-solves (e-k/drift) | "
           "budget used (never) |",
           "|---|---|---|---|---|---|"]
    for scn, row in sorted(res.items()):
        if not isinstance(row, dict):
            continue
        cells, resolves = [], []
        for trig in ("never", "every-k", "drift"):
            d = row.get(trig)
            if isinstance(d, dict) and d.get("accuracy"):
                cells.append(f"{d['accuracy'][-1]:.3f}")
                if trig != "never":
                    resolves.append(str(len(d.get("replans", []))))
            else:
                cells.append("—")
                if trig != "never":
                    resolves.append("—")
        never = row.get("never", {})
        used = (f"{never['times'][-1]:.1f}"
                if isinstance(never, dict) and never.get("times") else "—")
        out.append(f"| {scn} | " + " | ".join(cells)
                   + f" | {'/'.join(resolves)} | {used} |")
    out.append("")
    return "\n".join(out)


def section_async_sweep() -> str:
    """Round-synchronous vs buffered semi-async aggregation under the same
    ``T_max`` (``benchmarks/async_sweep.py``): final accuracy per arm plus
    the carry-buffer staleness statistics of the buffered arms."""
    fn = os.path.join(RESULTS, "results", "async_sweep.json")
    if not os.path.exists(fn):
        return ""
    with open(fn) as f:
        res = json.load(f)
    out = ["### async_sweep (staleness-weighted delayed gradients, "
           "same T_max)\n",
           "carried in = buffered late contributions folded into a later "
           "round's update (weight lam**tau); stale_mean = their mean "
           "staleness in rounds; dropped = expired (> max_age) or "
           "ring-evicted.\n",
           "| scenario | adel-sync | salf-buffered | adel-buffered | "
           "carried in (salf/adel) | stale_mean | dropped |",
           "|---|---|---|---|---|---|---|"]
    for scn, row in sorted(res.items()):
        if not isinstance(row, dict):
            continue
        cells, carried, stale, dropped = [], [], [], []
        for arm in ("adel-sync", "salf-buffered", "adel-buffered"):
            d = row.get(arm)
            if isinstance(d, dict) and d.get("accuracy"):
                cells.append(f"{d['accuracy'][-1]:.3f}")
            else:
                cells.append("—")
            if arm != "adel-sync" and isinstance(d, dict):
                drift = (d.get("telemetry") or {}).get("drift", {})
                carried.append(str(drift.get("carried_in_total", "—")))
                if "stale_mean" in drift:
                    stale.append(f"{drift['stale_mean']:.2f}")
                dropped.append(str(drift.get("carried_dropped_total", "—")))
        out.append(f"| {scn} | " + " | ".join(cells)
                   + f" | {'/'.join(carried) or '—'}"
                   + f" | {'/'.join(stale) or '—'}"
                   + f" | {'/'.join(dropped) or '—'} |")
    out.append("")
    return "\n".join(out)


def section_telemetry() -> str:
    """Round-runtime telemetry recorded by the instrumented suites
    (``History.telemetry`` blocks inside ``fleet_smoke.json``).

    Columns: *predicted* is the exponential clock model's forecast
    (expected backprop depth ``E[min(z, L)]`` from the planned deadline,
    Eq. 5), *simulated* is the straggler-draw clock the runtime charges
    against ``T_max``, and *wall* is measured host time
    (``time.perf_counter``); the drift columns quantify how far the
    realized draws land from the model the Problem-2 solver planned with.
    """
    fn = os.path.join(RESULTS, "results", "fleet_smoke.json")
    if not os.path.exists(fn):
        return ""
    with open(fn) as f:
        res = json.load(f)
    rows = []
    for setting, methods in sorted(res.items()):
        if not isinstance(methods, dict):
            continue
        for method, d in sorted(methods.items()):
            tel = d.get("telemetry") if isinstance(d, dict) else None
            if not tel or not tel.get("drift"):
                continue
            drift = tel["drift"]
            phases = tel.get("phases", {})
            step = ("round_step", "local_train", "aggregate")
            train_s = sum(phases.get(p, {}).get("total_s", 0.0)
                          for p in step)
            other_s = sum(v.get("total_s", 0.0)
                          for k, v in phases.items() if k not in step)
            ctr = tel.get("counters", {})
            logical = ctr.get("aggregate_bytes_logical", 0)
            wire = ctr.get("aggregate_bytes_wire", 0)
            if wire:
                bytes_cell = (f"{_fmt_bytes(logical)}/{_fmt_bytes(wire)} "
                              f"({logical / wire:.1f}x)")
            else:
                bytes_cell = "—"
            rows.append(
                f"| {setting} | {method} | {drift.get('rounds', '—')} "
                f"| {train_s:.2f}/{other_s:.2f} "
                f"| {bytes_cell} "
                f"| {drift.get('depth_drift_mean', '—')} "
                f"| {drift.get('miss_rate', '—')} "
                f"| {drift.get('zero_rate', '—')} "
                f"| {drift.get('deadline_vs_full_wait', '—')} |")
    if not rows:
        return ""
    out = ["### telemetry (round-runtime phase spans + clock-model drift)\n",
           "predicted = exponential-model forecast at the planned deadline; "
           "simulated = straggler-draw clock charged against T_max; "
           "wall = measured host perf_counter time. depth_drift = realized "
           "minus predicted backprop depth (layers, mean over rounds); "
           "deadline_vs_full_wait = planned deadline as a fraction of the "
           "synchronized full-depth wait (the paper's Eq. 5 saving). "
           "bytes logical/wire = dense-float32 payload the aggregation "
           "consumed vs compressed bytes on the wire "
           "(repro.core.compression), with the reduction ratio.\n",
           "| setting | method | rounds | train/other wall_s "
           "| bytes logical/wire | depth_drift | miss_rate | zero_rate "
           "| T_t/full_wait |",
           "|---|---|---|---|---|---|---|---|---|"]
    out += rows
    out.append("")
    return "\n".join(out)


def section_repro() -> str:
    out = []
    for name in ("fig2_mnist", "fig3_cifar", "fig4_robustness",
                 "table2_budgets", "fleet_smoke", "fleet_scenarios"):
        fn = os.path.join(RESULTS, "results", f"{name}.json")
        if not os.path.exists(fn):
            continue
        with open(fn) as f:
            res = json.load(f)
        out.append(f"### {name}\n")
        out.append("| setting | " + " | ".join(
            ["adel", "salf", "drop", "wait", "heterofl"]) + " |")
        out.append("|---|---|---|---|---|---|")
        for setting, methods in res.items():
            if not isinstance(methods, dict):
                continue
            cells = []
            for m in ("adel", "salf", "drop", "wait", "heterofl"):
                d = methods.get(m)
                if isinstance(d, dict) and d.get("accuracy"):
                    cells.append(f"{d['accuracy'][-1]:.3f}")
                elif isinstance(d, dict) and "final_acc" in d:
                    cells.append(f"{d['final_acc']:.3f}")
                else:
                    cells.append("—")
            out.append(f"| {setting} | " + " | ".join(cells) + " |")
        out.append("")
    sweep = section_backend_sweep()
    if sweep:
        out.append(sweep)
    replan = section_replan_sweep()
    if replan:
        out.append(replan)
    async_ = section_async_sweep()
    if async_:
        out.append(async_)
    lm = section_lm_smoke()
    if lm:
        out.append(lm)
    tel = section_telemetry()
    if tel:
        out.append(tel)
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "dryrun", "roofline", "repro",
                             "telemetry"])
    args = ap.parse_args(argv)
    if args.section == "telemetry":
        print("## Round-runtime telemetry\n")
        print(section_telemetry())
        return
    if args.section in ("all", "dryrun"):
        print("## Dry-run records\n")
        print(section_dryrun())
        print()
    if args.section in ("all", "roofline"):
        print("## Roofline\n")
        print(section_roofline())
        print()
    if args.section in ("all", "repro"):
        print("## Reproduction results\n")
        print(section_repro())


if __name__ == "__main__":
    main()
