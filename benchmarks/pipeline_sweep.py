"""Pipeline sweep — serial vs prefetch round driver on two committed
configs, with the overlap accounting from the new pipeline counters.

The prefetch pipeline (``ExecSpec.pipeline="prefetch"``) overlaps round
``t+1``'s host planning/stacking with round ``t``'s device step, drains
eval scalars asynchronously, and AOT-warms the round step before the timed
loop. Two configs are swept, each in both modes:

* ``lm`` — the reduced-arch federated LM driver (same shape as
  ``lm_smoke``: dense backend, U=4, seq=32);
* ``fleet`` — a 10k-device parametric population with hierarchical
  two-tier aggregation (same shape as ``fleet_scale``'s smallest sweep).

Per mode the suite records total wall, steady-state s/round (for prefetch
the one-off ``warm_up_s`` counter is subtracted — it is reported
separately), final accuracy, and for prefetch the overlap fraction
(planning time hidden behind the device step over total wall). The two
modes must produce BIT-identical trajectories (``identical`` is asserted,
not just recorded); the headline ``speedup_pct`` compares steady-state
s/round. Emits ``experiments/results/pipeline_sweep.json`` plus one
telemetry stream per (config, mode) under ``events/``.
"""
from __future__ import annotations

from benchmarks.common import cached_result, events_path, save_result

ARCH = "qwen1.5-4b"
MODES = ("serial", "prefetch")


def _counters(hist) -> dict:
    return (hist.telemetry or {}).get("counters", {})


def _row(hist, wall: float, rounds: int, mode: str) -> dict:
    c = _counters(hist)
    warm = float(c.get("warm_up_s", 0.0)) if mode == "prefetch" else 0.0
    steady = max(wall - warm, 0.0)
    row = {
        "mode": mode,
        "rounds": rounds,
        "wall_s": round(wall, 4),
        # prefetch pays compile once in warm_up_s (reported below), so its
        # per-round number is the steady-state rate; serial's includes the
        # round-0 compile it cannot avoid
        "wall_per_round_s": round(steady / max(rounds, 1), 4),
        "final_acc": round(float(hist.accuracy[-1]), 4)
        if hist.accuracy else None,
        "accuracy": [round(float(a), 6) for a in hist.accuracy],
    }
    if mode == "prefetch":
        row["warm_up_s"] = round(warm, 4)
        row["prefetch_rounds"] = int(c.get("prefetch_rounds", 0))
        row["overlap_s"] = round(float(c.get("prefetch_overlap_s", 0.0)), 4)
        row["dispatch_wait_s"] = round(
            float(c.get("dispatch_wait_s", 0.0)), 4)
        row["overlap_frac"] = round(row["overlap_s"] / max(wall, 1e-9), 4)
        # bytes copied from host memory: the fleet's host-stacked cohorts;
        # the LM's cohort and plans already live on the device
        row["h2d_bytes"] = int(c.get("h2d_bytes", 0))
    return row


def _summarize(name: str, rows: dict) -> None:
    serial, prefetch = rows["serial"], rows["prefetch"]
    assert prefetch["accuracy"] == serial["accuracy"], \
        f"[pipeline_sweep] {name}: prefetch trajectory diverged from serial"
    rows["identical"] = True
    s, p = serial["wall_per_round_s"], prefetch["wall_per_round_s"]
    rows["speedup_pct"] = round(100.0 * (s - p) / max(s, 1e-9), 2)
    print(f"[pipeline_sweep] {name}: serial {s:.3f}s/round vs prefetch "
          f"{p:.3f}s/round (+{rows['speedup_pct']:.1f}%), "
          f"overlap={prefetch['overlap_s']:.3f}s "
          f"({100 * prefetch['overlap_frac']:.1f}% of wall), "
          f"warm_up={prefetch['warm_up_s']:.2f}s")


def run(quick: bool = False) -> dict:
    cached = cached_result("pipeline_sweep")
    if cached is not None:
        return cached
    from repro import obs
    from repro.data.synthetic import make_image_dataset
    from repro.fl.spec import ExecSpec
    from repro.fleet.engine import partition_fleet, run_fleet
    from repro.fleet.population import make_population
    from repro.launch.train import run_training
    from repro.models.paper_models import make_mlp

    result = {}

    lm_rounds = 6 if quick else 12
    lm = {}
    for mode in MODES:
        tracer = obs.make_tracer(events_path(f"pipeline_sweep.lm.{mode}"))
        t0 = obs.now()
        _, hist = run_training(
            ARCH, method="adel", rounds=lm_rounds, tmax=5.0 * lm_rounds,
            U=4, seq=32, eta0=1.0, seed=0, solver_steps=600, eval_every=1,
            verbose=False, exec=ExecSpec(pipeline=mode), tracer=tracer)
        lm[mode] = _row(hist, obs.now() - t0, lm_rounds, mode)
        tracer.close()
    _summarize("lm", lm)
    result["lm"] = lm

    fleet_rounds = 3 if quick else 5
    x_tr, y_tr, x_te, y_te = make_image_dataset(
        "mnist", n_train=800 if quick else 1600, n_test=300, seed=0,
        noise_std=1.0)
    data = partition_fleet(x_tr, y_tr, x_te, y_te, 64, alpha=0.5, seed=0)
    population = make_population(
        "parametric:longtail-mobile", size=10_000,
        availability="bernoulli", availability_kwargs=(("rate", 0.7),),
        regions=4)
    fleet = {}
    for mode in MODES:
        tracer = obs.make_tracer(events_path(f"pipeline_sweep.fleet.{mode}"))
        t0 = obs.now()
        _, hist = run_fleet(
            make_mlp(), population, data=data, method="adel",
            rounds=fleet_rounds, cohort_size=16, solver_steps=300,
            eval_every=1, seed=0, verbose=False,
            exec=ExecSpec(backend="hierarchical", regions=4, pipeline=mode),
            tracer=tracer)
        fleet[mode] = _row(hist, obs.now() - t0, fleet_rounds, mode)
        tracer.close()
    _summarize("fleet", fleet)
    result["fleet"] = fleet

    save_result("pipeline_sweep", result)
    return result


if __name__ == "__main__":
    run()
