"""End-to-end federated LM training driver (ADEL-FL on an assigned arch).

A thin front-end over the unified round runtime: the arch config becomes a
:func:`repro.fl.tasks.lm_task` (transformer ``ModelAPI`` + synthetic token
streams + token-loss eval), and the round loop is
:class:`repro.fl.runtime.RoundRuntime` — the SAME loop that serves the
image and fleet workloads — so the paper's full pipeline (Problem-2
schedule -> per-round straggler draws (B1-B3) -> deadline-truncated
layer-wise aggregation (Eq. 5) -> SGD) plus online re-planning, every
execution backend (``dense`` / ``chunked`` / ``shard_map`` / ``temporal``
— the grad-accumulation client layout required for the big archs — /
``buffered``, the semi-async delayed-gradient backend), and HeteroFL
width scaling all work on LM configs with no LM-specific loop code. The
execution surface is one :class:`repro.fl.spec.ExecSpec` (``exec=`` /
the shared ``--backend/--compression/--lam/...`` CLI group).
Checkpointing rides the runtime's ``on_round`` hook.

``--reduced`` (the default) trains the CPU toy of the config, with every
width cut (:meth:`repro.configs.base.ArchConfig.reduced`). ``--full`` trains
the config as the registry gives it: for ``qwen1.5-4b`` that is one TPU v5e
chip's share of the model at its published widths (4 layers, 1/8 of the
vocabulary; :mod:`repro.configs.qwen1_5_4b`), which fits one chip on the
``temporal`` backend. ``chip_smoke.py`` at the repository root runs it.

    PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b \
        --method adel --rounds 60 --tmax 240 --backend temporal
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math

import jax
import numpy as np

from repro import obs
from repro.checkpoint import save_checkpoint
from repro.configs import get_config
from repro.core.baselines import make_policy
from repro.core.replan import TRIGGERS, ReplanConfig
from repro.core.scheduler import solve
from repro.core.types import AnalysisConfig
from repro.fl.runtime import History, RoundRuntime, probe_s_max
from repro.fl.spec import ExecSpec
from repro.fl.tasks import lm_task
from repro.fleet.population import PopulationSpec
from repro.launch.compile_cache import use_compile_cache


def run_training(arch: str, *, method: str = "adel", rounds: int = 40,
                 tmax: float = 160.0, U: int = 8, seq: int = 64,
                 n_seq: int = 96, eta0: float = 0.5, seed: int = 0,
                 reduced: bool = True, solver: str = "adam",
                 solver_steps: int | None = None,
                 exec: ExecSpec | None = None,
                 backend: str | None = None, chunk_size: int | None = None,
                 mesh=None, replan=None, local_iters: int | None = None,
                 donate: bool | None = None,
                 compression=None, agg_impl: str | None = None,
                 population=None,
                 s_max_cap: int = 32, eval_every: int | None = None,
                 ckpt: str | None = None, ckpt_every: int | None = None,
                 verbose: bool = True, tracer=None) -> tuple[object, History]:
    """Federated LM training on ``RoundRuntime``; returns ``(params,
    History)`` — ``History.accuracy`` is next-token accuracy and
    ``History.train_loss`` the token CE over a fixed in-pool eval head
    (perplexity = exp; see :func:`repro.fl.tasks.lm_task` for why the
    synthetic stream has no meaningful held-out split).

    HOW rounds execute is one :class:`repro.fl.spec.ExecSpec` (``exec=``):
    backend choice (``dense`` default; ``temporal`` is the big-arch
    grad-accumulation layout, ``buffered`` the semi-async delayed-gradient
    backend), ``chunk_size`` / ``mesh``, ``local_iters``, donation,
    ``compression`` / ``agg_impl``, and the staleness knobs. The
    individual kwargs remain as deprecated aliases; both forms funnel
    through :meth:`ExecSpec.resolve` (bit-identical either way). The
    spec's ``compression`` is priced into the Problem-2 plan before
    solving.

    ``replan`` selects the online re-planning trigger (None | "never" |
    "every-k" | "drift" | :class:`repro.core.replan.ReplanConfig`),
    ``ckpt`` a checkpoint path saved every ``ckpt_every`` rounds (default
    R/4) through the runtime's ``on_round`` hook, ``tracer`` a
    :class:`repro.obs.Tracer` for structured telemetry (phase spans +
    clock-model ledger in ``History.telemetry``).

    ``population`` (None by default) switches WHO the LM trains against:
    a :class:`repro.fleet.population.PopulationSpec` / source string /
    :class:`Population` routes the run through
    :func:`repro.fleet.engine.run_fleet` — per-round availability and
    cohort sampling over a simulated device fleet (lazy parametric
    populations scale to millions of devices) instead of the static
    ``U``-client pool. The cohort size stays ``U``; ``ckpt`` is not
    supported on the fleet path.
    """
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()

    spec = ExecSpec.resolve(exec, backend=backend, chunk_size=chunk_size,
                            mesh=mesh, local_iters=local_iters,
                            donate=donate, compression=compression,
                            agg_impl=agg_impl)
    if population is not None:
        if ckpt:
            raise ValueError("ckpt= is not supported on the fleet "
                             "(population=) path")
        from repro.fl.tasks import (lm_eval_metrics, lm_fleet_data,
                                    make_lm_model)
        from repro.fleet.engine import run_fleet
        from repro.fleet.population import make_population
        pop = make_population(population)
        model = make_lm_model(cfg)
        # virtual sharding: device id mod shards, so million-device
        # populations never materialize per-device token arrays
        data = lm_fleet_data(cfg, min(pop.size, 1024), seq=seq,
                             rows_per_device=max(n_seq // U, 4), seed=seed)
        return run_fleet(
            model, pop, data=data, method=method, rounds=rounds,
            T_max=tmax, cohort_size=U, exec=spec, eta0=eta0,
            solver=solver, solver_steps=solver_steps or 600,
            eval_every=eval_every or max(rounds // 20, 1), seed=seed,
            verbose=verbose, replan=replan, eval_metrics=lm_eval_metrics,
            tracer=tracer)
    task = lm_task(cfg, U=U, seq=seq, n_seq=n_seq, seed=seed)
    acfg = AnalysisConfig.default(U=U, L=task.model.L, R=rounds, T_max=tmax,
                                  eta0=eta0, seed=seed)
    comp = spec.compression
    if comp.mode != "none":
        # price the compressed wire into the Problem-2 plan: B_u shrinks by
        # the wire ratio, so the solved schedule re-spends the freed
        # deadline budget on larger batches (Schedule.batch_sizes / B_eff)
        import dataclasses as _dc
        n_params = sum(int(np.prod(l.shape)) for l in
                       jax.tree.leaves(jax.eval_shape(
                           task.model.init,
                           jax.ShapeDtypeStruct((2,), np.uint32))))
        acfg = _dc.replace(acfg, comm_scale=comp.wire_scale(),
                           bytes_full=4.0 * n_params)
    schedule = None
    if method == "adel":
        kw = {"steps": solver_steps} if (solver == "adam"
                                         and solver_steps) else {}
        schedule = solve(acfg, solver, **kw)
    policy = make_policy(method, acfg, schedule=schedule)
    # the minibatch pad width prices EVERY client's round compute and
    # activation memory at O(s_max) sequences, so cap it: larger planned
    # batches are clipped by the sampler (only the straggler clock keeps
    # the full B3 batch). At the qwen1.5-4b chip share with seq=512 a cap
    # of 8 fits one v5e on the temporal backend; 32 does not
    s_max = max(min(probe_s_max(policy, rounds), s_max_cap,
                    4 * task.n_per_client), 2)

    runtime = RoundRuntime(task.model, policy, exec=spec, tracer=tracer)

    on_round = None
    if ckpt:
        every = ckpt_every or max(rounds // 4, 1)

        def on_round(t, params, hist):
            if (t + 1) % every == 0 or t == rounds - 1:
                save_checkpoint(ckpt, params, step=t + 1,
                                meta={"arch": cfg.name, "method": method,
                                      "backend": spec.backend})

    params, hist = runtime.run(
        task.source(), rounds=rounds, T_max=tmax, eta=acfg.eta, s_max=s_max,
        key=jax.random.PRNGKey(seed), eval_fn=task.eval_fn(),
        eval_every=eval_every or max(rounds // 20, 1), verbose=verbose,
        method=method, replan=replan, on_round=on_round)
    if ckpt and (not hist.rounds or hist.rounds[-1] < rounds):
        # budget exhausted before the last planned round: persist the final
        # params the periodic hook may have missed
        save_checkpoint(ckpt, params, step=hist.rounds[-1] if hist.rounds
                        else 0, meta={"arch": cfg.name, "method": method,
                                      "backend": spec.backend})
    return params, hist


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="hymba-1.5b")
    ap.add_argument("--method", default="adel",
                    choices=["adel", "salf", "drop", "wait", "heterofl"])
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--tmax", type=float, default=160.0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--eta0", type=float, default=0.5)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="the config's CPU toy, every width cut "
                         "(default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the config as registered, at published widths "
                         "(qwen1.5-4b: one v5e chip's share) — TPU only")
    ap.add_argument("--replan", default=None, choices=list(TRIGGERS),
                    help="online re-planning trigger (repro.core.replan)")
    ap.add_argument("--replan-every", type=int, default=None,
                    help="every-k re-plan period")
    # the shared execution-spec flag block (--backend / --chunk-size /
    # --no-donate / --compression / --agg-impl / --lam / ...) — one
    # surface with repro.fleet.scenarios, derived from repro.fl.spec
    ExecSpec.add_cli_args(ap)
    # ... and the shared population flag block (--population / --fleet-size
    # / --availability / --regions): any of these set routes the run over a
    # simulated device fleet via repro.fleet.engine.run_fleet
    PopulationSpec.add_cli_args(ap)
    ap.add_argument("--solver", default="adam",
                    choices=["adam", "trust-constr"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="write the structured telemetry stream (phase "
                         "spans, clock-model ledger) to this JSONL file; "
                         "render with python -m repro.obs.timeline")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the whole "
                         "run into DIR (view with TensorBoard / Perfetto), "
                         "with the program's repro.* spans on its clock; "
                         "--events then records without syncing, so the "
                         "trace shows the real overlap. A profiler that "
                         "fails fails the run")
    args = ap.parse_args(argv)
    use_compile_cache()
    replan = args.replan
    if replan is not None and args.replan_every is not None:
        replan = ReplanConfig(trigger=replan, every=args.replan_every)
    spec = ExecSpec.from_cli(args)
    pop_flags = (args.population, args.fleet_size, args.availability,
                 args.regions)
    pspec = (PopulationSpec.from_cli(args)
             if any(v is not None for v in pop_flags) else None)
    # under the profiler the device timeline comes from the trace: a tracer
    # that synced every round would serialize what the trace should show
    tracer = obs.make_tracer(args.events, sync=not args.profile_dir)
    t0 = obs.now()
    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with profile:
        _, hist = run_training(args.arch, method=args.method,
                               rounds=args.rounds,
                               tmax=args.tmax, U=args.clients, eta0=args.eta0,
                               seq=args.seq, seed=args.seed,
                               reduced=args.reduced, solver=args.solver,
                               exec=spec, replan=replan, population=pspec,
                               ckpt=args.ckpt, tracer=tracer)
    tracer.close()
    if args.profile_dir:
        print(f"[train] device trace -> {args.profile_dir}")
    loss = hist.train_loss[-1]
    print(f"[train] done in {obs.now() - t0:.1f}s wall; "
          f"final token loss {loss:.4f} (ppl {math.exp(min(loss, 30)):.1f}, "
          f"token acc {hist.accuracy[-1]:.4f})")
    if args.events:
        print(f"[train] telemetry -> {args.events} "
              f"(render: python -m repro.obs.timeline {args.events})")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({**hist.as_dict(), "arch": args.arch,
                       "backend": spec.backend,
                       "exec": spec.as_dict()}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
