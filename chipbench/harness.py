"""One run of a benchmark cell, driven by the cell's data files.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the ``ArchConfig`` fields as run, the source and the cut) and a traffic
mix (``traffic/<name>.json``: cohort, row and plan sizes, eval cadence,
the execution fields it sets). ``limits/<cell>.json`` holds the limit of
each number the correctness check compares, ``metrics/<name>.py`` one
reader per per-layer metric, and ``families/<family>.py`` the parameter
layout, forward pass, layer map and costs of the configuration's family.
Nothing here names a cell or a leaf.

A run:

1. set-up: build the task as ``repro.launch.train.run_training`` does
   (``AnalysisConfig.default`` from the traffic's ``profile_seed``, the
   Problem-2 ``solve``, ``make_policy``, ``probe_s_max`` capped by the
   traffic) and one ``RoundRuntime``; drive it through ``check_rounds``
   rounds from the seed's weights, recording each round's inputs, the
   change of the weights after the first and the last of them and the eval
   loss after each;
2. window: the same runtime, continuing from those weights, runs whole
   rounds until ``--seconds`` have passed; the benchmark's policy wrapper
   then hands the runtime a plan past ``T_max``, which ends ``run`` (a run
   that ends on the plan's own ``T_max`` or on its last round fails);
3. after the window, with the program's state freed: the plain reference
   (``reference.py``) reruns the checked rounds on their own inputs and
   the compared numbers are set beside their limits.

The benchmark wraps only public interfaces: the policy, the cohort
source, ``backend.run_round``, ``eval_fn`` and ``ModelAPI.init`` (which
hands the runtime the benchmark's weights). Each wrapper opens a
``jax.profiler.TraceAnnotation`` (``chipbench.<layer>``), which the
per-layer readers find in the ``--trace 1`` run; the program's own tracer
stays off, since it synchronises every round.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, inputs, reference, tracefile  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the compared numbers, in the order they are printed (the eval-loss gap is
# reported beside them but has no upper reading to set a limit from)
CHECKS = ("grad1_gap", "change_gap")


def load_json(p: Path) -> dict:
    with open(p) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # limits/<cell>.json (may be empty while calibrating)
    per_layer: list       # BENCHMARK.json per_layer entries for this cell

    @property
    def arch(self) -> dict:
        return self.config["arch"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              data_dir: Path = BENCH) -> Cell:
    """The cell ``name`` of ``bench_file``: configuration files resolve
    against the benchmark file's directory, traffic and limits files under
    ``data_dir``."""
    bench_file = Path(bench_file)
    bench = load_json(bench_file)
    (w,) = [w for w in bench["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise SystemExit(f"chipbench: no workload {name!r} in {bench_file}")
    (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    limits_file = Path(data_dir) / "limits" / f"{name}.json"
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(bench_file.parent / c["file"]),
                traffic=load_json(Path(data_dir) / "traffic"
                                  / f"{w['traffic']}.json"),
                limits=(load_json(limits_file) if limits_file.exists()
                        else {}),
                per_layer=per_layer)


def reader(metric: str):
    """The per-layer metric's reader module ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def note(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _span(layer: str, **kw):
    """A host span in the profiler's trace (next to free when none is
    taken)."""
    return jax.profiler.TraceAnnotation(f"chipbench.{layer}", **kw)


class PlanRecorder:
    """Wraps the policy: records each plan, and past the window's deadline
    returns a plan longer than any budget, which stops ``run``."""

    def __init__(self, policy):
        self.inner = policy
        self.deadline = None
        self.stopped = False
        self.last = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def round(self, key, t, view=None):
        from repro.core.baselines import RoundPlan
        with _span("plan", t=t):
            if self.deadline is not None and time.perf_counter() >= \
                    self.deadline:
                self.stopped = True
                return RoundPlan(mask=None, p=None, batch_sizes=None,
                                 elapsed=math.inf, bias_correct=True)
            self.last = self.inner.round(key, t, view=view)
            return self.last


class CohortSpans:
    """Wraps the cohort source with a ``cohort`` span."""

    def __init__(self, source):
        self.inner = source

    @property
    def cohort_size(self) -> int:
        return self.inner.cohort_size

    def round_cohort(self, t):
        with _span("cohort", t=t):
            return self.inner.round_cohort(t)


class Bench:
    """Set-up, checked rounds, window and reference check of one cell."""

    def __init__(self, cell: Cell):
        from repro.configs.base import ArchConfig
        from repro.core.baselines import make_policy
        from repro.core.scheduler import solve
        from repro.core.types import AnalysisConfig
        from repro.fl.runtime import RoundRuntime, probe_s_max
        from repro.fl.spec import ExecSpec
        from repro.fl.tasks import lm_eval_metrics, make_lm_model

        self.cell, tr = cell, cell.traffic
        self.a = cell.arch
        self.cfg = ArchConfig(**self.a)
        self.model = make_lm_model(self.cfg)
        self._lm_eval = lm_eval_metrics
        shapes = jax.eval_shape(self.model.init,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        ours = jax.eval_shape(lambda: inputs.make_weights(self.a, 0))
        if jax.tree.structure(shapes) != jax.tree.structure(ours) or any(
                (x.shape, x.dtype) != (y.shape, y.dtype) for x, y in
                zip(jax.tree.leaves(shapes), jax.tree.leaves(ours))):
            raise SystemExit(
                "chipbench: the program's parameter layout is not the one "
                f"families/{self.a['family']}.py writes down")
        self.n_params = sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(shapes))
        self.spec = ExecSpec(**tr["exec"])
        self.U, self.seq = tr["clients"], tr["seq"]
        acfg = AnalysisConfig.default(
            U=self.U, L=self.model.L, R=tr["plan_rounds"], T_max=tr["T_max"],
            eta0=tr["eta0"], seed=tr["profile_seed"])
        if self.spec.compression.mode != "none":
            acfg = dataclasses.replace(
                acfg, comm_scale=self.spec.compression.wire_scale(),
                bytes_full=4.0 * self.n_params)
        self.acfg = acfg
        policy = make_policy(tr["method"], acfg,
                             schedule=solve(acfg, "adam"))
        # run_training's cap, without its floor of 2: a cell's cap holds
        self.s_max = max(min(probe_s_max(policy, tr["plan_rounds"]),
                             tr["s_max_cap"], 4 * tr["rows_per_client"]), 1)
        self.policy = PlanRecorder(policy)
        self.runtime = RoundRuntime(self.model, self.policy, exec=self.spec)
        self.backend = self.runtime.backend
        self.U_pad = self.backend.cohort_pad(self.U)
        self._run_round = self.backend.run_round
        self.backend.run_round = self._round
        self.model.init = self._init
        self.executed: list = []      # the plan of every executed round
        self.evals: list = []         # (acc, loss) device scalars
        self.capture = None           # per-round hook of the checked rounds
        self.compiles = 0
        self._counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)

    # -- wrappers on public interfaces -----------------------------------
    def _on_compile(self, event, duration, **kw):
        if event == COMPILE_EVENT and self._counting:
            self.compiles += 1

    def _init(self, key):
        return self._start_params

    def _round(self, params, xb, yb, wb, mask, p, eta, **kw):
        with _span("round_step"):
            out = self._run_round(params, xb, yb, wb, mask, p, eta, **kw)
        self.executed.append(self.policy.last)
        if self.capture is not None:
            self.capture(len(self.executed), xb, eta, out)
        return out

    def _eval(self, params):
        with _span("eval"):
            r = self._lm_eval(self.model, params, self._eval_rows)
        self.evals.append(r)
        return r

    # -- the run -----------------------------------------------------------
    def start(self, seed: int) -> None:
        """Make the seed's inputs (weights on the device, token rows)."""
        from repro.fl.runtime import StaticCohortSource
        tr = self.cell.traffic
        rows, ev = inputs.make_tokens(self.a, tr, seed)
        n = tr["rows_per_client"]
        self.source = CohortSpans(StaticCohortSource(
            jnp.asarray(rows), jnp.zeros((self.U, n), jnp.int32),
            jnp.full((self.U,), n, jnp.int32)))
        self._eval_rows = jnp.asarray(ev)
        self.eval_rows_host = ev
        self.seed = seed
        self._start_params = inputs.make_weights(self.a, seed)
        self.key = inputs.seed_key(seed)

    def _run(self, rounds: int, key, eval_every: int):
        tr = self.cell.traffic
        return self.runtime.run(
            self.source, rounds=rounds, T_max=tr["T_max"], eta=self.acfg.eta,
            s_max=self.s_max, key=key, eval_fn=self._eval,
            eval_every=eval_every)

    def checked_rounds(self) -> dict:
        """The first ``check_rounds`` rounds, through the window's own call:
        their inputs, the program's weight change after the first and the
        last, and its eval loss after each. Leaves the weights to the
        window."""
        k = self.cell.traffic["check_rounds"]
        rec = {"rounds": []}

        def capture(i, xb, eta, out):
            plan = self.policy.last
            rec["rounds"].append({
                "xb": np.asarray(jax.device_get(xb))[:self.U],
                "batch": np.asarray(plan.batch_sizes),
                "mask": np.asarray(plan.mask),
                "p": np.asarray(plan.p), "eta": float(eta)})
            if i in (1, k):
                w0 = inputs.make_weights(self.a, self.seed)
                rec["grad1" if i == 1 else "change"] = \
                    reference.change_norms(out, w0, self.a)
                del w0

        self.capture = capture
        self.executed.clear()
        self.evals.clear()
        params, _ = self._run(k, jax.random.fold_in(self.key, 1), 1)
        self.capture = None
        rec["loss"] = [float(loss) for _, loss in self.evals]
        self._start_params = params
        return rec

    def window(self, seconds: float, trace_dir: str | None = None) -> dict:
        """Whole rounds for ``seconds`` from the checked rounds' weights."""
        tr = self.cell.traffic
        self.executed.clear()
        self.evals.clear()
        self.compiles = 0
        prof = None
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            prof = jax.profiler.trace(trace_dir, profiler_options=opts)
            prof.__enter__()
        try:
            with _span("window"):
                self._counting = True
                t0 = time.perf_counter()
                self.policy.deadline = t0 + seconds
                params, _ = self._run(tr["plan_rounds"],
                                      jax.random.fold_in(self.key, 2),
                                      tr["eval_every"])
                jax.block_until_ready(params)
                t1 = time.perf_counter()
                self._counting = False
        finally:
            self.policy.deadline = None
            if prof is not None:
                prof.__exit__(None, None, None)
        stopped, self.policy.stopped = self.policy.stopped, False
        self._start_params = None
        del params
        n = len(self.executed)
        real = [np.minimum(np.asarray(p.batch_sizes, np.float64)[:self.U],
                           self.s_max) for p in self.executed]
        return {"t0": t0, "seconds": t1 - t0, "rounds": n,
                "stopped_on_clock": stopped, "compiles": self.compiles,
                "tokens": float(sum(r.sum() for r in real)) * self.seq,
                "real_rows": float(sum(r.sum() for r in real)),
                "padded_rows": float(n * self.U_pad * self.s_max),
                "evals": len(self.evals),
                "flops": [flops.round_flops(
                    self.a, self.seq, np.asarray(p.batch_sizes)[:self.U],
                    np.asarray(p.mask)[:self.U], self.s_max)
                    for p in self.executed]}

    def compiled_peak(self) -> int:
        """Compiled peak bytes of the round step at the run's shapes, from
        the backend's jitted step (``memory_stats`` does not count the
        step's temporaries on this runtime)."""
        step = getattr(self.backend, "_step", None)
        if step is None:
            raise SystemExit("chipbench: the backend exposes no jitted round "
                             "step to read the compiled peak from")
        sds = jax.ShapeDtypeStruct
        L = self.model.L
        params = jax.eval_shape(lambda: inputs.make_weights(self.a, 0))
        args = (params, sds((self.U_pad, self.s_max, self.seq + 1), jnp.int32),
                sds((self.U_pad, self.s_max), jnp.int32),
                sds((self.U_pad, self.s_max), jnp.float32),
                sds((self.U_pad, L), jnp.float32), sds((L,), jnp.float32),
                sds((), jnp.float32), None)
        m = step(True, False).lower(*args).compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self._start_params = None
        self.source = None
        self._eval_rows = None
        gc.collect()


def reference_numbers(a: dict, seed: int, rec: dict,
                      s_max: int, eval_rows: np.ndarray, *,
                      wire: str, precision: str = "f32",
                      keep_rows: float = 1.0) -> dict:
    """Run the reference (or, with ``precision``/``keep_rows``, the control
    or a fault put in the program's place) over the checked rounds' inputs:
    its weight change after the first and last round, and its eval loss
    after each."""
    ref = reference.Reference(a, wire=wire, precision=precision)
    w = inputs.make_weights(a, seed)
    w0 = inputs.make_weights(a, seed)
    out = {"loss": []}
    for i, r in enumerate(rec["rounds"], 1):
        w = ref.round(w, r["xb"], r["batch"], r["mask"], r["p"], r["eta"],
                      s_max, keep_rows=keep_rows)
        out["loss"].append(ref.eval_loss(w, eval_rows))
        if i == 1:
            out["grad1"] = reference.change_norms(w, w0, a)
    out["change"] = reference.change_norms(w, w0, a)
    del w, w0
    gc.collect()
    return out


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers of the program (or a stand-in) against the
    reference: worst-leaf gap of the first round's change (the first
    gradient as the server step applies it, times eta) and of the change
    after the last checked round, and the largest relative eval-loss gap."""
    keep = reference.moving_leaves(ref["grad1"])
    g1, leaf1 = reference.norm_gap(prog["grad1"], ref["grad1"], keep)
    gk, leafk = reference.norm_gap(prog["change"], ref["change"], keep)
    lg = max(abs(x - y) / abs(y) for x, y in zip(prog["loss"], ref["loss"]))
    return {"grad1_gap": g1, "change_gap": gk, "loss_gap": lg,
            "worst_leaf_grad1": leaf1, "worst_leaf_change": leafk,
            "leaves_compared": len(keep), "leaves": len(ref["grad1"])}
