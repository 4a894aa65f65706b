"""Unified federated round runtime — ONE round loop for every workload.

One :class:`RoundRuntime` owns everything a federated round loop needs,
independent of the task being trained (image classification, synthetic
fleet workloads, big-arch LM token streams) and of how the cohort's
compute is executed:

* per-round policy planning through the ``view=`` kwarg of
  :meth:`repro.core.baselines.Policy.round`,
* cohort stacking / padding to jit-stable fixed shapes (padded rows carry
  an all-zero mask, batch size 1, and zero data, so they contribute 0),
* ``s_max`` probing (:func:`probe_s_max`, vectorized over the FULL
  schedule so non-monotone re-planned deadline tails can never plan a
  batch the executor would silently clip),
* HeteroFL width-mask derivation (cached per distinct width-ratio vector),
* the simulated wall-clock under Requirements R1 (max R rounds) and
  R2 (total time <= T_max),
* online re-planning (:mod:`repro.core.replan`), including crediting the
  un-spent deadline of skipped empty rounds back to the next re-solve,
* eval cadence and the :class:`History` record.

The three axes of variation are all pluggable:

* WHAT is trained is a task adapter (:mod:`repro.fl.tasks`): a
  :class:`~repro.fl.tasks.Task` bundles a :class:`ModelAPI`, a data source
  (classification ``(U, n, feat)`` arrays or LM token rows
  ``(U, n, seq+1)`` with shifted-label batching inside the model's loss),
  and eval metrics (classification accuracy vs token accuracy /
  perplexity) — supplied to :meth:`RoundRuntime.run` as ``eval_fn``.
* HOW a round executes is an :class:`repro.fl.backends.ExecutionBackend`
  (``dense`` / ``chunked`` / ``shard_map`` / ``temporal`` / ``buffered`` /
  ``hierarchical``), selected through one
  :class:`repro.fl.spec.ExecSpec`; all of them donate
  the incoming ``params`` buffers to the round step. Stateful backends
  (the buffered semi-async carry buffer) additionally receive a
  :class:`RoundContext` each round — the simulated clock span plus the
  straggler-model rates — so in-flight work can cross round boundaries.
* WHERE the clients come from is a cohort source:
  :class:`StaticCohortSource` replays one pre-stacked population every
  round (``repro.fl.server.run_federated`` and the LM driver
  ``repro.launch.train``), while the fleet engine's source samples
  availability + cohort per round (``repro.fleet.engine.run_fleet``).

Per-round observers (checkpointing, logging) hook in via the ``on_round``
callback of :meth:`RoundRuntime.run`. Policies, width masks, availability
models, and re-planning are therefore written once and work under every
backend and every task.

Observability flows through the single ``tracer=`` hook
(:mod:`repro.obs`, default :data:`repro.obs.NULL_TRACER` — zero overhead,
bit-identical trajectories): the runtime emits nestable phase spans
(``cohort`` / ``replan`` / ``plan`` / ``stack`` / ``eval`` /
``checkpoint``; the execution backends add ``round_step``, or
``local_train`` / ``aggregate``), typed counters (padded-vs-real batch
elements, bytes copied from host memory), and one clock-model ledger
event per executed round (:func:`repro.obs.ledger.round_record`: planned
deadline vs simulated clock vs measured wall time vs the exponential
model's predicted straggler depths). ``verbose=True`` renders from the
same records via :mod:`repro.obs.format`, so printed and recorded numbers
cannot drift apart; the aggregate lands in ``History.telemetry``.

Every phase is also a ``repro.<phase>`` profiler annotation, tracer or
not (:func:`repro.obs.annotate`), nested as ``repro.run`` ⊃
``repro.round`` (``round=t``) ⊃ the phases, with the policies'
``repro.draw`` inside ``repro.plan``; the prefetch worker's phases sit
under its own ``repro.prefetch`` root. A profiler trace thus places the
program's own spans on the device trace's clock.

Pipelined execution (``ExecSpec.pipeline``) — the round loop runs in one
of two modes, with bit-identical trajectories:

* ``"serial"`` (default): plan round t, execute round t, repeat — the
  classic loop.
* ``"prefetch"``: a one-round-lookahead driver. Every host-only phase of
  round t+1 — ``cohort`` sampling, the replan trigger + re-solve, the
  PRNG key splits, the policy ``plan``, the ``T_max`` stop check, and the
  minibatch ``stack`` (the draw and any H2D transfer) — runs on a worker
  thread while round t's ``backend.run_round`` is in flight on the
  device. Those phases read only sequential host state (source RNG,
  schedule, replanner, the PLANNED clock — ``plan.elapsed`` is known
  before execution), never round t's device results, which is what makes
  the speculation exact. The two things that do read live state stay on
  the main thread at consume time: HeteroFL width masks (need current
  ``params``) and all telemetry emission (the worker only opens its
  profiler annotations and collects timings; see
  :meth:`repro.obs.Tracer.span_record`). The prefetcher
  keeps at most two rounds of stacked ``(xb, yb, wb, mask)`` buffers
  alive (the in-flight round's and the prefetched round's — a double
  buffer whose slots are dropped right after dispatch), and it never
  touches ``params``, so round-step donation stays safe. After a skipped
  round or a replan event the next round is planned inline (serial
  fallback) — those rounds change the planning state the speculation
  would have had to guess. Prefetch mode also AOT-warms the backend's
  round step and the eval step (``backend.warm_up`` + one dummy eval)
  before dispatching round 0, so first-round trace/compile cost moves
  out of the measured round loop. Eval becomes non-blocking in BOTH
  modes: ``eval_fn`` returns device scalars that sit in a pending ring
  and are materialized to ``History`` floats only at report boundaries
  (a rendered eval record, a replan event, an ``on_round`` hook, end of
  run) — the only hard syncs left are the ones an active tracer with
  ``sync=True`` explicitly inserts. Counters: ``h2d_bytes`` (bytes the
  runtime copies from host memory to the device per round: the cohort,
  plan and learning-rate arrays that are not device arrays already),
  ``prefetch_overlap_s`` (worker planning time hidden behind device
  execution), ``dispatch_wait_s`` (main-thread stalls on the prefetch
  future), ``warm_up_s``.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.baselines import Policy, RoundPlan
from repro.core.replan import Replanner, make_replan
from repro.fl.backends import make_backend
from repro.fl.client import sample_client_batches
from repro.fl.spec import ExecSpec

PyTree = Any

__all__ = ["ModelAPI", "History", "Cohort", "StaticCohortSource",
           "RoundContext", "RoundRuntime", "probe_s_max", "evaluate",
           "eval_metrics"]


@dataclasses.dataclass
class ModelAPI:
    """Minimal model interface consumed by the FL runtime."""

    init: Callable[[jax.Array], PyTree]
    loss: Callable[[PyTree, jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    predict: Callable[[PyTree, jnp.ndarray], jnp.ndarray]
    layer_ids: Callable[[PyTree], PyTree]
    L: int
    name: str = "model"
    # HeteroFL support: width_masks(params, ratios (U,)) -> pytree with leading U axis
    width_masks: Optional[Callable[[PyTree, np.ndarray], PyTree]] = None


@dataclasses.dataclass
class History:
    times: list = dataclasses.field(default_factory=list)
    rounds: list = dataclasses.field(default_factory=list)
    accuracy: list = dataclasses.field(default_factory=list)
    deadlines: list = dataclasses.field(default_factory=list)
    train_loss: list = dataclasses.field(default_factory=list)
    # fleet runs only: reachable-device count per executed round
    available: list = dataclasses.field(default_factory=list)
    # online re-planning only: one record per mid-run re-solve
    # (round, reachable N, re-estimated U, new T tail, new m, ...)
    replans: list = dataclasses.field(default_factory=list)
    method: str = ""
    # tracer-enabled runs only: the run's telemetry summary — per-phase
    # wall totals, counter totals, the per-round clock-model ledger, and
    # its drift statistics (repro.obs.Tracer.summary)
    telemetry: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        """JSON-round-trippable dict (``json.dump``-able as-is).

        ``replans`` entries are converted through their own ``as_dict``
        when they are :class:`repro.core.replan.ReplanEvent` dataclasses —
        ``dataclasses.asdict`` recursion would also swallow jax/numpy
        leaves elsewhere and silently deep-copies every list, so the
        conversion is explicit and shallow.
        """
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["replans"] = [r.as_dict() if hasattr(r, "as_dict") else r
                        for r in self.replans]
        return d


def _jit_predict(model: ModelAPI):
    """One jit wrapper per ModelAPI instance, reused across every eval call
    (a fresh ``jax.jit(model.predict)`` per call would retrace each time)."""
    fn = getattr(model, "_predict_jit", None)
    if fn is None:
        fn = jax.jit(model.predict)
        model._predict_jit = fn
    return fn


def evaluate(model: ModelAPI, params: PyTree, x: jnp.ndarray, y: jnp.ndarray,
             batch: int = 512) -> jnp.ndarray:
    """Full test-set accuracy as a DEVICE scalar (no host sync).

    Per-batch correct counts accumulate on-device, so every predict batch
    dispatches asynchronously and the caller decides when (if ever) to
    block — the round runtime defers the conversion to report boundaries.
    ``float()`` the result for a Python number.
    """
    n = x.shape[0]
    predict = _jit_predict(model)
    correct = jnp.int32(0)
    for i in range(0, n, batch):
        logits = predict(params, x[i:i + batch])
        correct = correct + (jnp.argmax(logits, -1) == y[i:i + batch]).sum()
    return correct / float(n)


def eval_metrics(model: ModelAPI, params: PyTree, test_x: jnp.ndarray,
                 test_y: jnp.ndarray, *, loss_samples: int = 256
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(accuracy over the full test set, mean loss over a fixed head),
    both device scalars — no host sync (see :func:`evaluate`)."""
    acc = evaluate(model, params, test_x, test_y)
    n = min(loss_samples, int(test_y.shape[0]))
    loss = model.loss(params, test_x[:n], test_y[:n],
                      jnp.full((n,), 1.0 / n, jnp.float32))
    return acc, loss


def probe_s_max(policy: Policy, rounds: int, *, view=None) -> int:
    """Largest batch size the policy can plan over the FULL horizon, so
    per-client minibatches can be padded to one fixed width.

    Schedule-driven policies (ADEL) are probed with one vectorized
    ``Schedule.batch_sizes`` evaluation over EVERY round's deadline — a
    re-planned schedule need not be monotone, so probing only the
    endpoints could under-estimate a mid-schedule peak and silently clip
    batches. Fixed-deadline policies plan the same batch every round and
    keep the cheap endpoint probe.
    """
    cfg = policy._resolve(view) if hasattr(policy, "_resolve") else view
    sch = getattr(policy, "schedule", None)
    R = max(int(rounds), 1)
    if sch is not None and cfg is not None and len(np.asarray(sch.T)) >= R:
        S = sch.batch_sizes(cfg)[:R]            # (R, U), all rounds at once
        return int(S.max())
    probe = [policy.round(jax.random.PRNGKey(0), t, view=view)
             for t in (0, max(rounds - 1, 0))]
    return int(max(float(jnp.max(pl.batch_sizes)) for pl in probe))


@dataclasses.dataclass
class Cohort:
    """One round's stacked client data, as produced by a cohort source.

    ``x``: (U_act, n_pad, ...) inputs — trailing dims are task-defined
    (``(feat...)`` for classification, ``(seq+1,)`` token rows for LM);
    ``y``: (U_act, n_pad) labels (all-zero for tasks whose loss derives
    labels from ``x``), ``counts``: (U_act,) valid samples per client.
    ``view`` is the per-round AnalysisConfig the policy should plan against
    (None keeps the policy's static config), ``available`` the
    reachable-device count (None outside fleet runs). ``regions`` is the
    per-client edge-region id (``(U_act,)`` int32, from the population
    draw) consumed by the hierarchical backend; None lets that backend
    fall back to a contiguous split.
    """

    x: Any
    y: Any
    counts: Any
    view: Any = None
    available: Optional[int] = None
    regions: Optional[np.ndarray] = None

    @property
    def size(self) -> int:
        return int(self.x.shape[0])


class StaticCohortSource:
    """The same pre-stacked client population every round (the classic
    ``run_federated`` setting: cohort == population, no churn)."""

    def __init__(self, client_x, client_y, n_per_client):
        self._cohort = Cohort(x=client_x, y=client_y, counts=n_per_client)

    @property
    def cohort_size(self) -> int:
        return self._cohort.size

    def round_cohort(self, t: int) -> Cohort:
        return self._cohort


@dataclasses.dataclass
class RoundContext:
    """The round's view of the simulated clock and straggler model, for
    backends that carry work across round boundaries (the buffered
    semi-async backend).

    ``sim_start``/``sim_end`` are the round's simulated-clock span
    (``sim_end - sim_start`` = planned deadline); ``lam`` the realized
    Poisson rates of the straggler draw; ``layer_s`` the mean per-layer
    backprop time ``S_u / P_u`` (the exponential clock); ``B`` the comm/
    setup overhead — all over the ACTIVE (unpadded) cohort rows, so a
    backend can model when a straggler's in-flight work lands.
    """

    t: int
    sim_start: float
    sim_end: float
    U_act: int
    lam: np.ndarray        # (U_act,)
    layer_s: np.ndarray    # (U_act,)
    B: np.ndarray          # (U_act,)
    # per-client edge-region ids from the cohort draw (hierarchical
    # backend); None -> the backend's contiguous fallback split
    regions: Any = None


def _round_context(t: int, elapsed: float, plan: RoundPlan, view_cfg,
                   U_act: int, regions=None) -> RoundContext:
    """Recover the straggler-model rates the plan was drawn under. Both
    policy families price a client's layer clock as Exp(S_u / P_u) with
    deadline ``plan.elapsed`` (B1-B3), so ``lam = P/S * max(T - B, 0)``
    reproduces the rate regardless of how S was chosen (B3 scaling or the
    baselines' fixed batch)."""
    T_d = float(plan.elapsed)
    P = np.asarray(view_cfg.P, np.float32)[:U_act]
    B = np.asarray(view_cfg.B_eff, np.float32)[:U_act]
    S = np.asarray(plan.batch_sizes, np.float32)
    S = (np.full(U_act, float(S), np.float32) if S.ndim == 0
         else S[:U_act])
    lam = P / np.maximum(S, 1.0) * np.maximum(T_d - B, 0.0)
    layer_s = S / np.maximum(P, 1e-9)
    return RoundContext(t=t, sim_start=float(elapsed),
                        sim_end=float(elapsed) + T_d, U_act=int(U_act),
                        lam=lam, layer_s=layer_s, B=B, regions=regions)


@dataclasses.dataclass
class _Prepared:
    """One planned round from the sequential host planner — everything the
    dispatch step needs, plus the worker-side telemetry to re-emit on the
    main thread. ``kind`` is ``"round"`` (executable), ``"skip"`` (empty
    cohort), or ``"stop"`` (the T_max budget check failed). The stacked
    device arrays are one slot of the prefetch double buffer;
    :meth:`release` drops them right after dispatch."""

    t: int
    kind: str
    spans: list                        # [(phase, t0, dur_s, attrs)]
    t_start: float = 0.0               # worker wall window, for the
    t_end: float = 0.0                 # prefetch_overlap_s counter
    replan: Optional[dict] = None      # the replan event's record
    cohort: Any = None
    plan: Any = None
    xb: Any = None
    yb: Any = None
    wb: Any = None
    mask: Any = None
    U_act: int = 0
    view_cfg: Any = None
    ctx: Any = None
    h2d_bytes: int = 0

    def release(self) -> None:
        self.cohort = self.xb = self.yb = self.wb = self.mask = None


def _host_bytes(*arrays) -> int:
    """Bytes that handing ``arrays`` to the device copies from host memory:
    those that are not device arrays already, at the dtype JAX gives them
    there."""
    return sum(int(np.size(a)) * jax.dtypes.canonicalize_dtype(
                   np.result_type(a)).itemsize
               for a in arrays if not isinstance(a, jax.Array))


class RoundRuntime:
    """The single federated round loop, parameterized by execution backend.

    HOW rounds execute is an :class:`repro.fl.spec.ExecSpec` (``exec=``):
    backend selection (``dense | chunked | shard_map | temporal |
    buffered``), its knobs (``chunk_size`` / ``mesh`` / staleness), the
    local-update shape (``local_iters`` / ``l2``), params-buffer donation,
    and the client->server wire format + aggregation implementation
    (``compression`` / ``agg_impl``). The individual kwargs remain as
    deprecated aliases — both forms funnel through
    :meth:`ExecSpec.resolve`, so trajectories are bit-identical either
    way. ``backend`` may also be an
    :class:`repro.fl.backends.ExecutionBackend` instance (passed through).

    ``tracer`` (:class:`repro.obs.Tracer`) enables structured telemetry —
    phase spans, counters, and the per-round clock-model ledger (including
    the buffered backend's ``carried_in``/``carried_out`` columns) — for
    the runtime AND the backend; the default :data:`repro.obs.NULL_TRACER`
    records nothing and perturbs nothing.

    ``ExecSpec.pipeline`` selects the round-driver mode (``"serial"`` |
    ``"prefetch"``): see the module docstring for the execution timeline.
    Both modes produce bit-identical trajectories.
    """

    def __init__(self, model: ModelAPI, policy: Policy, *,
                 exec: Optional[ExecSpec] = None, backend=None,
                 chunk_size: Optional[int] = None, mesh=None,
                 local_iters: Optional[int] = None,
                 l2: Optional[float] = None, donate: Optional[bool] = None,
                 compression=None, agg_impl: Optional[str] = None,
                 tracer=None):
        self.model = model
        self.policy = policy
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self.backend = make_backend(backend, model, exec=exec,
                                    chunk_size=chunk_size, mesh=mesh,
                                    local_iters=local_iters, l2=l2,
                                    donate=donate, compression=compression,
                                    agg_impl=agg_impl)
        self.backend.set_tracer(self.tracer)
        self.pipeline = exec.pipeline if exec is not None else "serial"
        self._wmask_cache: dict[bytes, PyTree] = {}

    # ------------------------------------------------------------------
    def _width_masks(self, params: PyTree, ratios, U_pad: int) -> PyTree:
        if self.model.width_masks is None:
            raise ValueError("model does not support HeteroFL width masks")
        r = np.asarray(ratios, np.float32)
        if r.shape[0] < U_pad:
            # padded clients pose as full-width; their mask row is zero, so
            # they never touch the overlap mean
            r = np.concatenate([r, np.ones(U_pad - r.shape[0], np.float32)])
        key = r.tobytes()
        if key not in self._wmask_cache:
            # fleet cohorts re-derive ratios every round, so bound the cache
            # (each entry is a cohort-sized mask pytree) LRU-style
            while len(self._wmask_cache) >= 8:
                self._wmask_cache.pop(next(iter(self._wmask_cache)))
            self._wmask_cache[key] = self.model.width_masks(params, r)
        return self._wmask_cache[key]

    def _prepare(self, cohort: Cohort, plan: RoundPlan, k_batch, s_max: int,
                 U_pad: int):
        """Draw the per-client minibatches, then pad the cohort axis to the
        backend's fixed width. Also returns the bytes of the inputs that
        were copied from host memory (the ``h2d_bytes`` counter).

        Sampling always happens at the UNPADDED cohort width: jax's
        counter-based PRNG ties the draw to the array shape, so sampling at
        a backend-dependent padded width would give every backend different
        minibatches. Padded rows get all-zero batches, weights, and mask —
        their aggregation coefficients are 0, so they contribute nothing.
        """
        U_act = cohort.size
        inputs = (cohort.x, cohort.y, cohort.counts, plan.batch_sizes)
        xb, yb, wb = sample_client_batches(
            k_batch, *(jnp.asarray(a) for a in inputs), s_max)
        mask = jnp.asarray(plan.mask, jnp.float32)
        h2d = _host_bytes(*inputs, plan.mask)
        if U_pad != U_act:
            pad = U_pad - U_act
            zrow = lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
            xb, yb, wb, mask = zrow(xb), zrow(yb), zrow(wb), zrow(mask)
        return xb, yb, wb, mask, U_act, h2d

    # ------------------------------------------------------------------
    @obs.annotated("run")
    def run(self, source, *, rounds: int, T_max: float, eta, s_max: int,
            key: jax.Array, test_x=None, test_y=None, eval_every: int = 1,
            verbose: bool = False, method: str = "",
            replan=None, eval_fn: Optional[Callable] = None,
            on_round: Optional[Callable] = None) -> tuple[PyTree, History]:
        """Run up to ``rounds`` rounds, stopping when the simulated clock
        exceeds ``T_max``; returns ``(params, History)``.

        ``eval_fn`` (``params -> (metric, loss)``) supplies the task's eval
        metrics — token accuracy / token CE for LM tasks
        (:meth:`repro.fl.tasks.Task.eval_fn`); when None the classification
        default :func:`eval_metrics` runs over ``test_x``/``test_y``.

        ``on_round`` (``(t, params, hist) -> None``) is called after every
        EXECUTED round — the checkpointing hook of the LM training driver.

        ``replan`` (None | trigger name | :class:`repro.core.replan.
        ReplanConfig`) enables online re-solving of the remaining-horizon
        Problem 2 when churn shifts the reachable population: the trigger is
        evaluated before each round against the cohort source's reachable
        count, the re-solve warm-starts from the incumbent schedule tail,
        and each event is appended to ``History.replans``. A round whose
        cohort is empty (``round_cohort`` returns None) never starts; its
        planned deadline is credited back to the replanner
        (:meth:`repro.core.replan.Replanner.note_skip`), which zeroes the
        stranded historical deadline and forces a re-solve at the next
        executed round so the un-spent budget is re-allocated immediately.
        Sources may expose ``replan_view(t, budget_left, eta_tail)`` to
        re-estimate the population view (the fleet source does); without it
        the policy's static config is restricted to the remaining horizon.

        Execution timeline: all host-only planning phases of a round
        (``cohort`` / ``replan`` / key splits / ``plan`` / T_max check /
        ``stack``) run through one sequential planner. Under
        ``pipeline="serial"`` it is called inline each round; under
        ``"prefetch"`` round t+1's call overlaps round t's device step on
        a worker thread, with a serial-fallback round after every skip or
        replan event (module docstring has the full picture). Eval results
        stay device scalars in a pending ring and are materialized at
        report boundaries, before every ``on_round`` call, on replan
        events, and at the end of the run — ``History`` always holds plain
        floats by the time ``run`` returns. Under a tracer with
        ``sync=False`` the ledger and eval records, which read device
        values, are emitted at those same boundaries.
        """
        model, policy, backend = self.model, self.policy, self.backend
        if getattr(policy, "name", "") == "heterofl" and \
                model.width_masks is None:
            raise ValueError("model does not support HeteroFL width masks")
        if eval_fn is None:
            if test_x is None:
                raise ValueError("run() needs either eval_fn or "
                                 "test_x/test_y")
            eval_fn = lambda p: eval_metrics(model, p, test_x, test_y)
        replan = make_replan(replan)
        replanner = (Replanner(replan, policy, rounds, eta, s_max=s_max,
                               rate_max=getattr(source, "plan_rate_max",
                                                None))
                     if replan is not None and replan.active else None)
        key, k_init = jax.random.split(key)
        params = model.init(k_init)
        U_pad = backend.cohort_pad(source.cohort_size)
        backend.reset_state()        # stateful backends: fresh carry buffer
        needs_ctx = bool(getattr(backend, "needs_ctx", False))
        prefetch = self.pipeline == "prefetch"

        tracer = self.tracer
        hist = History(method=method or policy.name)
        elapsed = 0.0
        wall_start = obs.now()

        # -- the sequential host planner ---------------------------------
        # Everything here reads host state only (source RNG, replanner,
        # schedule, the PLANNED clock — `plan.elapsed` is known before the
        # round executes), never a device result, so the prefetcher can run
        # it one round ahead and the trajectory stays bit-identical. The
        # planner's clock mirrors `elapsed` exactly: every planned "round"
        # is later executed, skips spend nothing, and a "stop" halts both.
        # Each phase opens its profiler annotation live, on the thread that
        # does the work; the JSONL spans are collected locally and
        # re-emitted at consume time on the main thread (tracer sinks are
        # not thread-safe).
        plan_key = key
        planned_elapsed = 0.0

        def plan_round(t: int) -> _Prepared:
            nonlocal plan_key, planned_elapsed
            spans: list = []
            t_start = t0 = obs.now()
            with obs.annotate("cohort"):
                cohort = source.round_cohort(t)
            spans.append(("cohort", t0, obs.now() - t0, {}))
            if cohort is None:
                # nobody reachable: the round never starts and spends
                # nothing — credit its planned deadline back so the next
                # re-solve re-allocates it instead of stranding it
                if replanner is not None:
                    replanner.note_skip(t)
                return _Prepared(t=t, kind="skip", spans=spans,
                                 t_start=t_start, t_end=obs.now())
            rep = None
            if replanner is not None:
                reachable = (cohort.available if cohort.available is not None
                             else source.cohort_size)
                if replanner.should_replan(t, reachable):
                    view = None
                    budget_left = max(T_max - planned_elapsed, 1e-6)
                    view_fn = getattr(source, "replan_view", None)
                    if view_fn is not None:
                        view = view_fn(t, budget_left, eta[t:rounds])
                    t0 = obs.now()
                    attrs = {"reachable": int(reachable)}
                    with obs.annotate("replan", **attrs):
                        ev = replanner.replan(t, budget_left, reachable, view)
                    spans.append(("replan", t0, obs.now() - t0, attrs))
                    rep = ev.as_dict()
            t0 = obs.now()
            with obs.annotate("plan"):
                plan_key, k_round, k_batch = jax.random.split(plan_key, 3)
                plan: RoundPlan = policy.round(k_round, t, view=cohort.view)
            spans.append(("plan", t0, obs.now() - t0, {}))
            if planned_elapsed + plan.elapsed > T_max * (1 + 1e-6):
                return _Prepared(t=t, kind="stop", spans=spans, replan=rep,
                                 t_start=t_start, t_end=obs.now())
            t0 = obs.now()
            with obs.annotate("stack"):
                xb, yb, wb, mask, U_act, h2d = self._prepare(
                    cohort, plan, k_batch, s_max, U_pad)
            spans.append(("stack", t0, obs.now() - t0, {}))
            view_cfg = (cohort.view if cohort.view is not None
                        else policy.cfg)
            ctx = (_round_context(t, planned_elapsed, plan, view_cfg,
                                  U_act, regions=cohort.regions)
                   if needs_ctx else None)
            planned_elapsed += plan.elapsed
            h2d += _host_bytes(plan.p, eta[t])    # handed over at dispatch
            return _Prepared(t=t, kind="round", spans=spans, replan=rep,
                             t_start=t_start, t_end=obs.now(),
                             cohort=cohort, plan=plan, xb=xb, yb=yb, wb=wb,
                             mask=mask, U_act=U_act, view_cfg=view_cfg,
                             ctx=ctx, h2d_bytes=h2d)

        def plan_ahead(t: int) -> _Prepared:
            # the prefetch worker's root span: its phases are round t's
            with obs.annotate("prefetch", round=t + 1):
                return plan_round(t)

        def emit_round(**kw) -> None:
            """The clock-model ledger row of one round, and the real batch
            elements it counts (both read the plan's device arrays)."""
            rec = obs.round_record(L=model.L, U_pad=U_pad, s_max=s_max, **kw)
            rnd = kw["t"] + 1
            tracer.count("batch_elements_real", rec["batch_real"],
                         round=rnd)
            tracer.event("round", round=rnd, **rec)

        pool = (concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="prefetch")
                if prefetch else None)
        pending: Optional[concurrent.futures.Future] = None
        pending_evals: list[int] = []    # History rows awaiting float()
        # records that read device values, held back under a tracer with
        # sync=False until the next drain
        deferred: list[Callable[[], None]] = []
        warmed = False
        dispatch_t0: Optional[float] = None

        def drain() -> None:
            """Materialize deferred eval device scalars into History (the
            conversion is the sync point; everything before it is free),
            then emit the records that waited for device values."""
            for i in pending_evals:
                hist.accuracy[i] = float(hist.accuracy[i])
                hist.train_loss[i] = float(hist.train_loss[i])
            pending_evals.clear()
            for emit in deferred:
                emit()
            deferred.clear()

        def emit_eval(rec: dict, i: int) -> None:
            # ONE record for the sink and the console, rendered from exactly
            # what History keeps
            rec.update(acc=hist.accuracy[i], loss=hist.train_loss[i])
            tracer.event("eval", **rec)
            if verbose:
                print(obs.format_eval(hist.method, rec))

        try:
            for t in range(rounds):
                with obs.annotate("round", round=t + 1):
                    tracer.set_round(t + 1)
                    wall_round0 = obs.now() if tracer.active else 0.0
                    if pending is not None:
                        t0 = obs.now()
                        prep: _Prepared = pending.result()
                        pending = None
                        if tracer.active:
                            t_res = obs.now()
                            tracer.count("dispatch_wait_s",
                                         round(t_res - t0, 6))
                            tracer.count("prefetch_rounds", 1)
                            if dispatch_t0 is not None:
                                # worker wall time hidden behind the
                                # device dispatch window of the
                                # previous round
                                lo = max(prep.t_start, dispatch_t0)
                                hi = min(prep.t_end, t_res)
                                if hi > lo:
                                    tracer.count("prefetch_overlap_s",
                                                 round(hi - lo, 6))
                    else:
                        prep = plan_round(t)
                    for name, s0, dur, attrs in prep.spans:
                        tracer.span_record(name, s0, dur, **attrs)
                    if prep.replan is not None:
                        hist.replans.append(prep.replan)
                        tracer.event("replan", **prep.replan)
                        drain()      # replan events report live state
                        if verbose:
                            print(obs.format_replan(hist.method,
                                                    prep.replan))
                    if prep.kind == "skip":
                        continue
                    if prep.kind == "stop":
                        break
                    plan, U_act = prep.plan, prep.U_act
                    wmasks = None
                    if plan.width_ratios is not None:
                        # HeteroFL masks read the LIVE params tree — the
                        # one stack input the planner cannot speculate on
                        with tracer.span("stack", part="wmasks"):
                            wmasks = self._width_masks(
                                params, plan.width_ratios, U_pad)
                    if prefetch and prep.replan is None and \
                            t + 1 < rounds:
                        # overlap round t+1's host phases with round t's
                        # device step; a replan round forces the next
                        # plan inline (and a skip `continue`s before
                        # this point)
                        pending = pool.submit(plan_ahead, t + 1)
                    if prefetch and not warmed:
                        # AOT warm-up: compile+execute the round step
                        # and the eval step on dummies before round 0
                        # dispatches, so trace cost never lands inside
                        # a measured round
                        t0 = obs.now()
                        with tracer.span("warm_up"):
                            backend.warm_up(
                                params, prep.xb, prep.yb, prep.wb,
                                prep.mask, plan.p, jnp.float32(eta[t]),
                                bias_correct=bool(plan.bias_correct),
                                wmasks=wmasks, ctx=prep.ctx)
                            dummy = jax.tree.map(
                                lambda a: jnp.zeros(jnp.shape(a),
                                                    jnp.result_type(a)),
                                params)
                            jax.block_until_ready(eval_fn(dummy))
                        tracer.count("warm_up_s", round(obs.now() - t0, 6))
                        warmed = True
                    available = prep.cohort.available
                    dispatch_t0 = obs.now()
                    params = backend.run_round(
                        params, prep.xb, prep.yb, prep.wb, prep.mask,
                        plan.p, jnp.float32(eta[t]),
                        bias_correct=bool(plan.bias_correct),
                        wmasks=wmasks, ctx=prep.ctx)
                    tracer.count("h2d_bytes", prep.h2d_bytes)
                    prep.release()   # free this round's buffer slot
                    elapsed += plan.elapsed
                    if tracer.active:
                        # the clock-model ledger row: planned deadline
                        # vs simulated clock vs measured wall vs the
                        # model's view
                        walls = {"wall_round_s": None,
                                 "wall_total_s": None}
                        if tracer.sync:
                            jax.block_until_ready(params)
                            wall_now = obs.now()
                            walls = {
                                "wall_round_s": wall_now - wall_round0,
                                "wall_total_s": wall_now - wall_start}
                        tracer.count("batch_elements_padded",
                                     U_pad * s_max)
                        row = functools.partial(
                            emit_round, t=t, plan=plan,
                            cfg=prep.view_cfg, U_act=U_act,
                            sim_total=elapsed, available=available,
                            carry=getattr(backend, "last_carry",
                                          None) or None,
                            regions=getattr(backend, "last_regions",
                                            None) or None, **walls)
                        if tracer.sync:
                            row()
                        else:
                            deferred.append(row)
                    if (t % eval_every == 0) or (t == rounds - 1):
                        with tracer.span("eval"):
                            acc, loss = eval_fn(params)
                            if tracer.sync:
                                # explicit telemetry sync: the span
                                # should measure eval compute, not
                                # async dispatch
                                jax.block_until_ready((acc, loss))
                        hist.times.append(elapsed)
                        hist.rounds.append(t + 1)
                        hist.accuracy.append(acc)
                        hist.deadlines.append(float(plan.elapsed))
                        hist.train_loss.append(loss)
                        if available is not None:
                            hist.available.append(int(available))
                        pending_evals.append(len(hist.accuracy) - 1)
                        if tracer.active or verbose:
                            rec = functools.partial(emit_eval, {
                                "round": t + 1, "available": available,
                                "cohort": U_act, "sim_total": elapsed,
                                "T_deadline": float(plan.elapsed)},
                                len(hist.accuracy) - 1)
                            if tracer.sync or verbose:
                                # only this report boundary pays the
                                # float() conversion
                                drain()
                                rec()
                            else:
                                deferred.append(rec)
                    if on_round is not None:
                        drain()    # hooks read materialized History
                        with tracer.span("checkpoint"):
                            on_round(t, params, hist)
        finally:
            if pool is not None:
                if pending is not None:
                    pending.cancel()
                pool.shutdown(wait=True)
        drain()
        tracer.set_round(None)
        if tracer.active:
            hist.telemetry = tracer.summary()
        return params, hist
