"""Telemetry (repro.obs): span invariants, NullTracer overhead budget,
tracing-on == tracing-off trajectories on every backend (with and without
syncing), the profiler annotations' nesting, the History round-trip fix,
the clock-model ledger math, and the timeline renderer."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.baselines import make_policy
from repro.core.replan import ReplanEvent
from repro.core.scheduler import solve
from repro.core.types import AnalysisConfig
from repro.data.synthetic import make_image_dataset
from repro.fl.partition import dirichlet_partition, stack_clients
from repro.fl.runtime import History, RoundRuntime, StaticCohortSource
from repro.fl.server import run_federated
from repro.fl.spec import ExecSpec
from repro.models.paper_models import make_mlp
from repro.obs.ledger import drift_summary, expected_depth, phase_table
from repro.obs.timeline import load_events, render

R = 4
U = 6


@pytest.fixture(scope="module")
def setup():
    x_tr, y_tr, x_te, y_te = make_image_dataset(
        "mnist", n_train=400, n_test=120, seed=0, noise_std=1.0)
    parts = dirichlet_partition(y_tr, U, alpha=0.5, seed=0)
    cx, cy, counts = stack_clients(x_tr, y_tr, parts)
    model = make_mlp()
    cfg = AnalysisConfig.default(U=U, L=model.L, R=R, T_max=R * model.L * 0.5,
                                 eta0=2.0, seed=0)
    data = (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(counts),
            jnp.asarray(x_te), jnp.asarray(y_te))
    schedule = solve(cfg, "adam", steps=60)
    return model, cfg, data, schedule


def _run(setup, backend, tracer=None, chunk_size=None):
    model, cfg, data, schedule = setup
    policy = make_policy("adel", cfg, schedule=schedule)
    if chunk_size is None and backend == "chunked":
        chunk_size = 3
    _, hist = run_federated(model, policy, cfg, *data,
                            key=jax.random.PRNGKey(0), backend=backend,
                            chunk_size=chunk_size, tracer=tracer)
    return hist


# ---------------------------------------------------------------------------
# span mechanics
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    """Spans record depth, enclosing parent, and a monotone sequence."""
    clock = iter(np.arange(0.0, 100.0, 0.5))
    sink = obs.MemorySink()
    tr = obs.Tracer(sink, clock=lambda: float(next(clock)))
    tr.set_round(1)
    with tr.span("plan"):
        with tr.span("stack"):
            pass
        with tr.span("local_train", backend="dense"):
            pass
    with tr.span("eval"):
        pass
    spans = [r for r in sink.records if r["kind"] == "span"]
    by_name = {r["name"]: r for r in spans}
    # children exit before the parent
    assert [r["name"] for r in spans] == ["stack", "local_train", "plan",
                                          "eval"]
    assert by_name["stack"]["parent"] == "plan"
    assert by_name["local_train"]["parent"] == "plan"
    assert by_name["local_train"]["backend"] == "dense"
    assert by_name["plan"]["parent"] is None
    assert by_name["stack"]["depth"] == 1
    assert by_name["plan"]["depth"] == 0
    seqs = [r["seq"] for r in spans]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # injected clock (0.5s ticks): leaves last one tick, the parent spans
    # its children — enter(0.0) ... exit(2.5)
    assert by_name["stack"]["dur_s"] == pytest.approx(0.5)
    assert by_name["local_train"]["dur_s"] == pytest.approx(0.5)
    assert by_name["plan"]["dur_s"] == pytest.approx(2.5)
    assert all(r["round"] == 1 for r in spans)


def test_tracer_summary_aggregates():
    tr = obs.Tracer()
    with tr.span("plan"):
        pass
    with tr.span("plan"):
        pass
    tr.count("batch_elements_real", 10)
    tr.count("batch_elements_real", 5)
    tr.count("dispatch_wait_s", 0.25)
    tr.count("dispatch_wait_s", 0.125)
    s = tr.summary()
    assert s["phases"]["plan"]["count"] == 2
    assert s["counters"]["batch_elements_real"] == 15
    assert s["counters"]["dispatch_wait_s"] == 0.375
    assert set(s) == {"phases", "counters", "ledger", "drift"}
    json.dumps(s)  # summary must be JSON-clean


def test_phase_order_within_round(setup):
    """In a real run every round's top-level spans appear in the canonical
    phase order (cohort -> plan -> stack -> train -> eval)."""
    sink = obs.MemorySink()
    tr = obs.Tracer(sink)
    _run(setup, "dense", tracer=tr)
    order = {p: i for i, p in enumerate(obs.PHASES)}
    for rnd in range(1, R + 1):
        names = [r["name"] for r in sink.records
                 if r["kind"] == "span" and r["round"] == rnd
                 and r["depth"] == 0]
        assert names, f"round {rnd} recorded no spans"
        idx = [order[n] for n in names if n in order]
        assert idx == sorted(idx), f"round {rnd} phases out of order: {names}"


# ---------------------------------------------------------------------------
# NullTracer: zero-overhead default
# ---------------------------------------------------------------------------

def test_null_tracer_overhead_budget(setup):
    """The NullTracer's total per-run cost stays under 1% of a dense run.

    Comparing two full wall-clock runs at 1% precision flakes on shared
    runners, so measure the per-call no-op cost directly and price the
    instrumented call sites a 10-round dense run actually executes."""
    null = obs.NULL_TRACER
    n = 50_000
    t0 = obs.now()
    for _ in range(n):
        with null.span("plan", backend="dense"):
            pass
        null.count("batch_elements_real", 7)
        null.count("h2d_bytes", 4)
        null.event("round", t=0)
        null.active  # the hot-path guard itself
    per_group = (obs.now() - t0) / n

    t0 = obs.now()
    hist = _run(setup, "dense", tracer=None)
    wall = obs.now() - t0
    assert hist.rounds, "dense run executed no rounds"
    # ~10 instrumented call groups per round is far above the real count
    groups = 10 * 10
    assert groups * per_group < 0.01 * wall, (
        f"NullTracer cost {groups * per_group:.6f}s vs 1% budget "
        f"{0.01 * wall:.6f}s")


def test_null_tracer_api_is_inert():
    null = obs.NULL_TRACER
    assert null.active is False and null.sync is False
    with null.span("anything", junk=1) as sp:
        assert sp is not None
    null.set_round(3)
    null.count("x")
    null.count("y", 1.0)
    null.event("round", t=0)
    assert null.summary() == {}
    null.close()


# ---------------------------------------------------------------------------
# tracing on == tracing off, every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync", [True, False])
@pytest.mark.parametrize("backend", ["dense", "chunked", "shard_map",
                                     "temporal"])
def test_tracing_preserves_trajectories(setup, backend, sync):
    """Identical History with tracing on vs off — telemetry must never
    touch PRNG keys or numerics, on any execution backend, whether or not
    the tracer syncs."""
    base = _run(setup, backend, tracer=None)
    tr = obs.Tracer(obs.MemorySink(), sync=sync)
    traced = _run(setup, backend, tracer=tr)
    a, b = base.as_dict(), traced.as_dict()
    tel = b.pop("telemetry")
    a.pop("telemetry")
    assert a == b
    # and the traced run actually recorded its rounds: the fused backends
    # under one round_step span, the chunked one under local_train
    step = "local_train" if backend == "chunked" else "round_step"
    assert tel["phases"][step]["count"] >= len(traced.rounds)
    assert len(tel["ledger"]) == len(traced.rounds)
    assert [r["round"] for r in tel["ledger"]] == traced.rounds
    assert tel["counters"]["batch_elements_real"] > 0
    # a tracer that does not sync cannot time the device
    assert all((r["wall_round_s"] is None) == (not sync)
               for r in tel["ledger"])


@pytest.mark.parametrize("pipeline", ["serial", "prefetch"])
def test_unsynced_tracer_never_blocks(setup, monkeypatch, pipeline):
    """A tracer with sync=False adds no block_until_ready to a run (the
    prefetch warm-up's own blocks stay), yet emits every ledger and eval
    record, each stamped with its own round."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    model, cfg, data, schedule = setup
    runs = {}
    for name, tracer in (("null", None),
                         ("unsynced", obs.Tracer(obs.MemorySink(),
                                                 sync=False)),
                         ("synced", obs.Tracer(obs.MemorySink()))):
        calls.clear()
        policy = make_policy("adel", cfg, schedule=schedule)
        _, hist = run_federated(model, policy, cfg, *data,
                                key=jax.random.PRNGKey(0),
                                exec=ExecSpec(pipeline=pipeline),
                                tracer=tracer)
        runs[name] = (len(calls), hist, tracer)
    assert runs["unsynced"][0] == runs["null"][0]
    assert runs["synced"][0] > runs["null"][0]
    if pipeline == "serial":
        assert runs["null"][0] == 0
    hist, tr = runs["unsynced"][1], runs["unsynced"][2]
    recs = tr.sinks[0].records
    evals = [r for r in recs if r["kind"] == "eval"]
    assert [r["round"] for r in evals] == hist.rounds
    assert [r["acc"] for r in evals] == hist.accuracy
    rows = [r for r in recs if r["kind"] == "round"]
    assert [r["round"] for r in rows] == list(range(1, len(rows) + 1))
    real = [r for r in recs if r.get("name") == "batch_elements_real"]
    assert [r["value"] for r in real] == [r["batch_real"] for r in rows]
    # the ledger renders without its wall column's numbers
    assert "clock-model ledger" in render(recs)
    assert "wall_per_sim_mean" not in drift_summary(rows)


def _profiled_events(tmp_path, setup, pipeline):
    """Host events named repro.* of a two-round run under the profiler:
    [(name, start_ns, end_ns, thread's line)]."""
    import glob

    model, cfg, data, schedule = setup
    cx, cy, counts, x_te, y_te = data
    runtime = RoundRuntime(model, make_policy("adel", cfg, schedule=schedule),
                           exec=ExecSpec(pipeline=pipeline))
    with jax.profiler.trace(str(tmp_path)):
        runtime.run(StaticCohortSource(cx, cy, counts), rounds=2,
                    T_max=cfg.T_max, eta=cfg.eta, s_max=16,
                    key=jax.random.PRNGKey(0), test_x=x_te, test_y=y_te)
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name.split("#")[0], e.start_ns, e.start_ns + e.duration_ns,
             (i, j))
            for i, plane in enumerate(pd.planes)
            if plane.name.startswith("/host")
            for j, line in enumerate(plane.lines) for e in line.events
            if e.name.startswith("repro.")]


@pytest.mark.parametrize("pipeline", ["serial", "prefetch"])
def test_profiler_spans_nest(tmp_path, setup, pipeline):
    """Untraced, the runtime's phases still reach the profiler's trace as
    repro.* annotations: repro.run > repro.round > repro.plan > repro.draw,
    and repro.round_step inside its round."""
    ev = _profiled_events(tmp_path, setup, pipeline)

    def inside(name, outer, thread=True):    # nested on one thread
        return [e for e in ev if e[0] == f"repro.{name}" and any(
            o[0] == f"repro.{outer}" and o[1] <= e[1] and e[2] <= o[2]
            and (o[3] == e[3] or not thread) for o in ev)]

    names = [e[0] for e in ev]
    assert names.count("repro.run") == 1
    assert names.count("repro.round") == 2
    assert len(inside("round", "run")) == 2
    # prefetch also warms the step up with one dummy round, in round 1
    warm = int(pipeline == "prefetch")
    assert len(inside("round_step", "round")) == 2 + warm
    assert len(inside("round_step", "warm_up")) == warm
    assert names.count("repro.plan") == 2
    assert len(inside("draw", "plan")) == 2
    # round 1 is planned inline; under prefetch round 2 is planned ahead,
    # on the worker thread, under its own repro.prefetch root
    assert len(inside("plan", "round")) == (2 if pipeline == "serial"
                                             else 1)
    assert len(inside("plan", "prefetch")) == (0 if pipeline == "serial"
                                                else 1)
    # ... while the main thread's run is open
    assert len(inside("prefetch", "run", thread=False)) == (
        0 if pipeline == "serial" else 1)


def test_chunked_splits_train_and_aggregate(setup):
    """Only the chunked backend can separate local_train from the final
    aggregate apply; the fused backends run both under round_step."""
    sink = obs.MemorySink()
    tr = obs.Tracer(sink)
    _run(setup, "chunked", tracer=tr, chunk_size=2)
    names = {r["name"] for r in sink.records if r["kind"] == "span"}
    assert "aggregate" in names and "local_train" in names


# ---------------------------------------------------------------------------
# History round-trip (satellite: replans as_dict fix)
# ---------------------------------------------------------------------------

def test_history_as_dict_round_trips_replan_events():
    ev = ReplanEvent(round=3, reachable=17, U_est=8, budget_left=12.5,
                     T_tail=[1.0, 0.9], m=1.1, objective=0.42, steps=100)
    hist = History(times=[1.0], rounds=[1], accuracy=[0.5], deadlines=[1.0],
                   train_loss=[2.0], replans=[ev], method="adel")
    d = hist.as_dict()
    blob = json.dumps(d)                   # must not raise on the dataclass
    back = json.loads(blob)
    assert back["replans"] == [ev.as_dict()]
    assert back["replans"][0]["round"] == 3
    # dict entries (what the runtime appends) pass through unchanged
    hist2 = History(replans=[ev.as_dict()])
    assert hist2.as_dict()["replans"] == [ev.as_dict()]


# ---------------------------------------------------------------------------
# ledger math
# ---------------------------------------------------------------------------

def test_expected_depth_exact():
    """E[min(z, L)] matches the closed form at the edges and Monte Carlo
    in the middle."""
    assert expected_depth(np.asarray([0.0]), 5)[0] == pytest.approx(0.0)
    # lam tiny -> E[min(z,L)] ~ E[z] = lam
    assert expected_depth(np.asarray([1e-4]), 5)[0] == pytest.approx(
        1e-4, rel=1e-3)
    # lam huge -> saturates at L
    assert expected_depth(np.asarray([200.0]), 5)[0] == pytest.approx(
        5.0, abs=1e-6)
    rng = np.random.default_rng(0)
    for lam, L in ((0.7, 3), (2.5, 4), (6.0, 8)):
        mc = np.minimum(rng.poisson(lam, size=200_000), L).mean()
        assert expected_depth(np.asarray([lam]), L)[0] == pytest.approx(
            mc, abs=0.02)


def test_drift_summary_fields():
    rows = [{"T_deadline": 1.0, "sim_round": 1.0, "wall_round_s": 0.5,
             "cohort": 4, "missed": 2, "zero_contrib": 1,
             "depth_real": 1.5, "depth_pred": 1.0, "p1_pred": 0.1,
             "layer1_zero": False, "pred_full_s": 2.0}] * 3
    d = drift_summary(rows)
    assert d["rounds"] == 3
    assert d["depth_drift_mean"] == pytest.approx(0.5)
    assert d["miss_rate"] == pytest.approx(0.5)
    assert d["zero_rate"] == pytest.approx(0.25)
    assert d["wall_per_sim_mean"] == pytest.approx(0.5)
    assert d["deadline_vs_full_wait"] == pytest.approx(0.5)
    assert drift_summary([]) == {}


def test_ledger_rows_in_real_run(setup):
    sink = obs.MemorySink()
    tr = obs.Tracer(sink)
    hist = _run(setup, "dense", tracer=tr)
    model, cfg, _, _ = setup
    rows = [r for r in sink.records if r.get("kind") == "round"]
    assert len(rows) == len(hist.rounds)
    for r in rows:
        assert r["cohort"] == U
        assert 0.0 <= r["depth_real"] <= model.L
        assert r["full"] + r["missed"] == r["cohort"]
        assert "depth_pred" in r and "pred_full_s" in r
        # the deadline should undercut the synchronized full-depth wait
        assert r["T_deadline"] < r["pred_full_s"]
    # sim clock in the ledger mirrors the History clock
    assert [r["sim_total"] for r in rows] == pytest.approx(hist.times)


# ---------------------------------------------------------------------------
# sinks + timeline renderer
# ---------------------------------------------------------------------------

def test_jsonl_sink_and_timeline(tmp_path, setup):
    path = os.path.join(tmp_path, "events", "run.jsonl")
    tr = obs.make_tracer(path)
    assert tr.active
    _run(setup, "dense", tracer=tr)
    tr.close()
    records = load_events(path)
    assert records and any(r["kind"] == "round" for r in records)
    assert phase_table(records)
    text = render(records, title="run")
    assert "phase timeline" in text
    assert "clock-model ledger" in text
    assert "stragglers / deadline misses" in text
    assert "drift summary" in text
    # one row per executed round in the ledger table
    assert f"\n    {R}  " in text or f"\n{R}  " in text.replace("  ", "  ")


def test_load_events_skips_torn_lines(tmp_path):
    p = os.path.join(tmp_path, "torn.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "span", "name": "plan", "round": 1,
                            "dur_s": 0.1}) + "\n")
        f.write('{"kind": "round", "t": 0, "T_dead')   # crashed mid-write
    recs = load_events(p)
    assert len(recs) == 1 and recs[0]["name"] == "plan"


def test_make_tracer_defaults_to_null():
    assert obs.make_tracer() is obs.NULL_TRACER
    assert obs.make_tracer(None) is obs.NULL_TRACER
