"""Pipelined round driver (``ExecSpec.pipeline="prefetch"``).

The one-round-lookahead prefetcher speculates only on host-deterministic
phases, so its trajectories must be BIT-identical to serial — not merely
close — on every backend, including the buffered backend's carry ring and
the hierarchical backend's region folds, and across skipped rounds and
mid-run replans (which force a serial-fallback round). The pipeline
counters (``h2d_bytes`` / ``prefetch_overlap_s`` / ``dispatch_wait_s`` /
``warm_up_s``) and the AOT warm-up span must land in the event stream, and
``h2d_bytes`` must count only what crosses from host memory.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import make_policy
from repro.core.replan import ReplanConfig
from repro.core.scheduler import solve
from repro.core.types import AnalysisConfig
from repro.data.synthetic import make_image_dataset
from repro.fl.partition import dirichlet_partition, stack_clients
from repro.fl.runtime import RoundRuntime, StaticCohortSource
from repro.fl.server import run_federated
from repro.fl.spec import ExecSpec
from repro.models.paper_models import make_mlp
from repro.obs import MemorySink, Tracer

R = 4
U = 8

# every backend, with the knobs that exercise its stateful paths: the
# buffered carry ring actually banking (lam > 0) and the hierarchical
# region split actually splitting (regions > 1, no population ids)
BACKEND_SPECS = [
    dict(backend="dense"),
    dict(backend="chunked", chunk_size=3),
    dict(backend="shard_map"),
    dict(backend="temporal"),
    dict(backend="buffered", lam=0.5, max_age=3, buffer_cap=3),
    dict(backend="hierarchical", regions=3),
]


@pytest.fixture(scope="module")
def setup():
    x_tr, y_tr, x_te, y_te = make_image_dataset(
        "mnist", n_train=400, n_test=100, seed=0, noise_std=1.0)
    parts = dirichlet_partition(y_tr, U, alpha=0.5, seed=0)
    cx, cy, counts = stack_clients(x_tr, y_tr, parts)
    model = make_mlp()
    cfg = AnalysisConfig.default(U=U, L=model.L, R=R, T_max=R * model.L * 0.5,
                                 eta0=2.0, seed=0)
    data = (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(counts),
            jnp.asarray(x_te), jnp.asarray(y_te))
    schedule = solve(cfg, "adam", steps=100)
    return model, cfg, data, schedule


def _run(setup, pipeline, backend_kw, tracer=None, replan=None):
    model, cfg, data, schedule = setup
    policy = make_policy("adel", cfg, schedule=schedule)
    _, hist = run_federated(model, policy, cfg, *data,
                            key=jax.random.PRNGKey(0),
                            exec=ExecSpec(pipeline=pipeline, **backend_kw),
                            tracer=tracer, replan=replan)
    return hist


def _assert_bit_identical(a, b):
    # the whole History, exact: clock, plans, accuracy, losses, replans
    assert a.as_dict() == b.as_dict()


@pytest.mark.parametrize("backend_kw", BACKEND_SPECS,
                         ids=[s["backend"] for s in BACKEND_SPECS])
def test_prefetch_bit_identical_to_serial(setup, backend_kw):
    _assert_bit_identical(_run(setup, "serial", backend_kw),
                          _run(setup, "prefetch", backend_kw))


def test_history_holds_plain_floats(setup):
    """The pending eval ring must be fully drained by the time run()
    returns — downstream consumers json-serialize History as-is."""
    hist = _run(setup, "prefetch", dict(backend="dense"))
    assert all(isinstance(v, float) for v in hist.accuracy)
    assert all(isinstance(v, float) for v in hist.train_loss)


def test_prefetch_skip_and_forced_replan(setup):
    """An empty-cohort round and the skip-forced re-solve at the next
    executed round (both of which mutate the planning state) must leave
    the prefetched trajectory bit-identical — the driver falls back to
    inline planning for the round after a skip/replan."""
    model, cfg, data, schedule = setup
    cx, cy, counts, x_te, y_te = data

    class SkippySource(StaticCohortSource):
        def round_cohort(self, t):
            return None if t == 1 else super().round_cohort(t)

    def run(pipeline):
        policy = make_policy("adel", cfg, schedule=schedule)
        runtime = RoundRuntime(model, policy,
                               exec=ExecSpec(pipeline=pipeline))
        _, hist = runtime.run(
            SkippySource(cx, cy, counts), rounds=cfg.R, T_max=cfg.T_max,
            eta=cfg.eta, s_max=16, key=jax.random.PRNGKey(0),
            test_x=x_te, test_y=y_te,
            replan=ReplanConfig(trigger="drift", drift_threshold=10.0,
                                steps=80))
        return hist

    a, b = run("serial"), run("prefetch")
    _assert_bit_identical(a, b)
    # the scenario actually exercised both fallback paths
    assert len(a.replans) == 1 and a.replans[0]["round"] == 2


def test_prefetch_counters_and_warmup(setup):
    """A traced prefetch run records the pipeline counters (all nonzero),
    the warm_up span, and one prefetched round per lookahead."""
    sink = MemorySink()
    hist = _run(setup, "prefetch", dict(backend="dense"),
                tracer=Tracer(sink))
    c = hist.telemetry["counters"]
    assert c["h2d_bytes"] > 0
    assert c["warm_up_s"] > 0
    assert c["prefetch_rounds"] == R - 1        # round 0 planned inline
    assert c["prefetch_overlap_s"] > 0
    assert "dispatch_wait_s" in c
    assert "warm_up" in hist.telemetry["phases"]
    # worker-planned phases are re-emitted on the main thread with the
    # right round stamp
    spans = [r for r in sink.records if r.get("kind") == "span"]
    assert {r["name"] for r in spans} >= {"warm_up", "cohort", "plan",
                                          "stack", "eval"}
    plan_rounds = sorted({r["round"] for r in spans
                          if r["name"] == "plan"})
    assert plan_rounds == list(range(1, R + 1))


def test_serial_counters_absent(setup):
    """Serial mode never engages the prefetcher or the warm-up."""
    hist = _run(setup, "serial", dict(backend="dense"),
                tracer=Tracer(MemorySink()))
    c = hist.telemetry["counters"]
    assert "prefetch_rounds" not in c
    assert "warm_up_s" not in c
    assert c["h2d_bytes"] > 0            # the host-bytes counter is modal-
    assert "warm_up" not in hist.telemetry["phases"]   # independent


def test_h2d_bytes_counts_host_arrays_only(setup):
    """h2d_bytes counts what the runtime copies from host memory: the
    learning rate alone where the cohort and the plan live on the device,
    plus the cohort's rows where the source hands over numpy arrays."""
    model, cfg, data, schedule = setup
    cx, cy, counts, x_te, y_te = data

    def run(*cohort):
        tr = Tracer(MemorySink())
        runtime = RoundRuntime(model, make_policy("adel", cfg,
                                                  schedule=schedule),
                               tracer=tr)
        runtime.run(StaticCohortSource(*cohort), rounds=R, T_max=cfg.T_max,
                    eta=cfg.eta, s_max=16, key=jax.random.PRNGKey(0),
                    test_x=x_te, test_y=y_te)
        return tr.counters["h2d_bytes"], len(tr.rounds)

    on_device, n = run(cx, cy, counts)
    assert n == R and on_device == 4 * n          # one float32 a round
    host = [np.asarray(a) for a in (cx, cy, counts)]
    assert run(*host) == (on_device + n * sum(a.nbytes for a in host), n)


def test_exec_spec_pipeline_validation_and_cli():
    with pytest.raises(ValueError):
        ExecSpec(pipeline="bogus")
    ap = argparse.ArgumentParser()
    ExecSpec.add_cli_args(ap)
    args = ap.parse_args(["--pipeline", "prefetch"])
    assert ExecSpec.from_cli(args).pipeline == "prefetch"
    assert ExecSpec.from_cli(ap.parse_args([])).pipeline == "serial"


def test_compile_cache_fixed_path(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX's config
    alone; unset, the cache goes to the checkout's fixed .jax_cache."""
    from pathlib import Path

    from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        assert CACHE_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
