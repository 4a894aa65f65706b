"""Execution-backend equivalence: dense / chunked / shard_map / temporal /
buffered(lam=0) produce the same History trajectories (up to float
summation order) for ADEL and SALF, HeteroFL width masks flow through
every backend, and the ``ExecSpec`` surface resolves identically to the
legacy kwargs.

The multi-device shard_map case needs ``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` set BEFORE jax initializes, so it
runs in a subprocess (>= 4 host devices, per the acceptance criteria)."""
import argparse
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import make_policy
from repro.core.scheduler import solve
from repro.core.types import AnalysisConfig
from repro.data.synthetic import make_image_dataset
from repro.fl.backends import (BACKENDS, BufferedBackend, ChunkedBackend,
                               DenseBackend, ExecSpec, ShardMapBackend,
                               TemporalBackend, make_backend)
from repro.fl.partition import dirichlet_partition, stack_clients
from repro.fl.server import run_federated
from repro.models.paper_models import make_mlp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

R = 5
U = 8


@pytest.fixture(scope="module")
def setup():
    x_tr, y_tr, x_te, y_te = make_image_dataset(
        "mnist", n_train=600, n_test=200, seed=0, noise_std=1.0)
    parts = dirichlet_partition(y_tr, U, alpha=0.5, seed=0)
    cx, cy, counts = stack_clients(x_tr, y_tr, parts)
    model = make_mlp()
    cfg = AnalysisConfig.default(U=U, L=model.L, R=R, T_max=R * model.L * 0.5,
                                 eta0=2.0, seed=0)
    data = (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(counts),
            jnp.asarray(x_te), jnp.asarray(y_te))
    schedule = solve(cfg, "adam", steps=150)
    return model, cfg, data, schedule


def _run(setup, method, backend, chunk_size=None, **kw):
    model, cfg, data, schedule = setup
    policy = make_policy(method, cfg,
                         schedule=schedule if method == "adel" else None)
    # chunk_size only applies to the chunked backend; passing it elsewhere
    # now (correctly) warns through ExecSpec.resolve
    if chunk_size is None and backend == "chunked":
        chunk_size = 3
    _, hist = run_federated(model, policy, cfg, *data,
                            key=jax.random.PRNGKey(0), backend=backend,
                            chunk_size=chunk_size, **kw)
    return hist


def _assert_equivalent(a, b):
    # the simulated clock and plans are backend-independent — exact
    assert a.rounds == b.rounds
    np.testing.assert_allclose(a.deadlines, b.deadlines, rtol=1e-6)
    np.testing.assert_allclose(a.times, b.times, rtol=1e-6)
    # learning trajectories agree up to float summation order
    np.testing.assert_allclose(a.accuracy, b.accuracy, atol=0.015)
    np.testing.assert_allclose(a.train_loss, b.train_loss, rtol=0.02,
                               atol=0.02)


@pytest.mark.parametrize("method", ["adel", "salf"])
def test_dense_vs_chunked(setup, method):
    """chunk_size=3 pads the 8-client cohort to 9 and runs 3 chunks."""
    _assert_equivalent(_run(setup, method, "dense"),
                       _run(setup, method, "chunked"))


@pytest.mark.parametrize("method", ["adel", "salf"])
def test_dense_vs_shard_map_single_device(setup, method):
    """1 host device -> 1 shard holding the whole cohort; psum over a
    singleton axis must reproduce the dense aggregation."""
    _assert_equivalent(_run(setup, method, "dense"),
                       _run(setup, method, "shard_map"))


@pytest.mark.parametrize("method", ["adel", "salf"])
def test_dense_vs_temporal(setup, method):
    """The grad-accumulation scan (Eq. 5 coefficient fold) reproduces the
    vmapped dense aggregation."""
    _assert_equivalent(_run(setup, method, "dense"),
                       _run(setup, method, "temporal"))


def test_heterofl_same_on_all_backends(setup):
    hists = [_run(setup, "heterofl", bk) for bk in BACKENDS]
    for h in hists[1:]:
        _assert_equivalent(hists[0], h)


def test_single_chunk_falls_through_to_dense(setup):
    """chunk_size >= cohort: the chunked backend reuses the dense step."""
    _assert_equivalent(_run(setup, "salf", "dense"),
                       _run(setup, "salf", "chunked", chunk_size=U))


def test_backend_registry_and_padding():
    model = make_mlp()
    assert make_backend("dense", model).cohort_pad(10) == 10
    chunked = make_backend("chunked", model, chunk_size=8)
    assert chunked.cohort_pad(10) == 16
    assert chunked.cohort_pad(8) == 8      # single chunk, no dead padding
    assert chunked.cohort_pad(4) == 4      # chunk clipped to the cohort
    assert make_backend("temporal", model).cohort_pad(10) == 10
    for name, cls in [("dense", DenseBackend), ("chunked", ChunkedBackend),
                      ("shard_map", ShardMapBackend),
                      ("temporal", TemporalBackend)]:
        assert isinstance(make_backend(name, model), cls)
    bk = DenseBackend(model)
    assert make_backend(bk, model) is bk
    with pytest.raises(ValueError):
        make_backend("nope", model)


# ---------------------------------------------------------------------------
# compressed wire payloads (repro.core.compression)
# ---------------------------------------------------------------------------

# stated drift tolerance for compressed-vs-dense trajectories: int8
# symmetric quantization perturbs each aggregated delta element by at most
# amax/254 per contributor, which over R=5 rounds must not move final
# accuracy by more than the ISSUE's acceptance bound
COMPRESSED_ACC_TOL = 0.02


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_int8_compressed_drift_all_backends(setup, backend):
    """int8-compressed trajectories on every backend stay within the
    stated tolerance of the uncompressed dense run; plans and the
    simulated clock are untouched by compression."""
    base = _run(setup, "adel", "dense")
    comp = _run(setup, "adel", backend, compression="int8")
    assert comp.rounds == base.rounds
    np.testing.assert_allclose(comp.deadlines, base.deadlines, rtol=1e-6)
    np.testing.assert_allclose(comp.times, base.times, rtol=1e-6)
    np.testing.assert_allclose(comp.accuracy, base.accuracy,
                               atol=COMPRESSED_ACC_TOL)
    assert abs(comp.accuracy[-1] - base.accuracy[-1]) <= COMPRESSED_ACC_TOL


def test_compressed_backends_agree(setup):
    """The SAME deterministic quantization runs everywhere, so compressed
    backends agree with compressed dense to the usual summation-order
    tolerance."""
    ref = _run(setup, "adel", "dense", compression="int8")
    for backend in ("chunked", "shard_map", "temporal"):
        _assert_equivalent(ref, _run(setup, "adel", backend,
                                     compression="int8"))


def test_topk8_compressed_converges(setup):
    """Top-k sparsification at a generous kept fraction still tracks the
    dense run within the stated tolerance."""
    base = _run(setup, "adel", "dense")
    comp = _run(setup, "adel", "dense", compression=("topk8", 0.5))
    np.testing.assert_allclose(comp.times, base.times, rtol=1e-6)
    assert abs(comp.accuracy[-1] - base.accuracy[-1]) <= COMPRESSED_ACC_TOL


@pytest.mark.parametrize("backend", ["dense", "temporal"])
def test_agg_impl_pallas_matches_jnp(setup, backend):
    """agg_impl="pallas" routes Eq. 5 through the fused kernels (interpret
    mode on CPU) and must reproduce the jnp fold."""
    _assert_equivalent(_run(setup, "adel", backend),
                       _run(setup, "adel", backend, agg_impl="pallas"))


def test_pallas_agg_with_compression(setup):
    """Compression + the fused adel_agg_q8 kernel together."""
    _assert_equivalent(
        _run(setup, "adel", "dense", compression="int8"),
        _run(setup, "adel", "dense", compression="int8",
             agg_impl="pallas"))


def test_heterofl_rejects_compression(setup):
    """HeteroFL's width-overlap mean has no sound dequant-weight: every
    backend must refuse the combination up front."""
    for backend in BACKENDS:
        with pytest.raises(ValueError, match="HeteroFL"):
            _run(setup, "heterofl", backend, compression="int8")


def test_describe_reports_compression_and_agg_impl():
    model = make_mlp()
    d = make_backend("dense", model, compression="int8",
                     agg_impl="pallas").describe()
    assert d["compression"] == "int8" and d["agg_impl"] == "pallas"
    d = make_backend("chunked", model).describe()
    assert d["compression"] == "none" and d["agg_impl"] == "jnp"


def test_compressed_byte_counters(setup):
    """All four backends record the split logical/wire counters, with the
    same deterministic totals (chunked counts per padded chunk)."""
    from repro import obs
    model, cfg, data, schedule = setup
    totals = {}
    for backend in BACKENDS:
        sink = obs.MemorySink()
        policy = make_policy("adel", cfg, schedule=schedule)
        run_federated(model, policy, cfg, *data, key=jax.random.PRNGKey(0),
                      backend=backend,
                      chunk_size=3 if backend == "chunked" else None,
                      compression="int8", tracer=obs.Tracer(sink))
        ctr = {}
        for r in sink.records:
            if r.get("kind") == "count" and "bytes" in r.get("name", ""):
                ctr[r["name"]] = ctr.get(r["name"], 0) + r["value"]
        assert ctr["aggregate_bytes_logical"] > 0
        assert ctr["aggregate_bytes_wire"] > 0
        ratio = ctr["aggregate_bytes_logical"] / ctr["aggregate_bytes_wire"]
        assert ratio > 3.5, (backend, ctr)
        totals[backend] = ctr
    # dense / shard_map (1 host device) / temporal count the same padded
    # cohort; chunked pads 8 clients to 3 chunks of 3
    assert totals["dense"] == totals["temporal"]


# ---------------------------------------------------------------------------
# ExecSpec: one execution surface for every entry point
# ---------------------------------------------------------------------------


def _assert_bit_identical(a, b):
    assert a.rounds == b.rounds
    np.testing.assert_array_equal(np.asarray(a.deadlines),
                                  np.asarray(b.deadlines))
    np.testing.assert_array_equal(np.asarray(a.times), np.asarray(b.times))
    np.testing.assert_array_equal(np.asarray(a.accuracy),
                                  np.asarray(b.accuracy))
    np.testing.assert_array_equal(np.asarray(a.train_loss),
                                  np.asarray(b.train_loss))


def test_execspec_roundtrip_and_resolve():
    spec = ExecSpec(backend="chunked", chunk_size=4, compression="int8",
                    agg_impl="pallas")
    # the legacy compression spec forms normalize on construction
    assert spec.compression.mode == "int8"
    d = spec.as_dict()
    assert d["backend"] == "chunked" and d["compression"]["mode"] == "int8"
    # legacy kwargs overlay through THE parsing path; None means "keep"
    r = ExecSpec.resolve(spec, agg_impl="jnp")
    assert r.agg_impl == "jnp" and r.chunk_size == 4
    assert ExecSpec.resolve(spec) == spec
    with pytest.raises(TypeError, match="unknown execution kwargs"):
        ExecSpec.resolve(spec, not_a_knob=1)
    with pytest.raises(ValueError, match="unknown backend"):
        ExecSpec(backend="nope")
    with pytest.raises(ValueError):
        ExecSpec(lam=1.5)


def test_execspec_warns_on_ignored_knobs():
    with pytest.warns(UserWarning, match="chunk_size"):
        ExecSpec.resolve(backend="dense", chunk_size=4)
    with pytest.warns(UserWarning, match="staleness"):
        ExecSpec.resolve(backend="dense", lam=0.5)
    with pytest.raises(ValueError, match="mesh"):
        ExecSpec.resolve(backend="dense", mesh=object(), strict=True)


def test_execspec_strict_env(monkeypatch):
    monkeypatch.setenv("REPRO_EXEC_STRICT", "1")
    with pytest.raises(ValueError, match="chunk_size"):
        ExecSpec.resolve(backend="temporal", chunk_size=4)


def test_execspec_cli_roundtrip():
    ap = argparse.ArgumentParser()
    ExecSpec.add_cli_args(ap)
    args = ap.parse_args(["--backend", "buffered", "--lam", "0.3",
                          "--compression", "int8"])
    spec = ExecSpec.from_cli(args)
    assert spec.backend == "buffered" and spec.lam == 0.3
    assert spec.compression.mode == "int8"
    # no flags -> the front-end's base spec rides through unchanged
    assert ExecSpec.from_cli(ap.parse_args([]),
                             base=ExecSpec(backend="chunked",
                                           chunk_size=4)) == \
        ExecSpec(backend="chunked", chunk_size=4)


def test_make_backend_accepts_spec_and_legacy():
    model = make_mlp()
    spec = ExecSpec(backend="chunked", chunk_size=8)
    a = make_backend(exec=spec, model=model)
    b = make_backend("chunked", model, chunk_size=8)
    assert type(a) is type(b) is ChunkedBackend
    assert a.chunk_size == b.chunk_size == 8
    # an ExecSpec in the positional selector slot works too
    c = make_backend(spec, model)
    assert isinstance(c, ChunkedBackend) and c.chunk_size == 8
    buf = make_backend("buffered", model, lam=0.25, max_age=2)
    assert isinstance(buf, BufferedBackend)
    assert buf.lam == 0.25 and buf.max_age == 2
    assert not buf.needs_ctx ^ (buf.lam > 0)


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_execspec_equals_legacy_kwargs(setup, backend):
    """run_federated(backend=...) and run_federated(exec=ExecSpec(...))
    must produce bit-identical Histories on every backend."""
    model, cfg, data, schedule = setup
    kw = {"chunk_size": 3} if backend == "chunked" else {}
    legacy = _run(setup, "adel", backend, **kw)
    policy = make_policy("adel", cfg, schedule=schedule)
    _, spec_hist = run_federated(model, policy, cfg, *data,
                                 key=jax.random.PRNGKey(0),
                                 exec=ExecSpec(backend=backend, **kw))
    _assert_bit_identical(legacy, spec_hist)


# ---------------------------------------------------------------------------
# buffered (semi-async) backend: staleness-weighted delayed gradients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["adel", "salf"])
def test_buffered_lam0_bit_identical_to_dense(setup, method):
    """lam=0 is exact round-synchronous semantics: the buffered backend
    delegates every round to the inherited dense step, bit for bit."""
    _assert_bit_identical(_run(setup, method, "dense"),
                          _run(setup, method, "buffered"))


def _run_buffered(setup, method="adel", lam=0.6, tracer=None, backend=None,
                  **spec_kw):
    model, cfg, data, schedule = setup
    policy = make_policy(method, cfg,
                         schedule=schedule if method == "adel" else None)
    exec_spec = (None if backend is not None
                 else ExecSpec(backend="buffered", lam=lam, **spec_kw))
    return run_federated(model, policy, cfg, *data,
                         key=jax.random.PRNGKey(0), exec=exec_spec,
                         backend=backend, tracer=tracer)


def test_buffered_carries_late_work(setup):
    """lam>0 banks stragglers' unfinished layers and folds them into later
    rounds; the ledger rows carry the carried_in/out/stale columns and the
    drift summary aggregates them."""
    from repro import obs
    from repro.obs.ledger import drift_summary, ledger_rows
    sink = obs.MemorySink()
    _, hist = _run_buffered(setup, tracer=obs.Tracer(sink))
    rows = ledger_rows(sink.records)
    assert rows
    assert any(r.get("carried_in", 0) > 0 for r in rows), rows
    assert any(r.get("carried_out", 0) > 0 for r in rows)
    # staleness of every fold is >= 1 round (work banked at round t is
    # never folded before round t+1)
    taus = {int(tau) for r in rows for tau in (r.get("stale") or {})}
    assert taus and min(taus) >= 1
    drift = drift_summary(rows)
    assert drift.get("carried_in_total", 0) > 0
    assert drift.get("stale_mean", 0.0) >= 1.0
    assert np.isfinite(hist.accuracy[-1])


def test_buffered_int8_banks_wire_format(setup):
    """Under compression the carry buffer stores the int8 WIRE tuples the
    on-time reduction consumed — never re-materialized dense float32."""
    bk = make_backend("buffered", make_mlp(), lam=0.6, compression="int8")
    _, hist = _run_buffered(setup, backend=bk)
    assert bk.last_carry["carried_in"] > 0 or bk.last_carry["carried_out"] > 0
    assert bk._slots, "expected banked late work in the carry ring"
    q, scale = bk._slots[-1]["banked"][0][:2]
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert np.isfinite(hist.accuracy[-1])


def test_buffered_heterofl_lam_positive_rejected(setup):
    with pytest.raises(ValueError, match="HeteroFL"):
        _run_buffered(setup, method="heterofl")


def test_buffered_reset_state_between_runs(setup):
    """A backend instance reused across runs must not leak carry slots."""
    bk = make_backend("buffered", make_mlp(), lam=0.6)
    _run_buffered(setup, backend=bk)
    assert bk._slots
    bk.reset_state()
    assert not bk._slots and not bk.last_carry


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    import numpy as np
    assert len(jax.devices()) >= 4, jax.devices()

    from repro.core.baselines import make_policy
    from repro.core.scheduler import solve
    from repro.core.types import AnalysisConfig
    from repro.data.synthetic import make_image_dataset
    from repro.fl.partition import dirichlet_partition, stack_clients
    from repro.fl.server import run_federated
    from repro.models.paper_models import make_mlp

    x_tr, y_tr, x_te, y_te = make_image_dataset(
        "mnist", n_train=600, n_test=200, seed=0, noise_std=1.0)
    U, R = 8, 5
    parts = dirichlet_partition(y_tr, U, alpha=0.5, seed=0)
    cx, cy, counts = stack_clients(x_tr, y_tr, parts)
    model = make_mlp()
    cfg = AnalysisConfig.default(U=U, L=model.L, R=R, T_max=R * model.L * 0.5,
                                 eta0=2.0, seed=0)
    data = (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(counts),
            jnp.asarray(x_te), jnp.asarray(y_te))
    schedule = solve(cfg, "adam", steps=150)

    from repro.fl.backends import make_backend
    bk = make_backend("shard_map", model)
    assert bk.n_shards >= 4, bk.describe()
    assert bk.cohort_pad(U) == U  # 8 clients over 8 shards

    for method in ("adel", "salf"):
        hists = {}
        for backend in ("dense", "shard_map"):
            policy = make_policy(
                method, cfg, schedule=schedule if method == "adel" else None)
            _, hists[backend] = run_federated(
                model, policy, cfg, *data, key=jax.random.PRNGKey(0),
                backend=backend)
        a, b = hists["dense"], hists["shard_map"]
        assert a.rounds == b.rounds
        np.testing.assert_allclose(a.times, b.times, rtol=1e-6)
        np.testing.assert_allclose(a.accuracy, b.accuracy, atol=0.015)
        np.testing.assert_allclose(a.train_loss, b.train_loss, rtol=0.02,
                                   atol=0.02)
        print(method, "ok:", [round(x, 4) for x in b.accuracy])
    print("MULTIDEV_OK")
""")


def test_shard_map_multi_device_subprocess():
    """shard_map over >= 4 forced host devices matches dense, adel + salf."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # the child runs on forced host devices and never contends for a chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0 and "MULTIDEV_OK" in proc.stdout, (
        proc.stdout + "\n" + proc.stderr)


# -- named scopes inside the fused round steps --------------------------------
# each backend's step under the four aggregation rules it compiles
STEP_BRANCHES = {"jnp": {}, "pallas": dict(agg_impl="pallas"),
                 "int8": dict(compression="int8"), "hetero": {}}


def _step_scopes(backend, branch, U=4, s=4):
    """The scope segments (between slashes) of the op_name metadata of one
    compiled round step of the MLP, at small shapes."""
    model = make_mlp()
    bk = make_backend(backend, model, **STEP_BRANCHES[branch])
    params = model.init(jax.random.PRNGKey(0))
    L = model.L
    hetero = branch == "hetero"
    wm = (model.width_masks(params, np.asarray([1.0, 0.5, 0.25, 1.0][:U],
                                               np.float32))
          if hetero else None)
    args = (params, jnp.zeros((U, s, 28, 28, 1)), jnp.zeros((U, s), jnp.int32),
            jnp.full((U, s), 1.0 / s), jnp.ones((U, L)), jnp.zeros((L,)),
            jnp.float32(0.1), wm)
    text = bk._step(True, hetero).lower(*args).compile().as_text()
    return _scope_segments(text)


def _scope_segments(text: str) -> dict:
    """{scope: op_names under it} from compiled HLO text."""
    out: dict = {}
    for name in set(re.findall(r'op_name="([^"]*)"', text)):
        for seg in name.split("/"):
            out.setdefault(seg, []).append(name)
    return out


@pytest.mark.parametrize("branch", list(STEP_BRANCHES))
@pytest.mark.parametrize("backend", ["dense", "temporal"])
def test_round_step_named_scopes(backend, branch):
    """The compiled step's ops say what they belong to: each client's local
    update (its backward under transpose(jvp(...))), the Eq. 5 fold and the
    server's apply."""
    scopes = _step_scopes(backend, branch)
    for scope in ("local_train", "fold", "server_apply"):
        assert scope in scopes, (scope, sorted(scopes))
    assert any("transpose(jvp" in n for n in scopes["local_train"])
    assert "exchange" not in scopes


_SCOPES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import json, sys
    sys.path.insert(0, os.path.join(os.environ["REPO"], "tests"))
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    from test_backends import STEP_BRANCHES, _step_scopes
    print("SCOPES " + json.dumps({
        b: sorted(_step_scopes("shard_map", b)) for b in STEP_BRANCHES
        if b != "pallas"}))     # shard_map folds with jnp inside the map
""")


@pytest.fixture(scope="module")
def shard_map_scopes():
    env = dict(os.environ, REPO=REPO, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCOPES_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("SCOPES ")]
    assert proc.returncode == 0 and lines, proc.stdout + proc.stderr
    return json.loads(lines[-1][len("SCOPES "):])


@pytest.mark.parametrize("branch", ["jnp", "int8", "hetero"])
def test_shard_map_step_named_scopes(shard_map_scopes, branch):
    """shard_map over four host devices: its psums run under ``exchange``
    beside local_train, fold and server_apply."""
    for scope in ("local_train", "fold", "exchange", "server_apply"):
        assert scope in shard_map_scopes[branch], scope
