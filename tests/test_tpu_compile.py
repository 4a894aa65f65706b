"""Compile the main path for a described TPU v5e, with no chip attached.

Interpret mode cannot show what the TPU compiler refuses (block shapes off
its tiling, programs larger than the chip's memory), so these tests lower
and compile for a described ``v5e:2x2`` topology and read the compiled
program: the fold kernels at the real widths of one Qwen1.5-4B FFN leaf,
the temporal round step at the one-chip share (``repro.configs.qwen1_5_4b``)
and the shard_map round step over the four-chip mesh, and in both the
embedding gradient's kernel in place of XLA's scatter-add. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.fl.backends import make_backend
from repro.fl.spec import ExecSpec
from repro.fl.tasks import make_lm_model
from repro.kernels.adel_agg import adel_agg, adel_agg_q8

# the v5e's usable HBM as the compiler reports it ("of 15.75G hbm"), read
# as 10**9 bytes — the stricter of the two readings
HBM_BYTES = 15.75e9
F_FFN = 2560 * 6912         # one Qwen1.5-4B FFN leaf, flattened
SEQ, S_MAX = 512, 8         # the chip run's shapes (chip_smoke.py)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables would be written to the
        # persistent cache but could never be read back here
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _peak(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _round_args(model, U, L, rep, cli):
    """Abstract round-step arguments placed by the two shardings."""
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(model.init, sds((2,), np.uint32))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype, sharding=rep),
                          params)
    return (params, sds((U, S_MAX, SEQ + 1), jnp.int32, sharding=cli),
            sds((U, S_MAX), jnp.int32, sharding=cli),
            sds((U, S_MAX), jnp.float32, sharding=cli),
            sds((U, L), jnp.float32, sharding=cli),
            sds((L,), jnp.float32, sharding=rep),
            sds((), jnp.float32, sharding=rep), None)


def _compile_round(spec, U, rep, cli):
    cfg = get_config("qwen1.5-4b")
    model = make_lm_model(cfg)
    step = make_backend(spec, model)._step(True, False)
    return step.lower(*_round_args(model, U, cfg.L, rep, cli)).compile()


@pytest.mark.parametrize("U", [8, 1])     # temporal folds one client a step
@pytest.mark.parametrize("kernel", ["adel_agg", "adel_agg_q8"])
def test_fold_kernel_compiles(one_chip, kernel, U):
    L = 4
    w = jax.ShapeDtypeStruct((L, U), jnp.float32, sharding=one_chip)
    if kernel == "adel_agg":
        g = jax.ShapeDtypeStruct((L, U, F_FFN), jnp.float32,
                                 sharding=one_chip)
        lowered = adel_agg.lower(g, w)
    else:
        q = jax.ShapeDtypeStruct((L, U, F_FFN), jnp.int8, sharding=one_chip)
        lowered = adel_agg_q8.lower(q, w, w)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's own stable name, which a trace reader can find it by
    assert f'/{kernel}/pallas_call"' in text


@pytest.mark.parametrize("agg_impl", ["jnp", "pallas"])
def test_temporal_round_step_fits_one_chip(one_chip, agg_impl, monkeypatch):
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here; the compile is for the described chip
    monkeypatch.setattr("repro.kernels.ops.default_interpret", lambda: False)
    compiled = _compile_round(ExecSpec(backend="temporal",
                                       agg_impl=agg_impl), 8,
                              one_chip, one_chip)
    assert _peak(compiled) < HBM_BYTES
    # the fold kernel engages exactly when asked for (the embedding
    # gradient's kernel is in both)
    text = compiled.as_text()
    assert ('/adel_agg/pallas_call"' in text) == (agg_impl == "pallas")


def test_shard_map_round_step_on_2x2_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    compiled = _compile_round(ExecSpec(backend="shard_map", mesh=mesh), 4,
                              NamedSharding(mesh, P()),
                              NamedSharding(mesh, P("data")))
    assert "all-reduce" in compiled.as_text()
    assert _peak(compiled) < HBM_BYTES          # bytes per device


@pytest.mark.parametrize("layout,peak_bytes", [
    ("temporal", 11.50e9),      # U=8 on one chip
    ("shard_map", 14.80e9),     # U=8 over the 2x2, two clients a chip
])
def test_embedding_grad_is_the_kernel(topo, layout, peak_bytes, monkeypatch):
    """The table gradient is the ``embed_grad`` kernel (one call on four
    chips, whose vmap rule folds the clients into the vocabulary), and no
    scatter writes a (k * V_pad, D) table; the peak stays in its bound."""
    monkeypatch.setattr("repro.kernels.ops.default_interpret", lambda: False)
    if layout == "temporal":
        rep = cli = SingleDeviceSharding(topo.devices[0])
        spec = ExecSpec(backend="temporal")
    else:
        mesh = Mesh(np.asarray(topo.devices), ("data",))
        rep, cli = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        spec = ExecSpec(backend="shard_map", mesh=mesh)
    compiled = _compile_round(spec, 8, rep, cli)
    text = compiled.as_text()
    assert "/embed_grad/pallas_call" in text
    cfg = get_config("qwen1.5-4b")
    for dims in re.findall(r"= \w+\[([\d,]+)\][^ ]* scatter\(", text):
        *_, rows, width = map(int, dims.split(","))
        assert not (width == cfg.d_model and rows % cfg.padded_vocab == 0)
    assert _peak(compiled) <= peak_bytes
