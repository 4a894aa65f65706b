"""Aggregation invariants (Eq. 5) incl. hypothesis property tests and the
shard_map/psum path equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core.aggregation import (aggregate_grads, aggregate_grads_local,
                                    layer_coefficients, masked_mean_grads)


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 9), st.integers(1, 7), st.integers(1, 5),
       st.integers(0, 2 ** 30))
def test_full_mask_recovers_fedavg(U, L, F, seed):
    """With everyone contributing and p = 0, Eq. (5) is exactly FedAvg."""
    g = np.random.default_rng(seed).normal(size=(U, L, F)).astype(np.float32)
    mask = jnp.ones((U, L))
    p = jnp.zeros((L,))
    agg = aggregate_grads({"w": jnp.asarray(g)}, {"w": jnp.arange(L)},
                          mask, p)["w"]
    np.testing.assert_allclose(np.asarray(agg), g.mean(0), rtol=2e-5,
                               atol=1e-6)


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 8), st.integers(2, 6), st.integers(0, 2 ** 30),
       st.floats(0.0, 0.19))
def test_scale_equivariance(U, L, seed, p_val):
    """agg(c * g) = c * agg(g) — aggregation is linear in the gradients."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(U, L, 3)).astype(np.float32))
    mask = jnp.asarray((rng.random((U, L)) > 0.4).astype(np.float32))
    p = jnp.full((L,), p_val, jnp.float32)
    ids = {"w": jnp.arange(L)}
    a1 = aggregate_grads({"w": 2.5 * g}, ids, mask, p)["w"]
    a2 = 2.5 * aggregate_grads({"w": g}, ids, mask, p)["w"]
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=2e-5,
                               atol=1e-6)


def test_empty_layer_zero_and_correction():
    U, L = 5, 4
    g = jnp.ones((U, L, 2))
    mask = jnp.ones((U, L)).at[:, 2].set(0.0)
    p = jnp.asarray([0.0, 0.1, 0.5, 0.19])
    agg = aggregate_grads({"w": g}, {"w": jnp.arange(L)}, mask, p)["w"]
    np.testing.assert_allclose(np.asarray(agg[2]), 0.0)
    np.testing.assert_allclose(np.asarray(agg[1]), 1 / 0.9, rtol=1e-6)


def test_masked_mean_no_correction():
    U, L = 4, 3
    g = jnp.ones((U, L, 2))
    mask = jnp.ones((U, L))
    out = masked_mean_grads({"w": g}, {"w": jnp.arange(L)}, mask)["w"]
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)


def test_shard_map_psum_path_matches():
    """aggregate_grads_local under shard_map == aggregate_grads globally."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    U, L, F = 4, 3, 6   # single CPU device -> 1 shard holding all clients
    g = _rand((U, L, F), 0)
    mask = (jax.random.uniform(jax.random.PRNGKey(1), (U, L)) > 0.3
            ).astype(jnp.float32)
    p = jnp.full((L,), 0.1)
    ids = {"w": jnp.arange(L)}

    ref = aggregate_grads({"w": g}, ids, mask, p)["w"]

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    fn = shard_map(
        lambda gg, mm: aggregate_grads_local({"w": gg}, ids, mm, p,
                                             "clients")["w"],
        mesh=mesh, in_specs=(P("clients"), P("clients")),
        out_specs=P())
    out = fn(g, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


@settings(deadline=None, max_examples=25)
@given(st.integers(2, 8), st.integers(2, 6), st.integers(0, 2 ** 30))
def test_coefficients_rowsum(U, L, seed):
    """For layers with k>0 contributors, coefficients sum to 1/(1-p_l);
    empty layers sum to 0 (update preserved)."""
    rng = np.random.default_rng(seed)
    mask = jnp.asarray((rng.random((U, L)) > 0.5).astype(np.float32))
    p = jnp.asarray(rng.uniform(0, 0.19, L).astype(np.float32))
    c = layer_coefficients(mask, p)
    sums = np.asarray(c.sum(0))
    counts = np.asarray(mask.sum(0))
    expect = np.where(counts > 0, 1.0 / (1.0 - np.asarray(p)), 0.0)
    np.testing.assert_allclose(sums, expect, rtol=1e-5, atol=1e-6)
