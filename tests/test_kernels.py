"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adel_agg import adel_agg, adel_agg_q8
from repro.kernels.embed_grad import CHUNK, _block_rows, embed_grad
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import (adel_aggregate_pallas, gqa_flash,
                               ssd_chunked_pallas)
from repro.kernels.ref import (adel_agg_q8_ref, adel_agg_ref,
                               flash_attention_ref, ssd_scan_ref)


def _qs(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA g=2
    (1, 8, 1, 128, 128),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype):
    q = _qs((B, H, S, hd), 0, dtype)
    k = _qs((B, KV, S, hd), 1, dtype)
    v = _qs((B, KV, S, hd), 2, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_window(window):
    B, H, KV, S, hd = 1, 2, 1, 256, 64
    q, k, v = (_qs((B, H, S, hd), 0), _qs((B, KV, S, hd), 1),
               _qs((B, KV, S, hd), 2))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal_cross_shapes():
    """Sq != Sk (cross-attention shape)."""
    B, H, KV, hd = 2, 2, 2, 64
    q = _qs((B, H, 128, hd), 0)
    k = _qs((B, KV, 256, hd), 1)
    v = _qs((B, KV, 256, hd), 2)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_flash_model_layout():
    B, S, H, KV, hd = 2, 128, 4, 2, 64
    q = _qs((B, S, H, hd), 3)
    k = _qs((B, S, KV, hd), 4)
    v = _qs((B, S, KV, hd), 5)
    out = gqa_flash(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = jnp.swapaxes(flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2)), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 1, 64, 128, 64),     # mamba2-370m block dims
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    x = _qs((B, S, H, P), 0)
    dt = jax.nn.softplus(_qs((B, S, H), 1))
    A = jax.nn.softplus(_qs((H,), 2))
    b = 0.3 * _qs((B, S, N), 3)
    c = 0.3 * _qs((B, S, N), 4)
    out = ssd_chunked_pallas(x, dt, A, b, c, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, A, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_scan_state_carry_vs_chunking():
    """Chunk size must not change the result (state carried correctly)."""
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = _qs((B, S, H, P), 0)
    dt = jax.nn.softplus(_qs((B, S, H), 1))
    A = jax.nn.softplus(_qs((H,), 2))
    b, c = 0.3 * _qs((B, S, N), 3), 0.3 * _qs((B, S, N), 4)
    o1 = ssd_chunked_pallas(x, dt, A, b, c, chunk=16, interpret=True)
    o2 = ssd_chunked_pallas(x, dt, A, b, c, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# ADEL aggregation
# ---------------------------------------------------------------------------

def _layer_major(x):
    """(U, L, ...) client-major -> the kernels' (L, U, ...) layout."""
    return jnp.swapaxes(x, 0, 1)


@pytest.mark.parametrize("U,L,F,bf", [
    (4, 3, 512, 512),
    (16, 8, 1024, 256),
    (7, 5, 512, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adel_agg_sweep(U, L, F, bf, dtype):
    g = _qs((U, L, F), 0, dtype)
    c = jax.random.uniform(jax.random.PRNGKey(1), (U, L)).astype(dtype)
    out = adel_agg(_layer_major(g), c.T, block_f=bf, interpret=True)
    ref = adel_agg_ref(g, c)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("U,L,F,bf", [
    (3, 2, 300, 128),     # F not a multiple of block_f
    (4, 3, 130, 512),     # F < block_f and odd
    (2, 2, 7, 4),         # tiny, non-multiple
])
def test_adel_agg_nonmultiple_feature_dim(U, L, F, bf):
    """The kernel pads the flattened feature dim and slices the output."""
    g = _qs((U, L, F), 0)
    c = jax.random.uniform(jax.random.PRNGKey(1), (U, L))
    out = adel_agg(_layer_major(g), c.T, block_f=bf, interpret=True)
    assert out.shape == (L, F)
    ref = adel_agg_ref(g, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quantized ADEL aggregation (int8 wire payloads)
# ---------------------------------------------------------------------------

def _quantize(g):
    """The wire's symmetric int8 absmax quantization of (U, L, F) deltas."""
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = amax / 127.0
    inv = jnp.where(amax > 0, 127.0 / amax, 0.0)
    return jnp.rint(g * inv[..., None]).astype(jnp.int8), scale


@pytest.mark.parametrize("U,L,F,bf", [
    (4, 3, 512, 512),
    (7, 5, 300, 128),     # odd U, F not a multiple of block_f
    (3, 2, 130, 64),      # F < 2*block_f and non-multiple
    (2, 2, 7, 4),         # tiny, non-multiple
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_adel_agg_q8_sweep(U, L, F, bf, dtype):
    """Fused dequantize+weight+accumulate vs the pure-jnp oracle (the
    acceptance tolerance is atol 1e-2 in interpret mode)."""
    q, scale = _quantize(_qs((U, L, F), 0))
    c = jax.random.uniform(jax.random.PRNGKey(1), (U, L))
    out = adel_agg_q8(_layer_major(q), scale.T.astype(dtype),
                      c.T.astype(dtype), block_f=bf, interpret=True)
    assert out.shape == (L, F) and out.dtype == jnp.float32
    ref = adel_agg_q8_ref(q, scale.astype(dtype), c.astype(dtype))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref, np.float32),
                               atol=1e-2, rtol=1e-2)


def test_adel_agg_q8_zero_coefficient_rows():
    """Clients with all-zero Eq. 5 coefficients (deadline misses at depth
    0) must contribute nothing — dropping their rows gives the same sum."""
    U, L, F = 6, 4, 96
    q, scale = _quantize(_qs((U, L, F), 2))
    c = jax.random.uniform(jax.random.PRNGKey(3), (U, L))
    c = c.at[1].set(0.0).at[4].set(0.0)
    out = adel_agg_q8(_layer_major(q), scale.T, c.T, block_f=64,
                      interpret=True)
    keep = jnp.asarray([0, 2, 3, 5])
    ref = adel_agg_q8_ref(q[keep], scale[keep], c[keep])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_adel_agg_q8_zero_scale_layer():
    """An all-zero delta layer quantizes to scale 0 and must aggregate to
    exactly zero (the inv-scale guard, not NaN/inf)."""
    U, L, F = 3, 2, 64
    g = _qs((U, L, F), 4).at[:, 1, :].set(0.0)
    q, scale = _quantize(g)
    c = jnp.ones((U, L))
    out = adel_agg_q8(_layer_major(q), scale.T, c.T, block_f=64,
                      interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


def test_adel_agg_q8_dequant_error_bound():
    """End-to-end quantize -> fused aggregate stays within the absmax/254
    per-element bound times the summed coefficients."""
    U, L, F = 5, 3, 256
    g = _qs((U, L, F), 5)
    q, scale = _quantize(g)
    c = jax.random.uniform(jax.random.PRNGKey(6), (U, L))
    out = adel_agg_q8(_layer_major(q), scale.T, c.T, block_f=128,
                      interpret=True)
    dense = adel_agg_ref(g, c)
    bound = jnp.sum(c * jnp.max(jnp.abs(g), axis=-1) / 254.0, axis=0)
    err = jnp.max(jnp.abs(out - dense), axis=-1)
    assert np.all(np.asarray(err) <= np.asarray(bound) * 1.001)


def test_adel_agg_pytree_matches_reference_path():
    from repro.core.aggregation import aggregate_grads
    U, L = 5, 4
    key = jax.random.PRNGKey(3)
    grads = {"a": _qs((U, L, 24, 8), 0), "b": _qs((U, 10), 1)}
    ids = {"a": jnp.arange(L), "b": jnp.int32(1)}
    mask = (jax.random.uniform(key, (U, L)) > 0.4).astype(jnp.float32)
    p = jnp.full((L,), 0.08)
    out_k = adel_aggregate_pallas(grads, ids, mask, p, interpret=True)
    out_r = aggregate_grads(grads, ids, mask, p)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out_k[k]),
                                   np.asarray(out_r[k]), rtol=2e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# embedding gradient (sorted segment sum into an f32 table)
# ---------------------------------------------------------------------------

def _edges(V, D, T):
    """Ids on both sides of every vocabulary block's edge."""
    bv = _block_rows(V, D, 2)
    assert bv < V
    e = np.arange(bv, V, bv)
    return np.resize(np.stack([e - 1, e], 1).ravel(), T)


EMBED_CASES = {
    # name: (T, V, D, ids from (rng, T, V, D), clients)
    "repeated_ids": (512, 1024, 256, lambda r, T, V, D: r.integers(0, 9, T),
                     0),
    "block_edges": (256, 4096, 640, lambda r, T, V, D: _edges(V, D, T), 0),
    "empty_blocks": (256, 4096, 640,
                     lambda r, T, V, D: np.where(r.random(T) < .5,
                                                 r.integers(0, 50, T),
                                                 r.integers(V - 50, V, T)),
                     0),
    "one_row": (384, 2048, 256, lambda r, T, V, D: np.full(T, 1500), 0),
    "ragged_tail": (CHUNK * 2 + 37, 1024, 256,
                    lambda r, T, V, D: r.integers(0, V, T), 0),
    "d_not_512": (256, 2048, 640, lambda r, T, V, D: r.integers(0, V, T), 0),
    "vmapped_clients": (200, 1024, 256,
                        lambda r, T, V, D: r.integers(0, V, (3, T)), 3),
}


@pytest.mark.parametrize("case", sorted(EMBED_CASES))
def test_embed_grad_matches_segment_sum(case):
    """The kernel equals an f32 ``jax.ops.segment_sum`` of the bf16 rows."""
    T, V, D, make_ids, U = EMBED_CASES[case]
    rng = np.random.default_rng(7)
    ids = jnp.sort(jnp.asarray(make_ids(rng, T, V, D), jnp.int32), axis=-1)
    rows = _qs(ids.shape + (D,), 0, jnp.bfloat16)

    def ref(i, r):
        return jax.ops.segment_sum(r.astype(jnp.float32), i, num_segments=V)

    def kern(i, r):
        return embed_grad(i, r, V, interpret=True)

    if U:
        ref, kern = jax.vmap(ref), jax.vmap(kern)
    out = kern(ids, rows)
    assert out.shape == ids.shape[:-1] + (V, D) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(ids, rows)),
                               rtol=1e-6, atol=1e-6)
