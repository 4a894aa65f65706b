"""Per-architecture smoke tests: a REDUCED variant of each assigned family
(2 layers, d_model <= 512, <= 4 experts) runs one forward + one federated
train step on CPU; output shapes check out and nothing is NaN."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.kernels import ops as kernel_ops
from repro.kernels.embed_grad import embed_grad
from repro.models import transformer as tr
from repro.launch.steps import make_train_step, make_serve_step

ARCH_NAMES = sorted(ARCHS)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _front(cfg, key, U=None, b=2):
    if cfg.frontend == "none":
        return None
    shape = ((U, b, cfg.n_frontend_tokens, cfg.d_model) if U
             else (b, cfg.n_frontend_tokens, cfg.d_model))
    return 0.02 * jax.random.normal(key, shape)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_reduced_constraints(name):
    r = ARCHS[name].reduced()
    assert r.L == 2
    assert r.d_model <= 512
    assert r.n_experts <= 4
    assert r.n_heads % r.n_kv == 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_no_nan(name, key):
    cfg = ARCHS[name].reduced()
    params = tr.init_params(key, cfg)
    B, S = 2, 32
    tok = jax.random.randint(key, (B, S), 0, cfg.vocab)
    logits, aux = tr.forward(params, cfg, tok, frontend=_front(cfg, key))
    S_out = S + (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
    assert logits.shape == (B, S_out, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_federated_train_step(name, key):
    """One ADEL federated round on the reduced config: loss drops params
    change, everything finite."""
    cfg = ARCHS[name].reduced()
    params = tr.init_params(key, cfg)
    U, b, S = 3, 2, 16
    L_tot = cfg.n_blocks_total
    tok = jax.random.randint(key, (U, b, S), 0, cfg.vocab)
    lab = jax.random.randint(key, (U, b, S), 0, cfg.vocab)
    mask = jnp.ones((U, L_tot), jnp.float32).at[0, 0].set(0.0)
    p = jnp.full((L_tot,), 0.05, jnp.float32)
    step = make_train_step(cfg, U=U, mode="temporal", remat=False)
    args = [params, tok, lab, mask, p, jnp.float32(0.1)]
    if cfg.frontend != "none":
        args.append(_front(cfg, key, U=U, b=b))
    new_params = jax.jit(step)(*args)
    leaves_old = jax.tree.leaves(params)
    leaves_new = jax.tree.leaves(new_params)
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in leaves_new)
    changed = sum(bool(np.any(np.asarray(a) != np.asarray(bb)))
                  for a, bb in zip(leaves_old, leaves_new))
    assert changed > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_serve_step(name, key):
    cfg = ARCHS[name].reduced()
    params = tr.init_params(key, cfg)
    B = 2
    cache = tr.init_cache(cfg, B, 32, dtype=jnp.float32)
    if cfg.enc_layers:
        frames = 0.02 * jax.random.normal(
            key, (B, cfg.n_frontend_tokens, cfg.d_model))
        enc_out = tr._run_encoder(params, cfg, frames, jnp.dtype(cfg.dtype))
        cache = cache._replace(cross=tr.build_cross_cache(params, cfg, enc_out))
    tok = jax.random.randint(key, (B,), 0, cfg.vocab)
    step = jax.jit(make_serve_step(cfg))
    nxt, cache2 = step(params, cache, tok, jnp.int32(0))
    assert nxt.shape == (B,)
    assert nxt.dtype == jnp.int32
    nxt2, _ = step(params, cache2, nxt, jnp.int32(1))
    assert np.isfinite(np.asarray(nxt2, np.float32)).all()


def _cast_then_gather(table, tokens, dtype):
    """The lookup as ``forward`` wrote it before ``embed_lookup``."""
    return table.astype(dtype)[tokens]


def _gather_then_cast(table, tokens, dtype):
    """Autodiff of this form scatter-adds the rows into an f32 table."""
    return table[tokens].astype(dtype)


LOOKUP_NAMES = ["qwen1.5-4b", "command-r-35b"]     # dense; tied embeddings


@pytest.mark.parametrize("name", LOOKUP_NAMES)
def test_embed_lookup_forward_is_bit_identical(name, key, monkeypatch):
    cfg = ARCHS[name].reduced(dtype="bfloat16")
    params = tr.init_params(key, cfg)
    tok = jax.random.randint(key, (2, 32), 0, cfg.vocab)

    def logits():
        return np.asarray(jax.jit(lambda p, t: tr.forward(p, cfg, t)[0])(
            params, tok), np.float32)

    new = logits()
    monkeypatch.setattr(tr, "embed_lookup", _cast_then_gather)
    np.testing.assert_array_equal(new, logits())


@pytest.mark.parametrize("path", ["segment_sum", "kernel"])
@pytest.mark.parametrize("repeated", [False, True])
@pytest.mark.parametrize("name", LOOKUP_NAMES)
def test_embed_grad_is_an_f32_sum(name, repeated, path, key, monkeypatch):
    """The table's gradient through ``loss_fn`` is the f32 sum of the bf16
    cotangent rows, as the f32 gather's own autodiff computes it; with
    tied embeddings the head's gradient adds to it."""
    cfg = ARCHS[name].reduced(dtype="bfloat16")
    params = tr.init_params(key, cfg)
    k1, k2 = jax.random.split(key)
    tok = jax.random.randint(k1, (2, 32), 0, 5 if repeated else cfg.vocab)
    lab = jax.random.randint(k2, (2, 32), 0, cfg.vocab)

    def grad():
        return np.asarray(jax.jit(jax.grad(tr.loss_fn), static_argnums=1)(
            params, cfg, tok, lab)["embed"])

    if path == "kernel":       # the TPU path, its kernel interpreted
        monkeypatch.setattr(kernel_ops, "default_interpret", lambda: False)
        monkeypatch.setattr(kernel_ops, "embed_grad",
                            lambda *a: embed_grad(*a, interpret=True))
    g = grad()
    monkeypatch.setattr(tr, "embed_lookup", _gather_then_cast)
    ref = grad()
    assert g.dtype == np.float32
    np.testing.assert_allclose(g, ref, rtol=1e-5,
                               atol=1e-6 * np.abs(ref).max())
