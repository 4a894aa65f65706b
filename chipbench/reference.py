"""Plain float32 reference of the ADEL-FL round, independent of the program.

One round, as the paper and the configuration state it:

1. every active client u takes the global weights w, runs one SGD step of
   the sample-weighted next-token loss on its minibatch (rows i < S_u
   weigh 1/S_u, the rest 0), and uploads ``delta_u = w - (w - eta g_u)``;
2. under ``int8`` the upload is the wire form: per (client, layer row of
   a leaf) ``scale = max|delta| / 127``, ``q = rint(delta / scale)``;
3. the server folds the layers each client finished (Eq. 5):
   ``c[u, l] = mask[u, l] / max(n_l, 1) / (1 - p_l)`` where ``n_l`` counts
   the clients that finished layer l (0 when none did), and
   ``w' = w - sum_u c[u, layer] * delta_u``, clients added in order.

The layer of each leaf row, and the forward pass itself, are the
configuration's family's (``families/<family>.py``: ``layer_map`` and
``logits``); nothing here names a leaf. Computed one client at a time, in
float32 with ``Precision.HIGHEST``. ``precision="fp8"`` is the control:
every matmul operand, forward and backward, rounded to float8 e4m3 with a
per-tensor scale.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families

HIGHEST = jax.lax.Precision.HIGHEST
EVAL_BLOCK = 8      # eval rows per call: the logits of 8 rows fit any cell


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(x):
    return x


_fp8_cotangent.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def make_mm(precision: str):
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: _fp8_cotangent(jnp.einsum(
            eq, _fp8_operand(a), _fp8_operand(b), precision=HIGHEST))
    raise ValueError(precision)


def row_nll(params, a: dict, rows, mm):
    """(B, S+1) rows -> (B,) mean next-token cross-entropy per row."""
    lg = families.load(a).logits(params, a, rows[:, :-1], mm)
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp, rows[:, 1:, None], -1)[..., 0]
    return nll.mean(-1)


def row_layers(a: dict) -> dict:
    """The family's layer map as 1-D int arrays: the mask layer of each row
    of a leaf (one row for a leaf wholly in one layer)."""
    return jax.tree.map(lambda lay: np.atleast_1d(np.asarray(lay, np.int32)),
                        families.load(a).layer_map(a),
                        is_leaf=lambda x: isinstance(x, tuple))


def by_row(ids: np.ndarray, x):
    """``x`` as ``(rows, -1)``, one row per entry of ``ids``."""
    if ids.size > 1 and x.shape[0] != ids.size:
        raise ValueError(f"a leaf of shape {x.shape} is mapped to "
                         f"{ids.size} layer rows")
    return x.reshape(ids.size, -1)


def fold(acc, d, c_row, layers: dict, wire: str):
    """``acc + c_row[layer] * d`` row by row of each leaf, with ``layers``
    from ``row_layers``; under ``int8`` each row of ``d`` first takes its wire
    form."""
    def one(ids, acc_l, d_l):
        x, c = by_row(ids, d_l), c_row[ids]
        if wire == "int8":
            amax = jnp.max(jnp.abs(x), -1)
            inv = jnp.where(amax > 0, 127.0 / amax, 0.0)
            x = jnp.rint(x * inv[:, None])
            c = c * (amax / 127.0)
        return acc_l + (c[:, None] * x).reshape(d_l.shape)
    return jax.tree.map(one, layers, acc, d)


def row_weights(batch: np.ndarray, s_max: int, rows: int) -> np.ndarray:
    """(U, rows) sample weights: 1/S for the first S = clip(S_u, 1, s_max)
    rows of each client."""
    S = np.clip(np.asarray(batch, np.float64), 1, s_max)
    return ((np.arange(rows)[None, :] < S[:, None]) / S[:, None]).astype(
        np.float32)


def coefficients(mask: np.ndarray, p: np.ndarray) -> np.ndarray:
    """Eq. 5 coefficients c[u, l] (bias-corrected layer-wise mean)."""
    mask = np.asarray(mask, np.float32)
    n = mask.sum(0)
    scale = np.where(n > 0, 1.0, 0.0) / np.maximum(1.0 - np.asarray(
        p, np.float32), 1e-6)
    return (mask * (scale / np.maximum(n, 1.0))[None, :]).astype(np.float32)


class Reference:
    """The reference round for one configuration, jitted per precision."""

    def __init__(self, a: dict, *, wire: str = "none",
                 precision: str = "f32"):
        mm = make_mm(precision)
        layers = row_layers(a)

        def delta(params, rows, w, eta):
            loss = lambda prm: jnp.sum(w * row_nll(prm, a, rows, mm))
            g = jax.grad(loss)(params)
            return jax.tree.map(lambda x, gg: x - (x - eta * gg), params, g)

        self._delta = jax.jit(delta)
        self._fold = jax.jit(
            lambda acc, d, c_row: fold(acc, d, c_row, layers, wire),
            donate_argnums=0)
        self._step = jax.jit(lambda p, acc: jax.tree.map(jnp.subtract, p,
                                                         acc),
                             donate_argnums=0)
        self._nll = jax.jit(lambda p, rows: row_nll(p, a, rows, mm).sum())

    def round(self, params, xb: np.ndarray, batch: np.ndarray,
              mask: np.ndarray, p: np.ndarray, eta: float, s_max: int,
              *, keep_rows: float = 1.0):
        """One round from ``params`` (consumed) on the round's own inputs.
        ``keep_rows`` < 1 is the fault of a batch cut short: each client
        trains on that share of its rows, the mean taken over them."""
        S = np.clip(np.asarray(batch, np.float64), 1, s_max)
        w = row_weights(np.maximum(np.floor(S * keep_rows), 1), s_max,
                        xb.shape[1])
        c = coefficients(mask, p)
        acc = jax.tree.map(jnp.zeros_like, params)
        for u in range(xb.shape[0]):
            if not c[u].any():
                continue                       # contributes nothing
            d = self._delta(params, jnp.asarray(xb[u]), jnp.asarray(w[u]),
                            jnp.float32(eta))
            acc = self._fold(acc, d, jnp.asarray(c[u]))
            del d
        return self._step(params, acc)

    def eval_loss(self, params, rows: np.ndarray) -> float:
        """Mean token cross-entropy over ``rows`` (equal-length rows, so the
        mean of the rows' means), in blocks of rows."""
        tot = 0.0
        for i in range(0, rows.shape[0], EVAL_BLOCK):
            tot += float(self._nll(params, jnp.asarray(
                rows[i:i + EVAL_BLOCK])))
        return tot / rows.shape[0]


@functools.lru_cache(maxsize=None)
def _norms_fn(arch: str):
    layers = row_layers(json.loads(arch))

    def norms(new, old):
        def one(ids, x, y):
            d = by_row(ids, x.astype(jnp.float32) - y.astype(jnp.float32))
            return jnp.sqrt(jnp.sum(d * d, -1))
        return jax.tree.map(one, layers, new, old)
    return jax.jit(norms)


def change_norms(new, old, a: dict) -> dict:
    """Per (leaf, row) norm of ``new - old``, a row for each layer row of the
    family's layer map: {"blocks/attn/wq/2": n}, the leaf's name alone for
    a leaf of one row. ``old`` is placed as ``new`` is (replicated over a
    mesh, say)."""
    old = jax.tree.map(lambda o, n: jax.device_put(o, n.sharding), old, new)
    tree = jax.device_get(_norms_fn(json.dumps(a, sort_keys=True))(new, old))
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(k.key) for k in path)
        v = np.asarray(v, np.float64)
        for i, x in enumerate(v):
            out[f"{name}/{i}" if v.size > 1 else name] = float(x)
    return out


def norm_gap(prog: dict, ref: dict, keep: set) -> tuple[float, str]:
    """Worst leaf's gap ``|n_prog - n_ref| / max(n_ref, median n_ref)`` over
    the leaves in ``keep``; returns (gap, leaf)."""
    med = float(np.median([ref[k] for k in keep]))
    worst, name = 0.0, ""
    for k in sorted(keep):
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if g > worst:
            worst, name = g, k
    return worst, name


def moving_leaves(grad_ref: dict) -> set:
    """Leaves the reference moves: first-step change at least a thousandth
    of the median leaf's (a key bias under softmax moves by round-off)."""
    med = float(np.median(list(grad_ref.values())))
    return {k for k, v in grad_ref.items() if v >= 1e-3 * med}
