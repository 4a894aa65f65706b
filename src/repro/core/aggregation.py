"""Layer-wise bias-corrected aggregation (Eq. 5 of the paper), in gradient form.

For client updates w_u^l = w^l - eta * g_u^l, Eq. (5)

    w~_{t+1}^l = w~_t^l                                  if |U_t^l| = 0
               = ( mean_{u in U^l} w_u^l - p^l w~_t^l ) / (1 - p^l)   otherwise

is algebraically equivalent to the *gradient-space* rule

    g~^l = 0                                             if |U_t^l| = 0
         = mean_{u in U^l} g_u^l / (1 - p^l)             otherwise

followed by w~_{t+1} = w~_t - eta g~. We implement the gradient form: it is
a masked weighted reduction over the client axis, which on the TPU mesh is a
single (masked) all-reduce — the paper's server-side aggregation mapped onto
jax.lax collectives.

Parameter->layer mapping: models expose ``layer_ids(params)``, a pytree
congruent with ``params`` whose leaves are int32 arrays of shape
  * ()    — the whole tensor belongs to that layer, or
  * (L,)  — the leading axis is the stacked-layer axis; entry i gives the
            layer id of slice i (normally arange(L)).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "layer_coefficients",
    "weight_by_layer",
    "aggregate_with_coeffs",
    "aggregate_grads",
    "aggregate_grads_chunk",
    "aggregate_grads_local",
    "hetero_overlap_partials",
    "hetero_overlap_mean",
    "masked_mean_grads",
]

PyTree = Any


def layer_coefficients(mask: jnp.ndarray, p: jnp.ndarray,
                       *, bias_correct: bool = True,
                       counts: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-(client, layer) aggregation coefficient c[u, l].

    agg^l = sum_u c[u, l] g_u^l reproduces Eq. (5):
      c[u, l] = mask[u, l] / count_l / (1 - p_l)   if count_l > 0 else 0.

    ``counts`` may be supplied externally (global counts under shard_map).
    """
    if counts is None:
        counts = mask.sum(0)                      # (L,)
    denom = jnp.maximum(counts, 1.0)
    scale = jnp.where(counts > 0, 1.0, 0.0)
    if bias_correct:
        scale = scale / jnp.maximum(1.0 - p, 1e-6)
    return mask * (scale / denom)[None, :]        # (U, L)


def weight_by_layer(g: jnp.ndarray, ids: jnp.ndarray,
                    c_row: jnp.ndarray) -> jnp.ndarray:
    """Scale ONE client's grad/delta leaf by its per-layer coefficient row.

    This is the Eq. 5 coefficient fold used by temporal (grad-accumulation)
    client layouts: summing ``weight_by_layer(g_u, ids, c[u])`` over clients
    u equals :func:`aggregate_grads` with coefficients ``c`` — but the
    accumulation never holds more than one gradient pytree.

    ``ids``: () whole-tensor layer id, or (L,) stacked-axis ids; ``c_row``:
    (L_total,) this client's coefficients.
    """
    ids = jnp.asarray(ids)
    if ids.ndim == 0:
        return g * c_row[ids]
    w = jnp.take(c_row, ids)                       # (L,)
    return g * w.reshape((-1,) + (1,) * (g.ndim - 1))


def _weight_leaf(g: jnp.ndarray, ids: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Reduce one grads leaf g of shape (U,)+param.shape with coeffs c (U, L)."""
    ids = jnp.asarray(ids)
    if ids.ndim == 0:
        w = c[:, ids]                             # (U,)
        return jnp.tensordot(w, g, axes=(0, 0))
    # stacked: g is (U, L, ...); weight (U, L) broadcast over trailing dims
    w = jnp.take(c, ids, axis=1)                  # (U, L)
    return jnp.einsum("ul,ul...->l...", w, g)


def aggregate_with_coeffs(grads: PyTree, layer_ids: PyTree,
                          coeffs: jnp.ndarray) -> PyTree:
    """Reduce stacked per-client grads with EXPLICIT coefficients.

    ``agg^l = sum_u coeffs[u, l] g_u^l`` — the raw coefficient fold that
    :func:`aggregate_grads` specializes with the Eq. 5 on-time
    coefficients. The buffered backend calls it directly with
    staleness-decayed late-set coefficients whose (mask, p) were fixed at
    the round the work belongs to.

    grads leaves: (U,) + param.shape; coeffs: (U, L).
    """
    return jax.tree.map(lambda g, ids: _weight_leaf(g, ids, coeffs),
                        grads, layer_ids)


def aggregate_grads(grads: PyTree, layer_ids: PyTree, mask: jnp.ndarray,
                    p: jnp.ndarray, *, bias_correct: bool = True) -> PyTree:
    """ADEL-FL aggregation of stacked per-client grads.

    grads: pytree with a leading client axis U on every leaf.
    mask: (U, L) contribution mask; p: (L,) zero-contributor probabilities.
    Returns the aggregated gradient pytree (no client axis).
    """
    c = layer_coefficients(mask, p, bias_correct=bias_correct)
    return jax.tree.map(lambda g, ids: _weight_leaf(g, ids, c), grads, layer_ids)


def aggregate_grads_local(local_grads: PyTree, layer_ids: PyTree,
                          local_mask: jnp.ndarray, p: jnp.ndarray,
                          axis_name: str | tuple[str, ...],
                          *, bias_correct: bool = True) -> PyTree:
    """shard_map/explicit-collective variant: each shard holds a slice of the
    client axis; counts and weighted sums are combined with jax.lax.psum.

    local_grads leaves: (U_local,) + param.shape; local_mask: (U_local, L).
    The psums run under the ``exchange`` named scope, the weighting under
    ``fold``.
    """
    with jax.named_scope("exchange"):
        counts = jax.lax.psum(local_mask.sum(0), axis_name)   # (L,) global
    with jax.named_scope("fold"):
        c = layer_coefficients(local_mask, p, bias_correct=bias_correct,
                               counts=counts)
        partial = jax.tree.map(lambda g, ids: _weight_leaf(g, ids, c),
                               local_grads, layer_ids)
    with jax.named_scope("exchange"):
        return jax.lax.psum(partial, axis_name)


def aggregate_grads_chunk(chunk_grads: PyTree, layer_ids: PyTree,
                          chunk_mask: jnp.ndarray, p: jnp.ndarray,
                          counts: jnp.ndarray, *,
                          bias_correct: bool = True) -> PyTree:
    """Sequential-chunk analogue of :func:`aggregate_grads_local`.

    The caller supplies the GLOBAL per-layer contributor counts and sums the
    returned partial aggregates over chunks — a software psum over the
    client-shard axis, so a large cohort never materializes one stacked
    (cohort, ...) delta pytree. Summing the partials over every chunk is
    exactly ``aggregate_grads`` on the concatenated client axis, and the
    chunk axis maps 1:1 onto a ``shard_map`` client mesh axis (swap the host
    loop for ``jax.lax.psum``).

    The same identity powers hierarchical two-tier aggregation
    (:class:`repro.fl.backends.HierarchicalBackend`): each edge REGION is
    one "chunk" — its partial aggregate, evaluated against the global
    counts, is what crosses the wide-area network, and the global fold is
    just the sum of region partials.

    chunk_grads leaves: (U_chunk,) + param.shape; chunk_mask: (U_chunk, L).
    """
    c = layer_coefficients(chunk_mask, p, bias_correct=bias_correct,
                           counts=counts)
    return jax.tree.map(lambda g, ids: _weight_leaf(g, ids, c),
                        chunk_grads, layer_ids)


def hetero_overlap_partials(deltas: PyTree, wmasks: PyTree,
                            part: jnp.ndarray) -> tuple[PyTree, PyTree]:
    """Per-shard partials of the HeteroFL width-overlap mean.

    HeteroFL averages each parameter ENTRY over the participating clients
    whose width-reduced submodel contains it:

        agg = sum_u part_u wm_u d_u / max(sum_u part_u wm_u, 1)

    Both sums are linear over the client axis, so — exactly like
    :func:`aggregate_grads_chunk` / :func:`aggregate_grads_local` — a
    backend computes local (num, den) partials over its slice of clients
    and combines them with a chunk-sum or ``jax.lax.psum`` before the
    final divide in :func:`hetero_overlap_mean`.

    deltas/wmasks leaves: (U_local,) + param.shape; part: (U_local,)
    participation indicator (all-or-nothing rows of the layer mask).
    """
    def w(wm):
        return part.reshape((-1,) + (1,) * (wm.ndim - 1)) * wm

    num = jax.tree.map(lambda d, wm: (w(wm) * d).sum(0), deltas, wmasks)
    den = jax.tree.map(lambda wm: w(wm).sum(0), wmasks)
    return num, den


def hetero_overlap_mean(num: PyTree, den: PyTree) -> PyTree:
    """Finish the width-overlap mean from globally combined partials;
    entries no participating client covers keep delta 0."""
    return jax.tree.map(lambda n, d: n / jnp.maximum(d, 1.0), num, den)


def masked_mean_grads(grads: PyTree, layer_ids: PyTree,
                      mask: jnp.ndarray) -> PyTree:
    """Plain masked mean without bias correction (Drop-Stragglers-style when
    given an all-or-nothing mask; SALF-without-correction ablation)."""
    p = jnp.zeros(mask.shape[1], mask.dtype)
    return aggregate_grads(grads, layer_ids, mask, p, bias_correct=False)
