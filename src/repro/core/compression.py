"""Delta compression for the client -> server wire (int8 / top-k payloads).

At fleet scale the round bottleneck is moving and reducing U full-precision
delta pytrees. This module defines the compressed wire format and the
aggregation that consumes it directly — the float32 delta tree is never
re-materialized per client:

* ``int8`` — symmetric absmax quantization with one float32 scale per
  (client, layer): ``scale[u, l] = max_f |d[u, l, f]| / 127``,
  ``q = rint(d / scale)`` (deterministic round-to-nearest, so trajectories
  and byte counts are exactly reproducible). 4 bytes/element -> 1 byte.
* ``topk8`` — per-(client, layer) top-k by magnitude over the flattened
  feature dim, int8 values + int32 indices (5 bytes per kept entry), same
  absmax scale. Wire cost ``~1.25 * top_k`` of dense float32.

Every leaf is handled in the canonical wire layout (U, L_leaf, F):
stacked-layer leaves (layer ids of shape (L,)) flatten trailing dims to F;
whole-tensor leaves are L_leaf = 1. Aggregation folds the Eq. 5 coefficient
``c[u, l]`` INTO the dequant scale, so dequantize + weight + accumulate is
one pass — pure-jnp einsum / scatter-add, or the fused Pallas
``kernels.adel_agg_q8`` (which reads the payload layer-major) when
``agg_impl="pallas"`` (interpret mode on CPU).

The payload crossing the jit/device boundary is a flat list (params-tree
flatten order) of per-leaf tuples ``(q, scale)`` or ``(q, scale, idx)`` —
a plain pytree, so chunked's chunk-sum and shard_map's shard-local
reduction consume int8 rather than float32 trees.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CompressionConfig",
    "make_compression",
    "compress_deltas",
    "aggregate_compressed",
    "payload_bytes",
]

PyTree = Any

MODES = ("none", "int8", "topk8")


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Client->server payload compression spec (hashable; lives inside
    frozen configs such as :class:`repro.configs.base.FleetConfig`).

    ``mode``: "none" | "int8" | "topk8"; ``top_k``: kept fraction of the
    flattened feature dim per (client, layer) in topk8 mode.
    """
    mode: str = "none"
    top_k: float = 0.05

    def __post_init__(self):
        assert self.mode in MODES, f"unknown compression mode {self.mode!r}"
        assert 0.0 < self.top_k <= 1.0

    def wire_scale(self) -> float:
        """Expected wire bytes as a fraction of the dense float32 payload
        (per-layer scale scalars excluded — negligible for real F). This is
        the ``comm_scale`` the Problem-2 cost model prices B_u with."""
        if self.mode == "int8":
            return 0.25
        if self.mode == "topk8":
            return 1.25 * self.top_k          # 1B value + 4B index per kept
        return 1.0


def make_compression(spec) -> CompressionConfig:
    """None | mode string | (mode, top_k) | CompressionConfig -> config."""
    if spec is None:
        return CompressionConfig()
    if isinstance(spec, CompressionConfig):
        return spec
    if isinstance(spec, str):
        return CompressionConfig(mode=spec)
    mode, top_k = spec
    return CompressionConfig(mode=mode, top_k=float(top_k))


def _leaf_dims(shape, ids_ndim: int) -> tuple[int, int]:
    """Canonical (L_leaf, F) of one param leaf."""
    if ids_ndim == 0:
        return 1, int(np.prod(shape)) if shape else 1
    return int(shape[0]), int(np.prod(shape[1:])) if shape[1:] else 1


def _leaf_k(F: int, cfg: CompressionConfig) -> int:
    return max(1, min(F, int(math.ceil(cfg.top_k * F))))


def _compress_leaf(g: jnp.ndarray, ids, cfg: CompressionConfig):
    """One delta leaf (U,) + param.shape -> wire tuple in (U, Ll, F) form."""
    ids = jnp.asarray(ids)
    U = g.shape[0]
    Ll, F = _leaf_dims(g.shape[1:], ids.ndim)
    flat = g.reshape(U, Ll, F).astype(jnp.float32)
    amax = jnp.max(jnp.abs(flat), axis=-1)                    # (U, Ll)
    scale = amax / 127.0
    inv = jnp.where(amax > 0, 127.0 / amax, 0.0)
    if cfg.mode == "int8":
        q = jnp.rint(flat * inv[..., None]).astype(jnp.int8)
        return (q, scale)
    k = _leaf_k(F, cfg)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)                  # (U, Ll, k)
    vals = jnp.take_along_axis(flat, idx, axis=-1)
    q = jnp.rint(vals * inv[..., None]).astype(jnp.int8)
    return (q, scale, idx.astype(jnp.int32))


def compress_deltas(deltas: PyTree, layer_ids: PyTree,
                    cfg: CompressionConfig) -> list:
    """Compress a stacked delta pytree (leading client axis U on every
    leaf) into the wire payload: a flat list, in ``jax.tree.flatten``
    order, of ``(q int8 (U, Ll, F), scale f32 (U, Ll))`` tuples —
    plus ``idx int32 (U, Ll, K)`` in topk8 mode."""
    leaves, _ = jax.tree.flatten(deltas)
    id_leaves, _ = jax.tree.flatten(layer_ids)
    return [_compress_leaf(g, i, cfg) for g, i in zip(leaves, id_leaves)]


def _leaf_coeff_rows(c: jnp.ndarray, ids) -> jnp.ndarray:
    """Eq. 5 coefficient rows for one leaf: (U, Ll)."""
    ids = jnp.asarray(ids)
    if ids.ndim == 0:
        return c[:, ids][:, None]
    return jnp.take(c, ids, axis=1)


def _agg_leaf(entry, param, ids, c, cfg: CompressionConfig,
              agg_impl: str, interpret: bool) -> jnp.ndarray:
    w = _leaf_coeff_rows(c, ids)                              # (U, Ll)
    shape = param.shape
    Ll, F = _leaf_dims(shape, jnp.asarray(ids).ndim)
    if cfg.mode == "topk8":
        q, scale, idx = entry
        contrib = (w * scale)[..., None] * q.astype(jnp.float32)
        l_idx = jnp.broadcast_to(jnp.arange(Ll)[None, :, None], idx.shape)
        out = jnp.zeros((Ll, F), jnp.float32).at[l_idx, idx].add(contrib)
        return out.reshape(shape)
    q, scale = entry
    if agg_impl == "pallas":
        # the kernel is layer-major: (Ll, U, F) payload, (Ll, U) weights
        from repro.kernels.adel_agg import adel_agg_q8
        out = adel_agg_q8(jnp.swapaxes(q, 0, 1), scale.T, w.T,
                          interpret=interpret)
    else:
        out = jnp.einsum("ul,ulf->lf", w * scale, q.astype(jnp.float32))
    return out.reshape(shape)


def aggregate_compressed(payload: list, params: PyTree, layer_ids: PyTree,
                         mask: jnp.ndarray, p: jnp.ndarray, *,
                         cfg: CompressionConfig,
                         counts: jnp.ndarray | None = None,
                         coeffs: jnp.ndarray | None = None,
                         bias_correct: bool = True,
                         agg_impl: str = "jnp",
                         interpret: bool | None = None) -> PyTree:
    """Fused dequantize + Eq. 5 weight + accumulate over the wire payload.

    Returns the aggregated float32 delta pytree (params structure; no
    client axis). ``counts`` supplies GLOBAL per-layer contributor counts
    (chunked / shard-local partials); ``coeffs`` overrides the Eq. 5
    coefficients entirely (temporal's one-client-at-a-time fold against
    cohort-global coefficients). ``params`` is used for leaf shapes only.
    """
    from repro.core.aggregation import layer_coefficients
    if interpret is None and agg_impl == "pallas":
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    if coeffs is None:
        coeffs = layer_coefficients(mask, p, bias_correct=bias_correct,
                                    counts=counts)
    p_leaves, treedef = jax.tree.flatten(params)
    id_leaves, _ = jax.tree.flatten(layer_ids)
    out = [_agg_leaf(e, pl, i, coeffs, cfg, agg_impl, interpret)
           for e, pl, i in zip(payload, p_leaves, id_leaves)]
    return jax.tree.unflatten(treedef, out)


def payload_bytes(params: PyTree, layer_ids: PyTree, U: int,
                  cfg: CompressionConfig) -> tuple[int, int]:
    """Deterministic analytic (logical, wire) byte counts for a U-client
    round payload.

    ``logical`` is the dense float32 delta pytree (4 bytes/element times
    U), independent of the model dtype — the uncompressed baseline every
    mode is measured against. ``wire`` is what the compressed payload
    actually ships: int8 values + float32 per-(client, layer) scales
    (+ int32 indices in topk8 mode).
    """
    logical = wire = 0
    for pleaf, ids in zip(jax.tree.leaves(params),
                          jax.tree.leaves(layer_ids)):
        Ll, F = _leaf_dims(pleaf.shape, getattr(ids, "ndim", 0))
        logical += 4 * Ll * F
        if cfg.mode == "int8":
            wire += Ll * F + 4 * Ll
        elif cfg.mode == "topk8":
            k = _leaf_k(F, cfg)
            wire += 5 * Ll * k + 4 * Ll
        else:
            wire += 4 * Ll * F
    return U * logical, U * wire
