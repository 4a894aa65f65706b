"""jit'd wrappers bridging model-layout tensors to the Pallas kernels.

``interpret`` defaults to True on the CPU backend and False on TPU; any
other backend raises, so a kernel never silently runs interpreted on an
accelerator. Each wrapper reshapes from model layout to kernel layout and
back, and is drop-in compatible with the pure-jnp path it accelerates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.adel_agg import adel_agg
from repro.kernels.embed_grad import embed_grad
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["gqa_flash", "ssd_chunked_pallas", "adel_aggregate_pallas",
           "embed_table_grad", "default_interpret"]


def default_interpret() -> bool:
    """Interpret the kernels on CPU, compile them on TPU, refuse the rest."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels target TPU (compiled) or CPU "
                       f"(interpreted); the default backend is {backend!r}")


def gqa_flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: int = 0,
              block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None) -> jnp.ndarray:
    """Model layout (B, S, H, hd) / (B, S, KV, hd) -> (B, S, H, hd)."""
    interpret = default_interpret() if interpret is None else interpret
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def ssd_chunked_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                       b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 64,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Model layout: x (B,S,H,P); dt (B,S,H); A (H,); b,c (B,S,N) -> y."""
    interpret = default_interpret() if interpret is None else interpret
    B, S, H, P = x.shape
    N = b.shape[-1]
    xdt = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)
    xdt = xdt.reshape(B * H, S, P)
    la = (-A[None, None, :] * dt).transpose(0, 2, 1).reshape(B * H, S)
    bh = jnp.broadcast_to(b[:, None], (B, H, S, N)).reshape(B * H, S, N)
    ch = jnp.broadcast_to(c[:, None], (B, H, S, N)).reshape(B * H, S, N)
    y = ssd_scan(xdt.astype(jnp.float32), la.astype(jnp.float32),
                 bh.astype(jnp.float32), ch.astype(jnp.float32),
                 chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3).astype(x.dtype)


def embed_table_grad(ids: jnp.ndarray, rows: jnp.ndarray,
                     num_rows: int) -> jnp.ndarray:
    """Gradient of a (num_rows, D) table from the cotangent rows (T, D) of
    a lookup at ``ids`` (T,): their float32 sum per id. On TPU the ids are
    sorted and the ``embed_grad`` kernel sums them; on CPU
    ``jax.ops.segment_sum`` does. All of it runs in the ``embed_grad``
    scope."""
    with jax.named_scope("embed_grad"):
        if default_interpret():
            return jax.ops.segment_sum(rows.astype(jnp.float32), ids,
                                       num_segments=num_rows)
        ids, perm = lax.sort_key_val(ids, lax.iota(jnp.int32, ids.shape[0]))
        return embed_grad(ids, rows[perm], num_rows)


def adel_aggregate_pallas(grads, layer_ids_tree, mask, p, *,
                          bias_correct: bool = True,
                          coeffs=None,
                          interpret: bool | None = None):
    """Pallas-backed equivalent of core.aggregation.aggregate_grads for
    pytrees whose leaves carry a leading client axis U.

    Stacked-layer leaves (ids of shape (L,)) go through the adel_agg kernel
    on their flattened feature dim; scalar-id leaves use the (U,) matvec.

    ``coeffs`` (U, L) overrides the internally computed Eq. 5 coefficients —
    the temporal backend folds one client at a time (U = 1 slices) against
    coefficients derived from GLOBAL cohort counts, which per-slice masks
    cannot reproduce.
    """
    from repro.core.aggregation import layer_coefficients
    interpret = default_interpret() if interpret is None else interpret
    if coeffs is None:
        coeffs = layer_coefficients(mask, p, bias_correct=bias_correct)
    c = coeffs                                                  # (U, L)

    def agg_leaf(g, ids):
        ids = jnp.asarray(ids)
        U = g.shape[0]
        if ids.ndim == 0:
            w = c[:, ids]                          # (U,)
            return jnp.tensordot(w, g.astype(jnp.float32),
                                 axes=(0, 0)).astype(g.dtype)
        L = g.shape[1]
        # the kernel is layer-major: (L, U, F) grads, (L, U) coefficients;
        # it pads F to a block multiple internally
        flat = jnp.swapaxes(g.reshape(U, L, -1), 0, 1)
        cl = jnp.take(c, ids, axis=1).T            # (L, U)
        out = adel_agg(flat, cl, interpret=interpret)
        return out.reshape(g.shape[1:]).astype(g.dtype)

    return jax.tree.map(agg_leaf, grads, layer_ids_tree)
