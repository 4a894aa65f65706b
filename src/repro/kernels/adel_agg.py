"""Pallas TPU kernels for ADEL-FL's layer-wise masked aggregation (Eq. 5).

The server-side hot loop of the paper: combine U clients' per-layer
gradients with per-(client, layer) coefficients

    out[l, f] = sum_u coeff[u, l] * grads[u, l, f]

i.e. a U-contraction batched over layers, tiled over the flattened feature
dim. The kernels take their operands LAYER-MAJOR — grads ``(L, U, F)``,
coefficients ``(L, U)`` — so every block's last two dims are
``(U, block_f)``, ``(U, 1)`` and ``(1, block_f)``: each equal to the full
array dim or a multiple of 128 lanes, which is what the TPU compiler
(Mosaic) requires of a block. A client-major ``(U, L, F)`` operand would
need a block of one layer row in the second-to-last dim, which Mosaic
refuses. Callers swap the two leading axes; with one client per fold (the
temporal backend's scan) that swap is a free reshape.

The contraction over U is an f32 multiply and a sublane reduction on the
vector unit, so the kernels are exact f32 sums at any matmul precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["adel_agg", "adel_agg_q8"]

# feature-block width: at 16384 lanes an (8, block_f) f32 tile is 512 KiB,
# so inputs and output double-buffered stay a few MiB of VMEM, while the
# grid over one Qwen1.5-4B FFN leaf (2560 * 6912 features) is 1080 steps
# rather than the 34560 of a 512-lane block
BLOCK_F = 16384


def _pad_features(x: jnp.ndarray, block_f: int) -> tuple[jnp.ndarray, int]:
    """Zero-pad the last dim of ``x`` to a multiple of the block width."""
    F = x.shape[-1]
    bf = min(block_f, F)
    pad = (-F) % bf
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    return x, bf


def _fold(grads: jnp.ndarray, weights: jnp.ndarray, kernel, out_dtype,
          block_f: int, interpret: bool, name: str) -> jnp.ndarray:
    """Shared pallas_call: (L, U, F) x (L, U, k) weight columns -> (L, F).
    ``name`` is the kernel's name in the compiled program and the device
    trace, kept stable so a reader can find the kernel by it."""
    L, U, F = grads.shape
    grads, bf = _pad_features(grads, block_f)
    Fp = grads.shape[-1]
    w_spec = pl.BlockSpec((None, U, 1), lambda l, f: (l, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(L, Fp // bf),
        in_specs=[pl.BlockSpec((None, U, bf), lambda l, f: (l, 0, f))]
        + [w_spec] * len(weights),
        out_specs=pl.BlockSpec((None, 1, bf), lambda l, f: (l, 0, f)),
        out_shape=jax.ShapeDtypeStruct((L, 1, Fp), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(grads, *[w.astype(jnp.float32)[..., None] for w in weights])
    return out[:, 0, :F]


def _kernel(g_ref, c_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)             # (U, bf)
    c = c_ref[...]                                 # (U, 1) f32
    o_ref[...] = jnp.sum(c * g, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def adel_agg(grads: jnp.ndarray, coeff: jnp.ndarray, *,
             block_f: int = BLOCK_F, interpret: bool = False) -> jnp.ndarray:
    """grads: (L, U, F) layer-major; coeff: (L, U) -> (L, F) in grads' dtype.

    Arbitrary F is supported: the flattened feature dim is zero-padded up to
    a ``block_f`` multiple for the kernel grid and the output sliced back.
    """
    return _fold(grads, [coeff], _kernel, grads.dtype, block_f, interpret,
                 "adel_agg")


def _kernel_q8(q_ref, s_ref, c_ref, o_ref):
    g = q_ref[...].astype(jnp.float32)             # (U, bf) dequant source
    # fold the Eq. 5 coefficient into the per-(client, layer) dequant scale
    # so dequantize + weight + accumulate is one pass over the int8 block
    w = c_ref[...] * s_ref[...]                    # (U, 1) f32
    o_ref[...] = jnp.sum(w * g, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def adel_agg_q8(q: jnp.ndarray, scales: jnp.ndarray, coeff: jnp.ndarray, *,
                block_f: int = BLOCK_F,
                interpret: bool = False) -> jnp.ndarray:
    """Fused dequantize + Eq. 5 weight + accumulate over int8 payloads.

    q: (L, U, F) int8 symmetric-quantized client deltas, layer-major;
    scales: (L, U) per-(layer, client) dequant scales (absmax / 127);
    coeff: (L, U) Eq. 5 aggregation coefficients.
    Returns (L, F) float32 = sum_u coeff[l, u] * scales[l, u] * q[l, u, :]
    — the reduction consumes the int8 wire format directly; the float32
    delta tree is never materialized per client.
    """
    return _fold(q, [scales, coeff], _kernel_q8, jnp.float32, block_f,
                 interpret, "adel_agg_q8")
