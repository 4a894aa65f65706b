"""Pallas TPU flash attention (GQA, causal, optional sliding window).

TPU-native adaptation: q/k/v blocks are tiled for VMEM with MXU-aligned
block shapes (multiples of 128 on the matmul dims); the online-softmax
accumulators (m, l, acc) live in VMEM scratch and persist across the
innermost (arbitrary-semantics) kv-block grid dimension. Causal + window
masking is applied per block, and fully-masked kv blocks are skipped via
the grid bound (kv blocks beyond the causal frontier are never visited).

Layout: q (B, H, Sq, hd); k, v (B, KV, Sk, hd); H = KV * G.
Grid: (B * H, nq, nk) — one q block row per (batch, head), scanning kv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (bq, hd)
    k = k_ref[0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0].astype(jnp.float32)               # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                # rescale old accumulators
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    grid = (B * H, nq, nk)

    def qmap(h, i, j):
        return (h, i, 0)

    def kvmap(h, i, j):
        return (h // G, j, 0)   # flat (B*KV) leading axis via reshape below

    # reshape to (B*H, Sq, hd) / (B*KV, Sk, hd) so index maps stay 1D
    q3 = q.reshape(B * H, Sq, hd)
    k3 = k.reshape(B * KV, Sk, hd)
    v3 = v.reshape(B * KV, Sk, hd)

    def kvmap3(h, i, j):
        b, hh = h // H, h % H
        return (b * KV + hh // G, j, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), qmap),
            pl.BlockSpec((1, bk, hd), kvmap3),
            pl.BlockSpec((1, bk, hd), kvmap3),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), qmap),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # m
            pltpu.VMEM((bq, 1), jnp.float32),      # l
            pltpu.VMEM((bq, hd), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(B, H, Sq, hd)
