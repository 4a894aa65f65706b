"""Pallas TPU kernel for the token-embedding lookup's backward.

The lookup ``h = table[tokens]`` has the table gradient

    grad[v, :] = sum over t with tokens[t] == v of rows[t, :]

a segment sum of T cotangent rows into a dense (V, D) table. XLA lowers it
to a scatter-add, whose emitter is slow at some widths (1.8 us a row at
d_model 2560 on a v5e). Here the ids come sorted, so every block of ``bv``
vocabulary rows owns one contiguous range of the sorted rows. The grid runs
over *work items*, one per (vocabulary block, row chunk) pair that overlap,
plus one per empty block: an item adds its chunk into its block's f32
output tile with a one-hot (bv, chunk) matmul, and the first item of a
block starts the tile. Items of one block are consecutive, so each output
tile stays in VMEM until its block is done and is written once.

A one-hot matrix times the rows is exact, and the MXU accumulates in f32,
so the result is an exact f32 sum of the rows, up to summation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["embed_grad"]

CHUNK = 128               # sorted rows per work item
COLS = 512                # lanes per matmul, bounding its f32 temporary
# VMEM for the double-buffered (bv, D) f32 output tile and row chunk,
# under the TPU compiler's default scoped limit of 16 MiB
VMEM_BUDGET = 12 * 2 ** 20
MAX_BV = 1024


def _block_rows(V: int, D: int, itemsize: int) -> int:
    """The largest power-of-two vocabulary block that fits the budget and
    divides V."""
    if V % 8:
        raise ValueError(f"the table's rows ({V}) must be a multiple of 8")
    bv = MAX_BV
    while bv > 8 and (V % bv or 2 * bv * D * 4 + 2 * CHUNK * D * itemsize
                      > VMEM_BUDGET):
        bv //= 2
    return bv


def _work_items(sorted_ids, V: int, bv: int, n_chunks: int):
    """Each work item's vocabulary block, row chunk and flags (bit 0: the
    block's first item; bit 1: the block has rows to add)."""
    nb = V // bv
    # a few dozen queries: one compare-and-count beats a binary search's
    # loop of tiny device ops
    starts = jnp.searchsorted(sorted_ids, jnp.arange(nb + 1) * bv,
                              side="left", method="compare_all"
                              ).astype(jnp.int32)
    s, e = starts[:-1], starts[1:]
    nonempty = e > s
    cs = jnp.minimum(s // CHUNK, n_chunks - 1)
    ce = jnp.where(nonempty, (e - 1) // CHUNK, cs)
    n = ce - cs + 1                              # items per block, >= 1
    ends = jnp.cumsum(n)
    offs = ends - n
    # overlapping pairs are at most n_chunks + nb - 1, empty blocks included
    i = jnp.arange(n_chunks + nb, dtype=jnp.int32)
    valid = i < ends[-1]
    blk = jnp.minimum(jnp.searchsorted(ends, i, side="right",
                                       method="compare_all"),
                      nb - 1).astype(jnp.int32)
    # past the last item, stay on the last block and chunk: no new DMA
    chunk = jnp.where(valid, cs[blk] + i - offs[blk], ce[-1])
    flags = ((valid & (i == offs[blk])).astype(jnp.int32)
             + 2 * (valid & nonempty[blk]).astype(jnp.int32))
    return blk, chunk.astype(jnp.int32), flags


def _kernel(blk_ref, chunk_ref, flag_ref, ids_ref, rows_ref, o_ref, *,
            bv: int, precision):
    i = pl.program_id(0)
    flag = flag_ref[i]
    first, add = (flag & 1) != 0, (flag & 2) != 0
    D = o_ref.shape[1]

    @pl.when(first & jnp.logical_not(add))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def accumulate(assign: bool):
        local = ids_ref[...] - blk_ref[i] * bv                  # (1, C)
        hit = lax.broadcasted_iota(jnp.int32, (bv, CHUNK), 0) == local
        onehot = jnp.where(hit, 1.0, 0.0).astype(rows_ref.dtype)
        for c0 in range(0, D, COLS):
            sl = slice(c0, min(c0 + COLS, D))
            part = jnp.dot(onehot, rows_ref[:, sl], precision=precision,
                           preferred_element_type=jnp.float32)
            o_ref[:, sl] = part if assign else o_ref[:, sl] + part

    @pl.when(first & add)
    def _():
        accumulate(True)

    @pl.when(jnp.logical_not(first) & add)
    def _():
        accumulate(False)


def _segment_sum(sorted_ids, rows, num_rows: int, interpret: bool):
    T, D = rows.shape
    bv = _block_rows(num_rows, D, rows.dtype.itemsize)
    pad = (-T) % CHUNK
    if pad:               # id num_rows lies past every block and keeps order
        sorted_ids = jnp.pad(sorted_ids, (0, pad), constant_values=num_rows)
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    n_chunks = rows.shape[0] // CHUNK
    blk, chunk, flags = _work_items(sorted_ids, num_rows, bv, n_chunks)
    precision = (lax.Precision.HIGHEST if rows.dtype == jnp.float32
                 else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blk.shape[0],),
        in_specs=[
            pl.BlockSpec((None, 1, CHUNK), lambda i, b, c, f: (c[i], 0, 0)),
            pl.BlockSpec((CHUNK, D), lambda i, b, c, f: (c[i], 0)),
        ],
        out_specs=pl.BlockSpec((bv, D), lambda i, b, c, f: (b[i], 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, bv=bv, precision=precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="embed_grad",
    )(blk, chunk, flags, sorted_ids.reshape(n_chunks, 1, CHUNK), rows)


def embed_grad(sorted_ids: jnp.ndarray, rows: jnp.ndarray, num_rows: int, *,
               interpret: bool = False) -> jnp.ndarray:
    """Segment sum of ``rows`` (T, D) by ``sorted_ids`` (T,), ascending ids
    in [0, num_rows), into a float32 (num_rows, D) table; ``num_rows`` is
    a multiple of 8 (a padded vocabulary is one of 512).

    Under ``vmap`` the clients' ids are offset by ``u * num_rows`` and the
    batch becomes one call over a (U * num_rows, D) table: the offset ids
    stay sorted, so no re-sort is needed.
    """

    @jax.custom_batching.custom_vmap
    def call(ids, rows):
        return _segment_sum(ids, rows, num_rows, interpret)

    @call.def_vmap
    def _(axis_size, in_batched, ids, rows):
        ids_b, rows_b = in_batched
        if not ids_b:
            ids = jnp.broadcast_to(ids, (axis_size,) + ids.shape)
        if not rows_b:
            rows = jnp.broadcast_to(rows, (axis_size,) + rows.shape)
        ids = ids + (jnp.arange(axis_size, dtype=ids.dtype)
                     * num_rows)[:, None]
        out = embed_grad(ids.reshape(-1), rows.reshape(-1, rows.shape[-1]),
                         axis_size * num_rows, interpret=interpret)
        return out.reshape(axis_size, num_rows, -1), True

    return call(sorted_ids, rows)
