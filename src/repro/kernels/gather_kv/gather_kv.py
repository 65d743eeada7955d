"""Pallas TPU kernel: on-demand Top-k KV fetch (paper §4.3 kernel iv).

The UVA analogue (DESIGN.md §2): the retrieval-region KV store lives in
pooled (sequence-shardable) HBM; after Stage-II selects k row indices, this
kernel copies exactly those rows to the compute buffer. Realized with the
canonical Pallas *scalar-prefetch gather*: the index vector is prefetched
(SMEM) and drives the input BlockSpec's index_map, so each grid step DMAs
one selected row (1, G·hd) HBM→VMEM — only k·G·hd bytes move, never the
full store, which is the entire point of retrieval sparsity.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(*refs):
    rows_ref, out_ref = refs[-2:]        # after the scalar-prefetch refs
    out_ref[...] = rows_ref[...].reshape(out_ref.shape)


def gather_rows_pallas(store: jax.Array, idx: jax.Array, *,
                       interpret=None) -> jax.Array:
    """store (n, d), idx (k,) int32 → (k, d). One DMA per selected row.

    Interpret-mode resolves outside the jitted body (env override honored
    per call, not frozen into the first trace)."""
    return _gather_rows_pallas(store, idx,
                               interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_rows_pallas(store, idx, *, interpret: bool):
    n, d = store.shape
    k = idx.shape[0]
    # rows as (1, d) tiles of an (n, 1, d) view: a block's last two dims
    # then equal the array's, as the TPU lowering requires
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[pl.BlockSpec((1, 1, d),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, 1, d), store.dtype),
        interpret=interpret,
    )(idx, store.reshape(n, 1, d)).reshape(k, d)


def gather_rows_paged_pallas(pool: jax.Array, block_table: jax.Array,
                             idx: jax.Array, *,
                             interpret=None) -> jax.Array:
    """Block-table-indirect fetch from a paged pool.

    pool (num_blocks, block_size, d), block_table (nblk,) int32 mapping a
    sequence's logical blocks to physical blocks, idx (k,) int32 *logical*
    token positions → (k, d). Both the index vector and the block table
    ride in SMEM via scalar prefetch; the input index_map double-dereferences
    ``block_table[idx[i] // block_size]`` so each grid step DMAs exactly
    one (1, d) physical row HBM→VMEM — the paged UVA fetch.
    """
    return _gather_rows_paged_pallas(pool, block_table, idx,
                                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_rows_paged_pallas(pool, block_table, idx, *, interpret: bool):
    num_blocks, block_size, d = pool.shape
    k = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k,),
        in_specs=[pl.BlockSpec(
            (1, 1, 1, d),
            lambda i, idx_ref, bt_ref: (bt_ref[idx_ref[i] // block_size],
                                        idx_ref[i] % block_size, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, d),
                               lambda i, idx_ref, bt_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, 1, d), pool.dtype),
        interpret=interpret,
    )(idx, block_table,
      pool.reshape(num_blocks, block_size, 1, d)).reshape(k, d)
