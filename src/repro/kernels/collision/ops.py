"""Batched wrappers over the collision kernel, interpret-mode autodetect
(see repro.kernels.resolve_interpret)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.collision.collision import (collision_paged_pallas,
                                               collision_pallas)


def collision_scores_kernel(ids: jax.Array, table: jax.Array,
                            block_n: int = 1024) -> jax.Array:
    """Batched collision scores. ids (..., n, B), table (..., B, C) → (..., n).

    Tail padding to the block multiple happens inside collision_pallas.
    """
    return collision_pallas(ids, table, block_n=block_n)


def collision_scores_paged_kernel(pool_ids: jax.Array,
                                  block_tables: jax.Array,
                                  tables: jax.Array, enc_end: jax.Array,
                                  sink_size: int) -> jax.Array:
    """Batched block-table-indirect Stage-I scores, masked to the valid
    retrieval region — the kernel twin of
    ``core.retrieval.collision_scores_paged``.

    pool_ids:     (num_blocks, G, block_size, B) uint8 (shared pool)
    block_tables: (b, nblk) int32 (entries < 0 = unallocated → clipped;
                  their positions are masked by ``enc_end``)
    tables:       (b, G, Hg, B, C) int32 tier-weight tables
    enc_end:      (b,) int32 retrieval-region end per row
    → (b, G, Hg, nblk · block_size) int32 scores, -1 outside
    [sink_size, enc_end).
    """
    nb, _, bs, _ = pool_ids.shape
    b, nblk = block_tables.shape
    safe_bt = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    scores = collision_paged_pallas(safe_bt, pool_ids, tables)  # (b,G,Hg,n)
    pos = jnp.arange(nblk * bs)
    valid = (pos[None] >= sink_size) & (pos[None] < enc_end[:, None])
    return jnp.where(valid[:, None, None, :], scores, -1)
