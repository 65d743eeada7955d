"""Pallas TPU kernels: Stage-I collision accumulation (paper §4.3 kernel ii).

Given per-key centroid ids and the per-(subspace, centroid) integer
tier-weight table (B, 2^m) — computed once per query from the ≤2^m bucket
ranking — accumulate S_i = Σ_b table[b, ids[i, b]].

One kernel serves both layouts:

* ``collision_paged_pallas``  — block-table-indirect over a paged metadata
  pool: the batch's block tables ride in SMEM (scalar prefetch) and drive
  the input BlockSpec's index_map, so each grid step DMAs exactly one
  physical (G, block_size, B) uint8 id tile HBM→VMEM. The logical id view
  is **never materialized** — this is the Stage-I half of the fused paged
  retrieval path, replacing the per-step ``paged_meta_view`` gather.
* ``collision_pallas``        — contiguous key stream (n, B), run as a
  one-head pool whose block table is the identity.

TPU adaptation: the per-key table lookup is a *gather*, which the VPU
dislikes; we re-express it as a one-hot × table product per subspace on
the MXU: (Hg, 2^m) table rows contracted with the (block_size, 2^m)
one-hot of the block's ids give all Hg query heads' partial scores
(Hg, block_size) at once, keys on lanes. Table entries are small integer
tier weights and the one-hot is 0/1, so bf16 operands with float32
accumulation are exact. The subspace loop is unrolled over the static B
(no dynamic slicing inside the kernel); the kv-head loop indexes the
untiled leading dim of the refs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# Lane width of a TPU vector register: the compiled kernel writes one
# (Hg, block_size) score tile per step, so block_size must fill whole lanes.
_LANES = 128


def _paged_kernel(bt_ref, ids_ref, table_ref, out_ref, *, num_kv_heads: int,
                  num_subspaces: int, num_centroids: int):
    """ids_ref (1, G, bs, B) uint8; table_ref (1, G, B, Hg, C) bf16;
    out_ref (1, G, Hg, bs) int32."""
    bs = ids_ref.shape[2]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bs, num_centroids), 1)

    def head(g, carry):
        ids = ids_ref[0, g].astype(jnp.int32)                 # (bs, B)
        acc = None
        for b in range(num_subspaces):
            onehot = (ids[:, b:b + 1] == iota).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                table_ref[0, g, b], onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (Hg, bs)
            acc = part if acc is None else acc + part
        out_ref[0, g] = acc.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, num_kv_heads, head, 0)


def collision_paged_pallas(block_tables: jax.Array, pool_ids: jax.Array,
                           tables: jax.Array, *, interpret=None) -> jax.Array:
    """Block-table-indirect Stage-I scores over a paged metadata pool.

    pool_ids:     (num_blocks, G, block_size, B) uint8 — the pool's
                  centroid ids, *physical* layout
                  (cache.PagedLayerKVCache.meta_ids).
    block_tables: (b, nblk) int32 — each sequence's logical→physical block
                  map (entries must be pre-clipped to [0, num_blocks);
                  positions under unallocated blocks are masked by enc_end
                  upstream).
    tables:       (b, G, Hg, B, C) integer tier weights (≤ 256, exact in
                  bf16).
    → (b, G, Hg, nblk · block_size) int32 logical collision scores.

    The flattened block tables are prefetched to SMEM and index the pool
    in the input BlockSpec — each (row, j) grid step streams one physical
    (G, block_size, B) id tile through VMEM and writes the logical block
    j's scores for every head. No (n_logical, B) id view exists in HBM.
    Compiled (non-interpret) calls need block_size % 128 == 0.
    """
    return _collision_paged_pallas(block_tables, pool_ids, tables,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _collision_paged_pallas(block_tables, pool_ids, tables, *,
                            interpret: bool):
    num_blocks, G, bs, B = pool_ids.shape
    b, nblk = block_tables.shape
    Hg, C = tables.shape[2], tables.shape[4]
    if not interpret and bs % _LANES:
        raise ValueError(f"compiled collision kernel needs block_size % "
                         f"{_LANES} == 0, got {bs}")
    # (b, G, B, Hg, C): one subspace's rows for all heads is a leading-dim
    # index inside the kernel
    tbl = jnp.swapaxes(tables, 2, 3).astype(jnp.bfloat16)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, G, bs, B),
                         lambda i, j, bt: (bt[i * nblk + j], 0, 0, 0)),
            pl.BlockSpec((1, G, B, Hg, C),
                         lambda i, j, bt: (i, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Hg, bs),
                               lambda i, j, bt: (i, 0, 0, j)),
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, num_kv_heads=G, num_subspaces=B,
                          num_centroids=C),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, G, Hg, nblk * bs), jnp.int32),
        interpret=interpret,
    )(bt_flat, pool_ids, tbl)


def collision_pallas(ids: jax.Array, table: jax.Array, *, block_n: int = 1024,
                     interpret=None) -> jax.Array:
    """ids: (..., n, B) uint8/int32; table: (..., B, C) int → scores
    (..., n) int32.

    The key stream is cut into ``block_n``-key blocks of a one-head pool
    with the identity block table. Arbitrary ``n`` is supported: the
    stream is zero-padded to the block multiple here (pad rows score
    against bucket 0 in every subspace) and the tail is sliced off —
    callers never pre-pad. Interpret-mode is resolved *outside* the jitted
    body so the REPRO_PALLAS_INTERPRET override is honored on every call,
    not frozen into the first trace's cache entry.
    """
    lead = ids.shape[:-2]
    n, B = ids.shape[-2:]
    C = table.shape[-1]
    ids = ids.reshape((-1, n, B))
    L = ids.shape[0]
    pad = (-n) % block_n
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros((L, pad, B), ids.dtype)], 1)
    nblk = (n + pad) // block_n
    pool = ids.reshape(L * nblk, 1, block_n, B)
    bt = jnp.arange(L * nblk, dtype=jnp.int32).reshape(L, nblk)
    tables = jnp.broadcast_to(table, lead + (B, C)).reshape(L, 1, 1, B, C)
    out = collision_paged_pallas(bt, pool, tables, interpret=interpret)
    return out[:, 0, 0, :n].reshape(lead + (n,))
