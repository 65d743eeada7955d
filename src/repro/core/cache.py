"""ParisKV cache state: Sink / Retrieval / Local / Update-Buffer regions.

Layout of one layer's cache (paper Fig. 5), realized with *static shapes*
(XLA requirement — DESIGN.md §2 assumption (3)):

      0 ........ sink | sink ........ enc_end | enc_end ....... pos | ...
      [   Sink     ]   [   Retrieval region ]  [ Local + Update buf ]

* ``[0, sink)``        — attention sinks, always attended densely (on-chip).
* ``[sink, enc_end)``  — retrieval region: full-precision K/V live in the
  pooled (sequence-shardable) store; per-key metadata (centroid ids, 4-bit
  codes, weights) is encoded and fresh.
* ``[enc_end, pos]``   — the most recent ``local_size`` tokens plus up to
  ``update_interval`` buffered new tokens, attended densely via one
  static-size window slice of length W = local_size + update_interval.

The **sliding-window update** (§4.2.1): once ``pos + 1 - enc_end`` reaches
W, the oldest ``update_interval`` tokens of the window are *promoted into
the retrieval region* by encoding their metadata in one vectorized block
(amortized, exactly as the paper's periodic codebook update), and
``enc_end`` advances by ``update_interval``. Under jit this is a
``lax.cond`` + ``dynamic_update_slice`` of a static-size block.

Region state is **per sequence**: ``CacheRegions.pos``/``enc_end`` are
``(b,)`` int32 vectors so every row of a batch tracks its own position and
retrieval-region boundary (continuous batching admits requests into cache
slots at different times, so rows are never in lockstep). Promotion is
per-row: each row triggers when *its* window fills, and the block encode
runs under one ``lax.cond`` guarded by "any row triggered", with the
results applied only to triggered rows. All public helpers also accept
scalar ``pos``/``enc_end`` (legacy single-sequence call sites, tests) and
broadcast internally.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import encode
from repro.core.config import ParisKVConfig


class LayerKVCache(NamedTuple):
    """Per-layer, per-batch KV store + ParisKV metadata.

    k, v:        (b, n_max, G, hd)
    meta_ids:    (b, G, n_max, B) uint8   — Stage-I centroid assignments
    meta_codes:  (b, G, n_max, B) uint32  — Stage-II packed 4-bit codes
    meta_w:      (b, G, n_max, B) float32 — RSQ-IP weights w_{i,b}

    A **latent** cache (MLA) has ``v`` None: its one K row per token
    (G = 1, hd = the latent width) is read as the value too, so it is
    stored once. Every helper here writes ``v`` only where it exists.
    """
    k: jax.Array
    v: Optional[jax.Array]
    meta_ids: jax.Array
    meta_codes: jax.Array
    meta_w: jax.Array


class CacheRegions(NamedTuple):
    pos: jax.Array       # (b,) int32: index of each row's most recent token
    enc_end: jax.Array   # (b,) int32: retrieval-region end (exclusive)


def _as_batch(x: jax.Array, batch: int) -> jax.Array:
    """Broadcast a scalar or (b,) region field to a (b,) int32 vector."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32), (batch,))


def window_size(cfg: ParisKVConfig) -> int:
    return cfg.local_size + cfg.update_interval


def initial_regions(lengths: jax.Array, cfg: ParisKVConfig) -> CacheRegions:
    """Per-row regions right after prefilling prompts of ``lengths`` (b,):
    pos at the last prompt token, enc_end clamped so the trailing
    local window stays dense (and never below the sink)."""
    lengths = jnp.asarray(lengths, jnp.int32)
    enc_end = jnp.maximum(jnp.minimum(cfg.sink_size, lengths),
                          lengths - cfg.local_size)
    return CacheRegions(pos=lengths - 1, enc_end=enc_end)


def init_layer_cache(batch: int, n_max: int, num_kv_heads: int, head_dim: int,
                     cfg: ParisKVConfig, dtype=jnp.bfloat16,
                     values: bool = True) -> LayerKVCache:
    """``values=False`` builds a latent cache (no ``v`` leaf)."""
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    return LayerKVCache(
        k=jnp.zeros((batch, n_max, g, head_dim), dtype),
        v=jnp.zeros((batch, n_max, g, head_dim), dtype) if values else None,
        meta_ids=jnp.zeros((batch, g, n_max, B), jnp.uint8),
        meta_codes=jnp.zeros((batch, g, n_max, B), jnp.uint32),
        meta_w=jnp.zeros((batch, g, n_max, B), jnp.float32),
    )


def cache_spec(batch: int, n_max: int, num_kv_heads: int, head_dim: int,
               cfg: ParisKVConfig, dtype=jnp.bfloat16,
               values: bool = True) -> LayerKVCache:
    """ShapeDtypeStruct twin of init_layer_cache — used by the dry-run."""
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    sds = jax.ShapeDtypeStruct
    return LayerKVCache(
        k=sds((batch, n_max, g, head_dim), dtype),
        v=sds((batch, n_max, g, head_dim), dtype) if values else None,
        meta_ids=sds((batch, g, n_max, B), jnp.uint8),
        meta_codes=sds((batch, g, n_max, B), jnp.uint32),
        meta_w=sds((batch, g, n_max, B), jnp.float32),
    )


def _encode_block(keys_block: jax.Array, cfg: ParisKVConfig,
                  signs: jax.Array) -> encode.KeyMetadata:
    """keys_block (b, L, G, hd) → metadata with layout (b, G, L, B)."""
    kt = jnp.moveaxis(keys_block, 2, 1)  # (b, G, L, hd)
    return encode.encode_keys(kt, cfg, signs)


def prefill_write(cache: LayerKVCache, k_new: jax.Array, v_new: jax.Array,
                  cfg: ParisKVConfig, signs: jax.Array,
                  lengths: Optional[jax.Array] = None
                  ) -> Tuple[LayerKVCache, CacheRegions]:
    """Write a full prompt's K/V and encode the retrieval-region metadata.

    k_new/v_new: (b, S, G, hd), LEFT-aligned prompts. ``lengths`` (b,) gives
    each row's true prompt length (default: all S). Metadata is encoded for
    every position (the valid mask at retrieval time restricts to
    [sink, enc_end)); per-row enc_end is set so each row's trailing local
    window stays dense. Positions ≥ lengths[i] hold padding garbage that is
    never attended (every mask is bounded by pos/enc_end) and is overwritten
    as row i decodes.
    """
    b, S = k_new.shape[:2]
    cache = cache._replace(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), 0, axis=1),
        v=None if cache.v is None else jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new.astype(cache.v.dtype), 0, axis=1),
    )
    meta = _encode_block(k_new, cfg, signs)
    cache = cache._replace(
        meta_ids=jax.lax.dynamic_update_slice_in_dim(cache.meta_ids, meta.centroid_ids, 0, axis=2),
        meta_codes=jax.lax.dynamic_update_slice_in_dim(cache.meta_codes, meta.codes, 0, axis=2),
        meta_w=jax.lax.dynamic_update_slice_in_dim(cache.meta_w, meta.weights, 0, axis=2),
    )
    if lengths is None:
        lengths = jnp.full((b,), S, jnp.int32)
    return cache, initial_regions(_as_batch(lengths, b), cfg)


def decode_append(cache: LayerKVCache, k_t: jax.Array, v_t: jax.Array,
                  pos: jax.Array) -> LayerKVCache:
    """Append one token's K/V at per-row position ``pos`` (scalar or (b,)).

    k_t/v_t: (b, G, hd)."""
    b = k_t.shape[0]
    pos = _as_batch(pos, b)
    upd = jax.vmap(lambda c, t, p: jax.lax.dynamic_update_slice_in_dim(
        c, t[None], p, axis=0))
    return cache._replace(
        k=upd(cache.k, k_t.astype(cache.k.dtype), pos),
        v=None if cache.v is None else upd(cache.v, v_t.astype(cache.v.dtype),
                                           pos),
    )


def promote_block(cache: LayerKVCache, start: jax.Array,
                  cfg: ParisKVConfig, signs: jax.Array) -> LayerKVCache:
    """Encode metadata for keys [start, start+update_interval) in place."""
    blk_k = jax.lax.dynamic_slice_in_dim(
        cache.k, start, cfg.update_interval, axis=1)
    meta = _encode_block(blk_k, cfg, signs)
    return cache._replace(
        meta_ids=jax.lax.dynamic_update_slice_in_dim(
            cache.meta_ids, meta.centroid_ids, start, axis=2),
        meta_codes=jax.lax.dynamic_update_slice_in_dim(
            cache.meta_codes, meta.codes, start, axis=2),
        meta_w=jax.lax.dynamic_update_slice_in_dim(
            cache.meta_w, meta.weights, start, axis=2),
    )


def promote_rows(cache: LayerKVCache, starts: jax.Array, mask: jax.Array,
                 cfg: ParisKVConfig, signs: jax.Array) -> LayerKVCache:
    """Per-row block promotion: for each batch row ``i`` with ``mask[i]``,
    encode metadata for keys [starts[i], starts[i]+update_interval).

    Rows with ``mask[i] == False`` are returned unchanged (the block encode
    still runs for them — vectorized — but the result is discarded), which
    is what keeps this a single fused computation under jit even when rows
    promote at different decode steps.
    """
    U = cfg.update_interval
    b = cache.k.shape[0]
    starts = _as_batch(starts, b)
    blk_k = jax.vmap(lambda k, s: jax.lax.dynamic_slice_in_dim(
        k, s, U, axis=0))(cache.k, starts)               # (b, U, G, hd)
    meta = _encode_block(blk_k, cfg, signs)              # (b, G, U, B)

    def upd(dst, new):
        out = jax.vmap(lambda d, n, s: jax.lax.dynamic_update_slice_in_dim(
            d, n, s, axis=1))(dst, new, starts)
        m = mask.reshape((b,) + (1,) * (dst.ndim - 1))
        return jnp.where(m, out, dst)

    return cache._replace(
        meta_ids=upd(cache.meta_ids, meta.centroid_ids),
        meta_codes=upd(cache.meta_codes, meta.codes),
        meta_w=upd(cache.meta_w, meta.weights),
    )


# ----------------------------------------------------------------------
# Chunked prefill (ISSUE 5): append a prompt chunk into a slot's region
# ----------------------------------------------------------------------
#
# The serving engines no longer have to prefill a prompt in one blocking
# forward pass: models.serve.decode_chunk can consume ``prefill_budget``
# prompt tokens per step for one "filling" slot while every other slot
# decodes. The helpers below are the cache side of that mixed step: they
# scatter one chunk's K/V (+ metadata) into the filling row — contiguous
# or through a block table — dropping the final partial chunk's pad tail
# instead of writing garbage (rings in particular must never hold
# positions that were not really produced).


def fill_enc_end(fill_pos: jax.Array, cfg: ParisKVConfig) -> jax.Array:
    """Retrieval-region end for a partially filled prompt whose first
    ``fill_pos`` tokens are written — ``initial_regions``' boundary as a
    function of fill progress, so a completed fill lands on exactly the
    regions a solo prefill of the same prompt would produce."""
    f = jnp.asarray(fill_pos, jnp.int32)
    return jnp.maximum(jnp.minimum(cfg.sink_size, f), f - cfg.local_size)


def fill_chunk_write(cache: LayerKVCache, row: jax.Array, start: jax.Array,
                     k_chunk: jax.Array, v_chunk: jax.Array,
                     valid: jax.Array, meta=None) -> LayerKVCache:
    """Scatter one prompt chunk into batch row ``row`` at positions
    [start, start+P): k_chunk/v_chunk (P, G, hd), ``valid`` (P,) bool
    (False → the write is dropped; the final partial chunk's tail),
    ``meta`` optional KeyMetadata arrays of shape (G, P, B)."""
    n = cache.k.shape[1]
    P = k_chunk.shape[0]
    posn = jnp.where(valid, start + jnp.arange(P), n)    # OOB → dropped
    rows = jnp.full((P,), row, jnp.int32)
    out = cache._replace(
        k=cache.k.at[rows, posn].set(k_chunk.astype(cache.k.dtype),
                                     mode="drop"),
        v=None if cache.v is None else cache.v.at[rows, posn].set(
            v_chunk.astype(cache.v.dtype), mode="drop"))
    if meta is not None:
        def upd(dst, new):                               # new: (G, P, B)
            return dst.at[rows, :, posn].set(jnp.moveaxis(new, 0, 1),
                                             mode="drop")
        out = out._replace(
            meta_ids=upd(out.meta_ids, meta.centroid_ids),
            meta_codes=upd(out.meta_codes, meta.codes),
            meta_w=upd(out.meta_w, meta.weights))
    return out


def paged_fill_chunk_write(pool: PagedLayerKVCache, bt_row: jax.Array,
                           start: jax.Array, k_chunk: jax.Array,
                           v_chunk: jax.Array, valid: jax.Array,
                           meta=None) -> PagedLayerKVCache:
    """Paged twin of :func:`fill_chunk_write`: one slot's chunk goes
    through its block-table row ``bt_row`` (nblk,) — writes into
    unallocated (< 0) blocks or past the table are dropped."""
    bs = paged_block_size(pool)
    nb = paged_num_blocks(pool)
    nblk = bt_row.shape[0]
    P = k_chunk.shape[0]
    lidx = start + jnp.arange(P)
    blk = lidx // bs
    off = lidx % bs
    pb = bt_row[jnp.clip(blk, 0, nblk - 1)]
    pb = jnp.where(valid & (blk < nblk) & (pb >= 0), pb, nb)  # OOB → drop
    out = pool._replace(
        k=pool.k.at[pb, off].set(k_chunk.astype(pool.k.dtype), mode="drop"),
        v=None if pool.v is None else pool.v.at[pb, off].set(
            v_chunk.astype(pool.v.dtype), mode="drop"))
    if meta is not None:
        def upd(dst, new):                               # new: (G, P, B)
            return dst.at[pb, :, off].set(jnp.moveaxis(new, 0, 1),
                                          mode="drop")
        out = out._replace(
            meta_ids=upd(out.meta_ids, meta.centroid_ids),
            meta_codes=upd(out.meta_codes, meta.codes),
            meta_w=upd(out.meta_w, meta.weights))
    return out


def paged_fill_hist_update(pool: PagedLayerKVCache, hist_row: jax.Array,
                           bt_row: jax.Array, f0: jax.Array, f1: jax.Array,
                           cfg: ParisKVConfig, span: int) -> jax.Array:
    """Advance the filling slot's incremental bucket histogram for the
    retrieval-region growth [enc(f0), enc(f1)) caused by moving the fill
    frontier f0 → f1 (``span`` is a static bound ≥ f1 − f0 ≥ e1 − e0; the
    region boundary grows at most one position per written token).

    Must run *after* the chunk's metadata is written: the newly counted
    positions can live in this very chunk. hist_row: (G, B, 2^m) int32 →
    updated copy. Keeps the fused-path invariant
    ``hist == histogram(ids, [sink, enc_end))`` true at every mixed step
    of a fill, not just at its completion."""
    from repro.core import retrieval as R
    bs = paged_block_size(pool)
    nb = paged_meta_blocks(pool)
    nblk = bt_row.shape[0]
    e0 = fill_enc_end(f0, cfg)
    e1 = fill_enc_end(f1, cfg)
    lidx = e0 + jnp.arange(span)
    blk = lidx // bs
    pb = bt_row[jnp.clip(blk, 0, nblk - 1)]
    phys = jnp.clip(pb, 0, nb - 1) * bs + (lidx % bs)
    G, B = pool.meta_ids.shape[1], pool.meta_ids.shape[-1]
    flat_ids = jnp.moveaxis(pool.meta_ids, 2, 1).reshape(nb * bs, G, B)
    new_ids = jnp.moveaxis(flat_ids[phys], 1, 0)         # (G, span, B)
    inc = ((lidx >= cfg.sink_size) & (lidx < e1) & (blk < nblk)
           & (pb >= 0))                                  # (span,)
    return hist_row + R.bucket_histogram(new_ids, inc[None],
                                         cfg.num_centroids())


def promote_trigger(regions: CacheRegions, cfg: ParisKVConfig) -> jax.Array:
    """Per-row bool: True where the Local+Buffer window is full and a block
    must promote. Shape follows ``regions`` (scalar in → scalar out)."""
    return (regions.pos + 1 - regions.enc_end) >= window_size(cfg)


def maybe_promote(cache: LayerKVCache, regions: CacheRegions,
                  cfg: ParisKVConfig, signs: jax.Array
                  ) -> Tuple[LayerKVCache, CacheRegions]:
    """Sliding-window update (§4.2.1), per row: wherever a row's Local+Buffer
    window is full, encode its oldest ``update_interval`` tokens and advance
    that row's enc_end. The encode is skipped entirely (lax.cond) when no
    row triggers, preserving the amortized cost profile."""
    b = cache.k.shape[0]
    pos = _as_batch(regions.pos, b)
    enc_end = _as_batch(regions.enc_end, b)
    trigger = (pos + 1 - enc_end) >= window_size(cfg)

    cache = jax.lax.cond(
        jnp.any(trigger),
        lambda c: promote_rows(c, enc_end, trigger, cfg, signs),
        lambda c: c, cache)
    new_enc = jnp.where(trigger, enc_end + cfg.update_interval, enc_end)
    return cache, CacheRegions(pos=pos, enc_end=new_enc)


def retrieval_valid_mask(n_max: int, regions: CacheRegions,
                         cfg: ParisKVConfig) -> jax.Array:
    """Bool mask over the Retrieval region [sink, enc_end).

    (n_max,) for scalar ``enc_end`` (legacy), (b, n_max) for (b,) vectors."""
    idx = jnp.arange(n_max)
    enc_end = jnp.asarray(regions.enc_end)
    return (idx >= cfg.sink_size) & (idx < enc_end[..., None])


# ======================================================================
# Paged KV cache: a global block pool + per-slot block tables
# ======================================================================
#
# The contiguous layout above gives every sequence a private ``n_max``
# region — short requests strand memory. The paged layout shares one
# physical pool of fixed-size blocks across all serving slots:
#
#   k, v:        (num_blocks, block_size, G, hd)
#   meta_*:      (num_blocks, G, block_size, B)
#
# A slot's sequence is described by a **block table** ``bt`` of shape
# (b, n_max // block_size) int32: logical token position ``p`` of row
# ``i`` lives at physical ``(bt[i, p // bs], p % bs)``. Entries < 0 mean
# "not allocated" — reads through them are clipped (and masked by the
# pos/enc_end validity masks, which never reach unwritten positions) and
# writes through them are dropped. Allocation itself is host-side policy
# (serving.PagedServingEngine owns the free list); everything here is
# pure device-side addressing.

PAGED_DEFAULT_BLOCK = 128


class PagedLayerKVCache(NamedTuple):
    """Block-pool twin of :class:`LayerKVCache` (no batch dim — the pool
    is shared by every slot; per-slot views go through a block table).

    k, v:        (num_blocks, block_size, G, hd)
    meta_ids:    (num_blocks, G, block_size, B) uint8
    meta_codes:  (num_blocks, G, block_size, B) uint32
    meta_w:      (num_blocks, G, block_size, B) float32

    ``v`` None: a latent pool (MLA), as for :class:`LayerKVCache`.
    """
    k: jax.Array
    v: Optional[jax.Array]
    meta_ids: jax.Array
    meta_codes: jax.Array
    meta_w: jax.Array


def init_paged_cache(num_blocks: int, block_size: int, num_kv_heads: int,
                     head_dim: int, cfg: ParisKVConfig,
                     dtype=jnp.bfloat16,
                     values: bool = True) -> PagedLayerKVCache:
    """``values=False`` builds a latent pool (no ``v`` leaf)."""
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    return PagedLayerKVCache(
        k=jnp.zeros((num_blocks, block_size, g, head_dim), dtype),
        v=(jnp.zeros((num_blocks, block_size, g, head_dim), dtype)
           if values else None),
        meta_ids=jnp.zeros((num_blocks, g, block_size, B), jnp.uint8),
        meta_codes=jnp.zeros((num_blocks, g, block_size, B), jnp.uint32),
        meta_w=jnp.zeros((num_blocks, g, block_size, B), jnp.float32),
    )


def paged_cache_spec(num_blocks: int, block_size: int, num_kv_heads: int,
                     head_dim: int, cfg: ParisKVConfig,
                     dtype=jnp.bfloat16,
                     values: bool = True) -> PagedLayerKVCache:
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    sds = jax.ShapeDtypeStruct
    return PagedLayerKVCache(
        k=sds((num_blocks, block_size, g, head_dim), dtype),
        v=sds((num_blocks, block_size, g, head_dim), dtype) if values else None,
        meta_ids=sds((num_blocks, g, block_size, B), jnp.uint8),
        meta_codes=sds((num_blocks, g, block_size, B), jnp.uint32),
        meta_w=sds((num_blocks, g, block_size, B), jnp.float32),
    )


def paged_block_size(pool: PagedLayerKVCache) -> int:
    return pool.k.shape[-3]


def paged_num_blocks(pool: PagedLayerKVCache) -> int:
    return pool.k.shape[-4]


def paged_meta_blocks(pool: PagedLayerKVCache) -> int:
    """Block count of the *metadata* tier. Equal to ``paged_num_blocks``
    for a uniform pool; larger for a tiered pool whose K/V leaves are a
    bounded staging subset (ISSUE 6) — metadata addressing must always
    derive its block count and OOB sentinels from the meta leaves."""
    return pool.meta_ids.shape[-4]


def paged_lookup_blocks(block_tables: jax.Array, lidx: jax.Array,
                        block_size: int) -> Tuple[jax.Array, jax.Array]:
    """Per-row block-table lookup: logical positions → (phys_block, offset).

    block_tables: (b, nblk) int32 (entries < 0 = unallocated, passed
    through so callers can sentinel/clip); lidx: (b, ...) logical token
    positions, row-aligned with the tables."""
    b = block_tables.shape[0]
    blk = lidx // block_size
    off = lidx % block_size
    flat = blk.reshape(b, -1)
    pb = jnp.take_along_axis(block_tables, flat, axis=1).reshape(blk.shape)
    return pb, off


def paged_physical_rows(block_tables: jax.Array, lidx: jax.Array,
                        num_blocks: int, block_size: int) -> jax.Array:
    """Logical positions → flat physical row ids into the
    (num_blocks·block_size)-row pool. Unallocated entries are clipped to
    block 0 — callers must mask such positions (every reader does: the
    pos/enc_end masks only admit written, hence allocated, positions)."""
    pb, off = paged_lookup_blocks(block_tables, lidx, block_size)
    return jnp.clip(pb, 0, num_blocks - 1) * block_size + off


def paged_decode_append(pool: PagedLayerKVCache, block_tables: jax.Array,
                        k_t: jax.Array, v_t: jax.Array, pos: jax.Array
                        ) -> PagedLayerKVCache:
    """Append one token's K/V at per-row logical position ``pos`` through
    the block table. k_t/v_t: (b, G, hd).

    Mirrors ``decode_append``'s clamp-at-capacity semantics (a frozen row
    at exactly n_max writes into its own last position — dead data) and
    drops writes whose block is unallocated (free slots with cleared
    tables)."""
    b = k_t.shape[0]
    bs = paged_block_size(pool)
    nb = paged_num_blocks(pool)
    n_log = block_tables.shape[1] * bs
    lidx = jnp.minimum(_as_batch(pos, b), n_log - 1)
    pb, off = paged_lookup_blocks(block_tables, lidx, bs)
    pb = jnp.where(pb < 0, nb, pb)          # unallocated → OOB → dropped
    return pool._replace(
        k=pool.k.at[pb, off].set(k_t.astype(pool.k.dtype), mode="drop"),
        v=None if pool.v is None else pool.v.at[pb, off].set(
            v_t.astype(pool.v.dtype), mode="drop"),
    )


def paged_meta_view(pool: PagedLayerKVCache, block_tables: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gather each row's logical metadata view through its block table.

    → (meta_ids, meta_codes, meta_w), each (b, G, n_logical, B). Values at
    unallocated positions are arbitrary pool contents; the retrieval valid
    mask (bounded by enc_end) never admits them."""
    nb = paged_meta_blocks(pool)
    bs = paged_block_size(pool)
    b, nblk = block_tables.shape
    safe = jnp.clip(block_tables, 0, nb - 1)

    def view(a):
        g = a.shape[1]
        out = a[safe]                            # (b, nblk, G, bs, B)
        out = jnp.moveaxis(out, 2, 1)            # (b, G, nblk, bs, B)
        return out.reshape(b, g, nblk * bs, a.shape[-1])

    return view(pool.meta_ids), view(pool.meta_codes), view(pool.meta_w)


def paged_gather_rows(pool_kv: jax.Array, block_tables: jax.Array,
                      lidx: jax.Array) -> jax.Array:
    """Gather K or V rows at per-row logical positions.

    pool_kv: (num_blocks, block_size, G, hd); lidx: (b, L) → (b, L, G, hd).
    The jnp twin of kernels.gather_kv.gather_rows_paged_pallas."""
    nb, bs = pool_kv.shape[:2]
    phys = paged_physical_rows(block_tables, lidx, nb, bs)
    flat = pool_kv.reshape((nb * bs,) + pool_kv.shape[2:])
    return flat[phys]


def gather_heads_physical(pool_kv: jax.Array, phys_rows: jax.Array
                          ) -> jax.Array:
    """Per-(kv-head) gather by flat physical pool row ids.

    pool_kv: (num_blocks, block_size, G, hd); phys_rows: (b, G, Q, k) →
    (b, G, Q, k, hd): index (i, g, q, j) reads head g of pool row
    phys_rows[i, g, q, j]."""
    nb, bs, G, hd = pool_kv.shape
    b, _, Q, k = phys_rows.shape
    flat = jnp.moveaxis(pool_kv.reshape(nb * bs, G, hd), 1, 0)  # (G, N, hd)
    idx = phys_rows.reshape(b, G, Q * k)
    out = jnp.take_along_axis(
        jnp.broadcast_to(flat[None], (b,) + flat.shape), idx[..., None],
        axis=2)
    return out.reshape(b, G, Q, k, hd)


def paged_gather_heads(pool_kv: jax.Array, block_tables: jax.Array,
                       lidx: jax.Array) -> jax.Array:
    """Per-(kv-head) gather of selected rows, the paged twin of
    ``attention.gather_kv_heads``.

    pool_kv: (num_blocks, block_size, G, hd); lidx: (b, G, Q, k) logical →
    (b, G, Q, k, hd): index (i, g, q, j) reads head g of the row at
    logical position lidx[i, g, q, j] of row i's sequence."""
    nb, bs = pool_kv.shape[:2]
    phys = paged_physical_rows(block_tables, lidx, nb, bs)   # (b, G, Q, k)
    return gather_heads_physical(pool_kv, phys)


def paged_promote_rows(pool: PagedLayerKVCache, block_tables: jax.Array,
                       starts: jax.Array, mask: jax.Array,
                       cfg: ParisKVConfig, signs: jax.Array,
                       kv_tables: Optional[jax.Array] = None
                       ) -> PagedLayerKVCache:
    """Per-row block promotion through the block table: for each row ``i``
    with ``mask[i]``, encode metadata for the keys at logical positions
    [starts[i], starts[i]+update_interval) and scatter it back to their
    physical blocks (a promotion span may straddle two blocks).

    Rows with ``mask[i] == False`` (and spans through unallocated table
    entries) are dropped via an out-of-bounds sentinel block id.

    ``kv_tables`` (default: ``block_tables``) addresses the K gather —
    a tiered pool passes its composed staging tables here while the meta
    scatter keeps the host tables (the promoted span sits inside the
    pinned local window, so its blocks are always staging-resident)."""
    U = cfg.update_interval
    b = block_tables.shape[0]
    nb = paged_meta_blocks(pool)
    bs = paged_block_size(pool)
    starts = _as_batch(starts, b)
    lidx = starts[:, None] + jnp.arange(U)[None]             # (b, U)
    kvt = block_tables if kv_tables is None else kv_tables
    rows = paged_gather_rows(pool.k, kvt, lidx)              # (b, U, G, hd)
    meta = _encode_block(rows, cfg, signs)                   # (b, G, U, B)

    pb, off = paged_lookup_blocks(block_tables, lidx, bs)
    tgt = jnp.where(mask[:, None] & (pb >= 0), pb, nb)       # sentinel → drop

    def upd(dst, new):                                       # new: (b, G, U, B)
        return dst.at[tgt, :, off].set(jnp.moveaxis(new, 1, 2), mode="drop")

    return pool._replace(
        meta_ids=upd(pool.meta_ids, meta.centroid_ids),
        meta_codes=upd(pool.meta_codes, meta.codes),
        meta_w=upd(pool.meta_w, meta.weights),
    )


def paged_maybe_promote(pool: PagedLayerKVCache, block_tables: jax.Array,
                        regions: CacheRegions, cfg: ParisKVConfig,
                        signs: jax.Array
                        ) -> Tuple[PagedLayerKVCache, CacheRegions]:
    """Paged twin of ``maybe_promote``: same trigger, same amortized
    any-row lax.cond, writes through the block table."""
    b = block_tables.shape[0]
    pos = _as_batch(regions.pos, b)
    enc_end = _as_batch(regions.enc_end, b)
    trigger = (pos + 1 - enc_end) >= window_size(cfg)

    pool = jax.lax.cond(
        jnp.any(trigger),
        lambda c: paged_promote_rows(c, block_tables, enc_end, trigger,
                                     cfg, signs),
        lambda c: c, pool)
    new_enc = jnp.where(trigger, enc_end + cfg.update_interval, enc_end)
    return pool, CacheRegions(pos=pos, enc_end=new_enc)


# ----------------------------------------------------------------------
# Incremental bucket histograms (fused paged retrieval, ISSUE 4)
# ----------------------------------------------------------------------
#
# Stage-I tier weights need the per-(row, kv-head, subspace) count of
# retrieval-region keys in each of the 2^m centroid buckets. The paged
# meta-view path recomputes that histogram with an O(n) scatter-add per
# query; here it is *cache state* of shape (b, G, B, 2^m) int32
# (b · G · B · 2^m · 4 bytes — e.g. 256 KiB per layer at b=4, G=4, B=16,
# m=8), maintained exactly:
#
#   * admission   — one histogram over the freshly prefilled metadata
#                   (bucket_hist_from_meta), amortized per request;
#   * decode      — appends write K/V only (metadata is encoded lazily at
#                   promotion), so the histogram is untouched: O(1);
#   * promotion   — the U re-encoded keys' buckets are incremented
#                   (paged_promote_rows_hist): O(U) every U steps, the
#                   drift-robustness bookkeeping. The overwritten stale
#                   entries sat at ≥ enc_end — outside the counted region
#                   — so no decrement arises (invariant-tested);
#   * eviction    — the slot's histogram row is zeroed by the engine.
#
# The invariant (tests/test_paged_fused.py):
#   hist[i] == bucket_histogram(logical_ids[i], valid[i])  at every step.


def bucket_hist_from_meta(meta_ids: jax.Array, regions: CacheRegions,
                          cfg: ParisKVConfig) -> jax.Array:
    """Histogram a contiguous metadata store over [sink, enc_end).

    meta_ids: (..., b, G, n, B) (extra leading dims — e.g. a stacked stage
    repeat — broadcast); regions aligned with the ``b`` axis.
    → (..., b, G, B, 2^m) int32.
    """
    from repro.core import retrieval as R
    n = meta_ids.shape[-2]
    valid = retrieval_valid_mask(n, regions, cfg)
    if valid.ndim == 1:
        valid = valid[None]
    return R.bucket_histogram(meta_ids, valid[:, None, :],
                              cfg.num_centroids())


def bucket_hist_from_paged_meta(pool: PagedLayerKVCache, bt_row: jax.Array,
                                enc_end: jax.Array, cfg: ParisKVConfig
                                ) -> jax.Array:
    """Rebuild one slot's bucket histogram from *pool* metadata over
    [sink, enc_end), addressed through its block-table row.

    The shared-prefix admission path (ISSUE 7) needs this: a slot that
    maps already-cached blocks into its table never runs a fill pass over
    them, so its incremental histogram cannot be built up chunk by chunk
    — it is derived here from the shared blocks' metadata (written by the
    donor's prefill/fill, final thereafter) in one amortized pass, the
    paged twin of :func:`bucket_hist_from_meta`. Works on resident and
    tiered pools alike (both keep metadata full-size on device), and on
    stacked (leading stage-repeat axis) or per-layer leaves.

    bt_row: (nblk,) int32, entries < 0 = unallocated (excluded);
    enc_end: traced scalar — the region boundary ``fill_enc_end(f)`` of
    the shared frontier. → (..., G, B, 2^m) int32, dtype-ready for the
    ``hist`` cache entry."""
    from repro.core import retrieval as R
    bs = paged_block_size(pool)
    nm = paged_meta_blocks(pool)
    nblk = bt_row.shape[0]
    lidx = jnp.arange(nblk * bs)
    pb = bt_row[lidx // bs]
    phys = jnp.clip(pb, 0, nm - 1) * bs + lidx % bs
    lead = pool.meta_ids.shape[:-4]
    G, B = pool.meta_ids.shape[-3], pool.meta_ids.shape[-1]
    flat = jnp.moveaxis(pool.meta_ids, -2, -3).reshape(
        lead + (nm * bs, G, B))
    ids = jnp.moveaxis(flat[..., phys, :, :], -2, -3)   # (..., G, n, B)
    valid = (lidx >= cfg.sink_size) & (lidx < enc_end) & (pb >= 0)
    return R.bucket_histogram(ids, valid, cfg.num_centroids())


def paged_promote_rows_hist(pool: PagedLayerKVCache, hist: jax.Array,
                            block_tables: jax.Array, starts: jax.Array,
                            mask: jax.Array, cfg: ParisKVConfig,
                            signs: jax.Array,
                            kv_tables: Optional[jax.Array] = None
                            ) -> Tuple[PagedLayerKVCache, jax.Array]:
    """``paged_promote_rows`` + exact O(U) histogram maintenance.

    For each promoting row the U newly encoded keys' buckets are
    incremented — only positions ≥ sink (short prompts can promote spans
    that start below the sink, which never become valid) under allocated
    blocks (unallocated writes are dropped by the promote itself). No
    decrement is needed: the span [starts, starts+U) starts at the
    pre-promotion enc_end, so the stale ids it overwrites were never
    inside the counted region [sink, enc_end) — the invariant test
    (hist == recomputed histogram after every step) pins this down, and
    any future overlapping re-encode would trip it immediately.
    """
    from repro.core import retrieval as R
    U = cfg.update_interval
    b = block_tables.shape[0]
    nb = paged_meta_blocks(pool)
    bs = paged_block_size(pool)
    nc = cfg.num_centroids()
    starts = _as_batch(starts, b)
    lidx = starts[:, None] + jnp.arange(U)[None]             # (b, U)
    pb, off = paged_lookup_blocks(block_tables, lidx, bs)
    phys = jnp.clip(pb, 0, nb - 1) * bs + off

    new_pool = paged_promote_rows(pool, block_tables, starts, mask, cfg,
                                  signs, kv_tables=kv_tables)
    flat_ids = jnp.moveaxis(new_pool.meta_ids, 2, 1).reshape(
        nb * bs, pool.meta_ids.shape[1], pool.meta_ids.shape[-1])
    new_ids = jnp.moveaxis(flat_ids[phys], 2, 1)             # (b, G, U, B)

    inc = mask[:, None] & (lidx >= cfg.sink_size) & (pb >= 0)  # (b, U)
    return new_pool, hist + R.bucket_histogram(new_ids, inc[:, None], nc)


def paged_maybe_promote_hist(pool: PagedLayerKVCache, hist: jax.Array,
                             block_tables: jax.Array, regions: CacheRegions,
                             cfg: ParisKVConfig, signs: jax.Array,
                             kv_tables: Optional[jax.Array] = None
                             ) -> Tuple[PagedLayerKVCache, jax.Array,
                                        CacheRegions]:
    """``paged_maybe_promote`` twin that also maintains the histogram."""
    b = block_tables.shape[0]
    pos = _as_batch(regions.pos, b)
    enc_end = _as_batch(regions.enc_end, b)
    trigger = (pos + 1 - enc_end) >= window_size(cfg)

    pool, hist = jax.lax.cond(
        jnp.any(trigger),
        lambda ph: paged_promote_rows_hist(ph[0], ph[1], block_tables,
                                           enc_end, trigger, cfg, signs,
                                           kv_tables=kv_tables),
        lambda ph: ph, (pool, hist))
    new_enc = jnp.where(trigger, enc_end + cfg.update_interval, enc_end)
    return pool, hist, CacheRegions(pos=pos, enc_end=new_enc)


def paged_scatter_prefill(pool: PagedLayerKVCache, cache1: LayerKVCache,
                          phys_blocks: jax.Array) -> PagedLayerKVCache:
    """Install a solo (batch=1) contiguous prefill result into the pool.

    cache1 leaves are stacked over the stage repeat with batch axis 1
    (k: (R, 1, n_logical, G, hd)); ``phys_blocks`` (n_logical // bs,) maps
    each logical block to its physical block, with out-of-range sentinels
    (>= num_blocks) for blocks the allocator did not hand out (their
    contents are prompt-pad garbage that no mask ever admits)."""
    bs = paged_block_size(pool)
    nblk = phys_blocks.shape[0]

    def kv(dst, src):                       # src (R, 1, n, G, hd)
        r, _, n, g, hd = src.shape
        view = src.reshape(r, nblk, bs, g, hd)
        return dst.at[:, phys_blocks].set(view.astype(dst.dtype),
                                          mode="drop")

    def meta(dst, src):                     # src (R, 1, G, n, B)
        r, _, g, n, B = src.shape
        view = jnp.moveaxis(src.reshape(r, g, nblk, bs, B), 1, 2)
        return dst.at[:, phys_blocks].set(view.astype(dst.dtype),
                                          mode="drop")

    return PagedLayerKVCache(
        k=kv(pool.k, cache1.k),
        v=None if pool.v is None else kv(pool.v, cache1.v),
        meta_ids=meta(pool.meta_ids, cache1.meta_ids),
        meta_codes=meta(pool.meta_codes, cache1.meta_codes),
        meta_w=meta(pool.meta_w, cache1.meta_w),
    )


def paged_clear_blocks(pool: PagedLayerKVCache,
                       phys_blocks: jax.Array) -> PagedLayerKVCache:
    """Zero the given physical blocks (eviction hygiene; correctness never
    depends on it — masks stop stale reads — but it keeps reclaimed blocks
    from leaking a tenant's K/V into debug dumps).

    With prefix sharing (ISSUE 7) a block may be referenced by several
    slots' tables: callers must pass only blocks whose refcount reached 0
    (the engine's ``_decref_blocks``), padding the rest of the row with
    out-of-range sentinels — zeroing a still-shared block would corrupt
    every surviving holder's prefix."""
    def z(a):
        return a.at[:, phys_blocks].set(0, mode="drop")
    return PagedLayerKVCache(k=z(pool.k),
                             v=None if pool.v is None else z(pool.v),
                             meta_ids=z(pool.meta_ids),
                             meta_codes=z(pool.meta_codes),
                             meta_w=z(pool.meta_w))


# ======================================================================
# Tiered pool: device metadata + bounded KV staging, host KV (ISSUE 6)
# ======================================================================
#
# The paged pool above must fit entirely in HBM. The tiered layout keeps
# the *retrieval metadata* (ids + codes + weights — the only thing Stage
# I/II ever touch, and tiny) fully device-resident, but shrinks the K/V
# leaves to a bounded **staging pool** of ``num_device_blocks`` hot
# blocks; the full K/V block pool lives in host memory
# (serving.offload.HostKVPool). The same ``PagedLayerKVCache`` tuple is
# reused — a tiered pool is simply one whose K/V leaves have fewer
# blocks than its meta leaves (``paged_meta_blocks`` > ``paged_num_blocks``).
#
# Addressing splits in two:
#   * metadata reads/writes go through the per-slot **host block tables**
#     (bt), exactly as before — Stage I/II are unchanged;
#   * K/V reads/writes go through **composed tables**
#     ``tiered_kv_tables(bt, dev_map)``: logical block → host block →
#     staging block, where ``dev_map`` (num_blocks,) int32 is the
#     device-residency map (-1 = not staged). Non-resident winners are
#     fetched from the host pool on demand (layers.attn_decode_pariskv_
#     tiered); everything a step *writes* (sink + local window + fill
#     frontier) is pinned resident by the engine, so appends, promotion
#     gathers, and window/sink attention reads always hit staging.


def init_tiered_cache(num_blocks: int, num_device_blocks: int,
                      block_size: int, num_kv_heads: int, head_dim: int,
                      cfg: ParisKVConfig, dtype=jnp.bfloat16
                      ) -> PagedLayerKVCache:
    """Tiered pool: meta leaves sized ``num_blocks``, K/V staging leaves
    sized ``num_device_blocks``."""
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    return PagedLayerKVCache(
        k=jnp.zeros((num_device_blocks, block_size, g, head_dim), dtype),
        v=jnp.zeros((num_device_blocks, block_size, g, head_dim), dtype),
        meta_ids=jnp.zeros((num_blocks, g, block_size, B), jnp.uint8),
        meta_codes=jnp.zeros((num_blocks, g, block_size, B), jnp.uint32),
        meta_w=jnp.zeros((num_blocks, g, block_size, B), jnp.float32),
    )


def tiered_cache_spec(num_blocks: int, num_device_blocks: int,
                      block_size: int, num_kv_heads: int, head_dim: int,
                      cfg: ParisKVConfig, dtype=jnp.bfloat16
                      ) -> PagedLayerKVCache:
    B = cfg.num_subspaces(head_dim)
    g = num_kv_heads
    sds = jax.ShapeDtypeStruct
    return PagedLayerKVCache(
        k=sds((num_device_blocks, block_size, g, head_dim), dtype),
        v=sds((num_device_blocks, block_size, g, head_dim), dtype),
        meta_ids=sds((num_blocks, g, block_size, B), jnp.uint8),
        meta_codes=sds((num_blocks, g, block_size, B), jnp.uint32),
        meta_w=sds((num_blocks, g, block_size, B), jnp.float32),
    )


def tiered_kv_tables(block_tables: jax.Array, dev_map: jax.Array
                     ) -> jax.Array:
    """Compose per-slot host block tables with the residency map.

    block_tables (b, nblk) logical → host block (< 0 unallocated);
    dev_map (num_blocks,) host block → staging block (-1 not staged).
    → (b, nblk) logical → staging block, where both "unallocated" and
    "allocated but not staged" come out < 0 (so existing clip/sentinel
    handling drops writes and masks reads exactly as for unallocated
    entries)."""
    nb = dev_map.shape[0]
    mapped = dev_map[jnp.clip(block_tables, 0, nb - 1)]
    return jnp.where(block_tables >= 0, mapped, -1)


def tiered_scatter_prefill_meta(pool: PagedLayerKVCache,
                                cache1: LayerKVCache,
                                phys_blocks: jax.Array) -> PagedLayerKVCache:
    """Meta-only half of :func:`paged_scatter_prefill` for solo admission
    into a tiered pool: the prompt's K/V goes to the host tier (engine-
    side numpy write) and into staging via the residency installer — only
    the metadata lands here."""
    bs = paged_block_size(pool)
    nblk = phys_blocks.shape[0]

    def meta(dst, src):                     # src (R, 1, G, n, B)
        r, _, g, n, B = src.shape
        view = jnp.moveaxis(src.reshape(r, g, nblk, bs, B), 1, 2)
        return dst.at[:, phys_blocks].set(view.astype(dst.dtype),
                                          mode="drop")

    return pool._replace(
        meta_ids=meta(pool.meta_ids, cache1.meta_ids),
        meta_codes=meta(pool.meta_codes, cache1.meta_codes),
        meta_w=meta(pool.meta_w, cache1.meta_w),
    )


def tiered_fill_chunk_write(pool: PagedLayerKVCache, bt_row: jax.Array,
                            dev_row: jax.Array, start: jax.Array,
                            k_chunk: jax.Array, v_chunk: jax.Array,
                            valid: jax.Array, meta=None
                            ) -> PagedLayerKVCache:
    """Tiered twin of :func:`paged_fill_chunk_write`: K/V goes through the
    composed staging row ``dev_row`` (the fill frontier is pinned
    resident), metadata through the host row ``bt_row``. Each side drops
    through its own OOB sentinel sized to its own tier."""
    bs = paged_block_size(pool)
    nd = paged_num_blocks(pool)
    nm = paged_meta_blocks(pool)
    nblk = bt_row.shape[0]
    P = k_chunk.shape[0]
    lidx = start + jnp.arange(P)
    blk = lidx // bs
    off = lidx % bs
    inb = valid & (blk < nblk)
    safe = jnp.clip(blk, 0, nblk - 1)
    pb_kv = dev_row[safe]
    pb_kv = jnp.where(inb & (pb_kv >= 0), pb_kv, nd)         # OOB → drop
    out = pool._replace(
        k=pool.k.at[pb_kv, off].set(k_chunk.astype(pool.k.dtype),
                                    mode="drop"),
        v=pool.v.at[pb_kv, off].set(v_chunk.astype(pool.v.dtype),
                                    mode="drop"))
    if meta is not None:
        pb_m = bt_row[safe]
        pb_m = jnp.where(inb & (pb_m >= 0), pb_m, nm)

        def upd(dst, new):                                   # new: (G, P, B)
            return dst.at[pb_m, :, off].set(jnp.moveaxis(new, 0, 1),
                                            mode="drop")
        out = out._replace(
            meta_ids=upd(out.meta_ids, meta.centroid_ids),
            meta_codes=upd(out.meta_codes, meta.codes),
            meta_w=upd(out.meta_w, meta.weights))
    return out


def tiered_stage_blocks(pool: PagedLayerKVCache, stag_blocks: jax.Array,
                        k_payload: jax.Array, v_payload: jax.Array
                        ) -> PagedLayerKVCache:
    """Install host-fetched K/V block payloads into staging slots.

    stag_blocks (n,) staging block ids (out-of-range = pad slot, write
    dropped); k/v_payload (R, n, block_size, G, hd) — the leading stage-
    repeat axis matches the stacked pool leaves."""
    return pool._replace(
        k=pool.k.at[:, stag_blocks].set(k_payload.astype(pool.k.dtype),
                                        mode="drop"),
        v=pool.v.at[:, stag_blocks].set(v_payload.astype(pool.v.dtype),
                                        mode="drop"))


def tiered_clear_blocks(pool: PagedLayerKVCache, meta_blocks: jax.Array,
                        stag_blocks: jax.Array) -> PagedLayerKVCache:
    """Eviction hygiene for a tiered pool: zero the slot's *host* blocks
    on the meta leaves and its *staging* blocks on the K/V leaves (the
    two id spaces differ, unlike :func:`paged_clear_blocks`). The same
    refcount contract applies under prefix sharing: both id lists must
    contain only blocks no surviving slot still maps."""
    def z(a, ids):
        return a.at[:, ids].set(0, mode="drop")
    return pool._replace(
        k=z(pool.k, stag_blocks), v=z(pool.v, stag_blocks),
        meta_ids=z(pool.meta_ids, meta_blocks),
        meta_codes=z(pool.meta_codes, meta_blocks),
        meta_w=z(pool.meta_w, meta_blocks))
