"""Two-stage GPU-native retrieval pipeline (paper §4.2.2, App. B.2).

Stage I  — coarse candidate generation by multi-tier subspace collisions.
Stage II — RSQ-IP reranking of the candidates from 4-bit codes.

This module is the *reference* (pure-jnp) implementation and the one the
**sharded serving path** runs shard-locally: every op here is independent
per kv-head, so a call over a head-slice of the pool/metadata returns
exactly that head-slice of the single-device result, bit for bit.
``models.serve`` exploits that under ``jax.shard_map`` (a 1-D mesh whose
axis partitions KV heads): each shard runs Stage I over its device-resident
metadata slice and Stage II over its own candidates, and
``retrieve_paged_fused_sharded`` reassembles the global result with one
tiled per-head ``all_gather`` — a pure concatenation, no float reductions,
so the merge is provably equivalent to single-device top-C
(tests/test_sharded_serving.py). ``repro.kernels`` provides Pallas TPU
kernels for the collision scan, bucket-top-k and fused rerank with
identical semantics, validated against these functions.

A crucial implementation point (matches the paper's "bucket-level" design):
the tier weight is a property of the *centroid bucket*, not of the key — all
keys assigned to the same centroid share its proxy score ⟨q_b, c⟩, so we (1)
histogram keys over the 2^m buckets, (2) count for each bucket the keys in
the buckets that outrank it by proxy score — one compare-and-reduce over
the ≤256 × 256 bucket pairs, already in bucket-id order, (3) convert that
position into a tier weight, and (4) look the weight up per key. Cost:
O(2^m · 2^m + n) per (query head, subspace) instead of O(n log n).

On the fused paged path the per-step histogram in (1) is never recomputed
— it lives as per-(slot, G, B) cache state with exactly four writers
(``core.cache`` owns them all): built once at admission
(``bucket_hist_from_meta`` from a solo prefill's metadata, or
``bucket_hist_from_paged_meta`` through the block table when a
shared-prefix admission maps already-cached blocks that never see a fill
pass), incremented O(U) at each sliding-window promotion
(``paged_promote_rows_hist``), advanced per chunked-fill step for the
region growth (``paged_fill_hist_update``), and zeroed at eviction. The
invariant ``hist == bucket_histogram(ids, [sink, enc_end))`` holds at
every step (tests/test_paged_fused.py, test_chunked_prefill.py,
test_prefix_sharing.py).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import centroids, phases
from repro.core.config import ParisKVConfig
from repro.core.encode import KeyMetadata, QueryTransform

# a numpy scalar: a jnp one would initialise JAX's backends (and take the
# chip) in every process that merely imports this module
NEG_INF = np.float32(-1e30)


class RetrievalResult(NamedTuple):
    indices: jax.Array   # (..., k) int32 — positions of the final Top-k keys
    scores: jax.Array    # (..., k) float32 — RSQ-IP estimates for them
    cand_indices: jax.Array  # (..., C) int32 — Stage-I candidate positions
    coarse_scores: jax.Array  # (..., n) int32 — Stage-I collision scores


class PagedRetrievalResult(NamedTuple):
    """Retrieval result addressed block-relatively for a paged KV pool.

    ``indices`` stay *logical* (what the attention masks need);
    ``block_ids``/``offsets`` are the (physical block, in-block offset)
    decomposition of each hit, and ``phys_rows`` the flattened physical
    row ids into the (num_blocks·block_size)-row pool — exactly what the
    block-table gather (kernels/gather_kv paged path) consumes."""
    indices: jax.Array      # (b, ..., k) int32 logical positions
    block_ids: jax.Array    # (b, ..., k) int32 physical block per hit
    offsets: jax.Array      # (b, ..., k) int32 offset within the block
    phys_rows: jax.Array    # (b, ..., k) int32 flat pool row ids
    scores: jax.Array
    cand_indices: jax.Array
    coarse_scores: jax.Array


def bucket_histogram(ids: jax.Array, valid: jax.Array, num_buckets: int) -> jax.Array:
    """Count keys per centroid bucket. ids (..., n, B) → (..., B, 2^m) int32."""
    lead = ids.shape[:-2]
    n, B = ids.shape[-2], ids.shape[-1]
    ids_t = jnp.swapaxes(ids, -1, -2).reshape((-1, n)).astype(jnp.int32)
    upd = jnp.broadcast_to(valid[..., None, :], lead + (B, n)).reshape((-1, n))

    def _hist(one_ids, one_upd):
        return jnp.zeros((num_buckets,), jnp.int32).at[one_ids].add(
            one_upd.astype(jnp.int32))

    counts = jax.vmap(_hist)(ids_t, upd)
    return counts.reshape(lead + (B, num_buckets))


def tier_weight_table(cent_scores: jax.Array, counts: jax.Array,
                      n_valid: jax.Array, cfg: ParisKVConfig) -> jax.Array:
    """Per-(subspace, centroid) integer tier weight (App. B.2.1).

    cent_scores: (..., B, 2^m) proxy scores ⟨q_b, ω_c⟩
    counts:      (..., B, 2^m) bucket histogram (may broadcast against extra
                 query-head dims in cent_scores)
    n_valid:     (...,) number of indexable keys
    → (..., B, 2^m) int32 weights in {0, 1, .., 6}.

    Buckets rank by descending score, ties to the lower bucket id (the
    order of a stable ``argsort(-cent_scores)``; -0.0 and 0.0 tie). A
    bucket's position is the number of keys in the buckets ranked above
    it, counted in bucket-id order by one compare-and-reduce over every
    pair of a row's buckets: no sort, gather or scatter, which a TPU runs
    an element at a time. The cost is O(rows · 2^m · 2^m), fine for the
    2^m ≤ 256 buckets ``centroids.codebook`` keeps.
    """
    nb = cent_scores.shape[-1]
    # pairs [c', c] with c' on the reduced (sublane) axis, c on the lanes
    s_other = cent_scores[..., :, None]
    s_self = cent_scores[..., None, :]
    lower_id = np.arange(nb)[:, None] < np.arange(nb)[None, :]
    above = (s_other > s_self) | ((s_other == s_self) & lower_id)
    keys_above = jnp.sum(jnp.where(above, counts[..., :, None], 0), axis=-2)

    # position of the bucket's *first* key as a fraction of the top-ρ budget
    denom = jnp.maximum(cfg.rho * n_valid.astype(jnp.float32), 1.0)
    pos_frac = keys_above.astype(jnp.float32) / denom[..., None, None]

    # weight of tier #{tier_pcts ≤ pos_frac} (tier_pcts ascending), tier L.. → 0
    wts = cfg.tier_weights + (0,)
    w = jnp.full(pos_frac.shape, wts[0], jnp.int32)
    for t, p in enumerate(np.asarray(cfg.tier_pcts, np.float32), 1):
        w = jnp.where(pos_frac >= p, wts[min(t, len(cfg.tier_weights))], w)
    return w


def collision_scores(meta_ids: jax.Array, q_sub: jax.Array, valid: jax.Array,
                     cfg: ParisKVConfig, hist_sample: int = 0) -> jax.Array:
    """Stage-I coarse scores S_i (Eq. 15). Pure-jnp reference.

    meta_ids: (..., n, B) uint8 centroid assignments
    q_sub:    (..., B, m) rotated query subspaces (may carry extra leading
              query-head dims that broadcast against meta_ids)
    valid:    (..., n) bool
    hist_sample: if >0, estimate the bucket histogram from a strided key
        subsample of ~this size (beyond-paper §Perf optimization: the tier
        *percentile boundaries* only need approximate counts; sampling cuts
        the scatter-add cost by n/hist_sample with bounded boundary noise).
    → (..., n) int32 collision scores (invalid keys get -1).
    """
    nb = cfg.num_centroids()
    n = meta_ids.shape[-2]
    with jax.named_scope(phases.TIER_TABLE):
        cs = centroids.centroid_scores(q_sub, cfg.m)               # (..., B, 2^m)
        stride = max(n // hist_sample, 1) if hist_sample else 1
        if stride > 1:
            counts = bucket_histogram(meta_ids[..., ::stride, :],
                                      valid[..., ::stride], nb) * stride
        else:
            counts = bucket_histogram(meta_ids, valid, nb)         # (..., B, 2^m)
        n_valid = jnp.sum(valid, axis=-1)
        table = tier_weight_table(cs, counts, n_valid, cfg)        # (..., B, 2^m)

    # per-key lookup S_i = Σ_b table[b, id_{i,b}] as ONE flat gather over
    # (B·2^m,) — avoids a (B, n) transpose copy + B separate gathers.
    with jax.named_scope(phases.STAGE1):
        table_flat = table.reshape(table.shape[:-2] + (-1,))       # (..., B·2^m)
        offsets = (jnp.arange(meta_ids.shape[-1], dtype=jnp.int32) * nb)
        idx_flat = (meta_ids.astype(jnp.int32) + offsets).reshape(
            meta_ids.shape[:-2] + (-1,))                           # (..., n·B)
        idx_flat = jnp.broadcast_to(
            idx_flat, table_flat.shape[:-1] + idx_flat.shape[-1:])
        per_key = jnp.take_along_axis(table_flat, idx_flat, axis=-1)
        scores = per_key.reshape(per_key.shape[:-1] + (n, meta_ids.shape[-1])
                                 ).sum(-1)
        return jnp.where(valid, scores, -1)


def select_candidates(scores: jax.Array, num_candidates: int) -> jax.Array:
    """Top-C by integer collision score, deterministic index-order ties.

    Reference semantics; the production path (`select_candidates_bucket`)
    and the Pallas bucket_topk kernel implement the paper's threshold
    selection with identical index sets.
    """
    _, idx = jax.lax.top_k(scores, num_candidates)
    return idx.astype(jnp.int32)


LANES = 128   # keys per block of the candidate compaction: the lane width


def select_candidates_bucket(scores: jax.Array, num_candidates: int,
                             score_range: int) -> jax.Array:
    """O(n) bucket_topk (paper §4.3 kernel i / App. B.2.1) in pure jnp,
    with no scatter and no sort.

    scores: (..., n) int32 in [-1, score_range] (-1 = invalid), n ≥ C.
    → (..., C) int32: lax.top_k's index set in ascending index order —
    every key above the threshold score, then the ties at the threshold,
    lowest index first, up to the quota.

    1. Threshold: ``count_ge[t] = Σ_i [s_i ≥ t]`` for every score t, one
       compare-and-reduce (a TPU serialises scatter-add updates, and a
       histogram's updates all collide in ≤ 98 bins). The
       threshold is the largest t with ``count_ge[t] ≥ C``; the keys
       above it, ``count_ge[t + 1]``, are the largest count below C.
    2. Compaction, inverting the ``take`` mask's prefix sums by search
       over 128-key blocks: slot j lies in the block whose exclusive and
       inclusive take counts bracket it (a compare-and-reduce over the
       n/128 blocks); that block's 128 in-block prefix sums are fetched
       by a one-hot (C, n/128) @ (n/128, 128) matmul (exact in bf16:
       one term, values ≤ 128), and the slot's lane is the number of
       them ≤ its rank within the block. Work O(rows · C · n/128).

    n is padded to a multiple of 128 with keys below every threshold,
    never counted nor taken. Supports arbitrary leading batch dims.
    """
    k = num_candidates
    lead, n = scores.shape[:-1], scores.shape[-1]
    nblk = -(-n // LANES)
    s = jnp.pad(scores.reshape((-1, n)).astype(jnp.int32),
                ((0, 0), (0, nblk * LANES - n)), constant_values=-2)
    rows = s.shape[0]

    t = jnp.arange(-1, score_range + 1, dtype=jnp.int32)
    count_ge = jnp.sum(s[:, None, :] >= t[:, None], axis=-1,
                       dtype=jnp.int32)              # (rows, score_range + 2)
    thresh = jnp.sum(count_ge >= k, axis=-1, dtype=jnp.int32)[:, None] - 2
    above = jnp.max(jnp.where(count_ge < k, count_ge, 0), axis=-1)
    quota = (k - above)[:, None]
    is_tie = s == thresh
    tie_rank = jnp.cumsum(is_tie.astype(jnp.int32), axis=-1) - 1
    take = (s > thresh) | (is_tie & (tie_rank < quota))

    in_incl = jnp.cumsum(take.reshape(rows, nblk, LANES).astype(jnp.int32),
                         axis=-1)                                # (rows, nblk, 128)
    blk_cnt = in_incl[..., -1]
    blk_incl = jnp.cumsum(blk_cnt, axis=-1)                      # (rows, nblk)
    j = jnp.arange(k, dtype=jnp.int32)
    before = blk_incl[:, None, :] <= j[:, None]                  # (rows, C, nblk)
    blk = jnp.sum(before, axis=-1, dtype=jnp.int32)
    rank = j - jnp.sum(jnp.where(before, blk_cnt[:, None, :], 0), axis=-1,
                       dtype=jnp.int32)
    onehot = (blk[..., None] == jnp.arange(nblk, dtype=jnp.int32))
    row = jnp.einsum("rcb,rbl->rcl", onehot.astype(jnp.bfloat16),
                     in_incl.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jnp.sum(row <= rank[..., None], axis=-1, dtype=jnp.int32)
    return (blk * LANES + lane).reshape(lead + (k,))


def rerank(meta: KeyMetadata, qt: QueryTransform, cand_idx: jax.Array,
           valid: jax.Array, cfg: ParisKVConfig) -> jax.Array:
    """Stage-II RSQ-IP estimates for the candidate set (Eq. 24).

    → (..., C) float32, invalid candidates masked to -inf.
    """
    from repro.core import quantizer

    B = meta.codes.shape[-1]
    codes_b = jnp.broadcast_to(
        meta.codes, cand_idx.shape[:-1] + meta.codes.shape[-2:])
    w_b = jnp.broadcast_to(
        meta.weights, cand_idx.shape[:-1] + meta.weights.shape[-2:])
    codes = jnp.take_along_axis(codes_b, cand_idx[..., None], axis=-2)   # (..., C, B)
    w = jnp.take_along_axis(w_b, cand_idx[..., None], axis=-2)           # (..., C, B)
    v = quantizer.decode_directions(codes, cfg.m, cfg.magnitude_bits)    # (..., C, B, m)
    dots = jnp.einsum("...cbm,...bm->...cb", v, qt.q_sub,
                      precision=jax.lax.Precision.HIGHEST)
    est = qt.q_norm[..., None] * jnp.sum(w * dots, axis=-1)

    valid_b = jnp.broadcast_to(valid, cand_idx.shape[:-1] + valid.shape[-1:])
    cand_valid = jnp.take_along_axis(valid_b, cand_idx, axis=-1)
    return jnp.where(cand_valid, est, NEG_INF)


def retrieve(meta: KeyMetadata, qt: QueryTransform, valid: jax.Array,
             cfg: ParisKVConfig, num_candidates: int, top_k: int,
             hist_sample: int = 0, bucket_select: bool = True
             ) -> RetrievalResult:
    """Full two-stage pipeline (Algorithm 1). Shapes broadcast as above.

    bucket_select: use the O(n) threshold Top-β (paper's bucket_topk,
    ``select_candidates_bucket``) instead of a sort-based top-k —
    identical index sets.
    """
    coarse = collision_scores(meta.centroid_ids, qt.q_sub, valid, cfg,
                              hist_sample=hist_sample)
    B = meta.centroid_ids.shape[-1]
    with jax.named_scope(phases.SELECT):
        if bucket_select:
            cand = select_candidates_bucket(
                coarse, num_candidates, score_range=max(cfg.tier_weights) * B)
        else:
            cand = select_candidates(coarse, num_candidates)
    with jax.named_scope(phases.STAGE2):
        est = rerank(meta, qt, cand, valid, cfg)
        top_est, top_pos = jax.lax.top_k(est, top_k)
        top_idx = jnp.take_along_axis(cand, top_pos, axis=-1)
    return RetrievalResult(top_idx, top_est, cand, coarse)


def split_block_relative(idx: jax.Array, block_size: int
                         ) -> Tuple[jax.Array, jax.Array]:
    """Logical positions → (logical_block, in-block offset)."""
    return idx // block_size, idx % block_size


def retrieve_paged(meta: KeyMetadata, qt: QueryTransform, valid: jax.Array,
                   cfg: ParisKVConfig, num_candidates: int, top_k: int,
                   block_tables: jax.Array, block_size: int,
                   hist_sample: int = 0, bucket_select: bool = True
                   ) -> PagedRetrievalResult:
    """Two-stage retrieval over a paged store's *logical* metadata view,
    with the winners translated to block-relative physical addresses.

    ``meta`` is the per-row logical view (cache.paged_meta_view output,
    broadcast over query heads exactly like the contiguous path), so
    Stage-I/II semantics — and the selected index sets — are identical to
    ``retrieve``; only the addressing of the result changes. The leading
    axis of every metadata/valid array is the batch row that
    ``block_tables`` (b, nblk) is aligned with.
    """
    res = retrieve(meta, qt, valid, cfg, num_candidates, top_k,
                   hist_sample=hist_sample, bucket_select=bucket_select)
    # unallocated entries (< 0) are clipped to block 0 (_block_relative) —
    # such hits only arise at masked (invalid) positions, which attention
    # re-masks by enc_end; allocated entries are in-bounds by construction
    with jax.named_scope(phases.STAGE2):
        safe_blk, off, phys_rows = _block_relative(res.indices, block_tables,
                                                   block_size)
    return PagedRetrievalResult(
        indices=res.indices, block_ids=safe_blk, offsets=off,
        phys_rows=phys_rows, scores=res.scores,
        cand_indices=res.cand_indices, coarse_scores=res.coarse_scores)


# ======================================================================
# Fused paged retrieval: Stage-I/II directly over the block pool
# ======================================================================
#
# ``retrieve_paged`` above consumes the *materialized* logical metadata
# view (cache.paged_meta_view): ids + codes + weights — 9·B bytes per key
# — gathered through the block table on every decode step before any
# scoring happens. The fused pipeline below eliminates that copy:
#
#   Stage I   reads only the uint8 centroid ids (1·B bytes/key in the
#             pure-jnp twin; the Pallas kernel
#             kernels.collision.collision_paged_pallas streams physical id
#             tiles through VMEM without materializing anything), and
#             takes the bucket histogram from **incrementally maintained
#             cache state** (cache.bucket_hist_* — O(U) bookkeeping at
#             promotion instead of an O(n) scatter-add per query);
#   Stage II  gathers codes/weights for the ≤C Stage-I survivors only, by
#             physical pool row (8·B bytes per *candidate*).
#
# Per step that is n·B + 2·C·4·B gathered metadata bytes instead of
# n·9·B — ≥4× less for n ≥ 16k — and the index sets/scores are
# *identical* to ``retrieve_paged`` (tests/test_paged_fused.py).

def gather_meta_heads_physical(pool_meta: jax.Array, phys_rows: jax.Array
                               ) -> jax.Array:
    """Per-(kv-head) metadata gather by flat physical pool row ids.

    pool_meta: (num_blocks, G, bs, B); phys_rows: (b, G, Q, C) →
    (b, G, Q, C, B): index (i, g, q, c) reads head g of pool row
    phys_rows[i, g, q, c]. Delegates to cache.gather_heads_physical —
    metadata pools just keep G before the block offset, so one moveaxis
    (free under jit) puts them in K/V pool layout."""
    from repro.core.cache import gather_heads_physical
    return gather_heads_physical(jnp.moveaxis(pool_meta, 1, 2), phys_rows)


def collision_scores_paged(pool_ids: jax.Array, block_tables: jax.Array,
                           q_sub: jax.Array, counts: jax.Array,
                           enc_end: jax.Array, cfg: ParisKVConfig
                           ) -> jax.Array:
    """Stage-I coarse scores over a paged pool — pure-jnp twin of the
    block-table-indirect kernel (kernels.collision.collision_paged_pallas).

    pool_ids:     (num_blocks, G, bs, B) uint8 physical centroid ids
    block_tables: (b, nblk) int32 (< 0 = unallocated; such positions lie
                  beyond enc_end and are masked)
    q_sub:        (b, G, Hg, B, m) rotated query subspaces
    counts:       (b, G, B, 2^m) int32 — the incrementally maintained
                  bucket histogram over each row's [sink, enc_end)
                  (cache state; replaces the per-query O(n) scatter-add)
    enc_end:      (b,) int32 retrieval-region end per row
    → (b, G, Hg, n_logical) int32 scores, -1 outside [sink, enc_end).

    Only the uint8 ids are gathered through the table — codes and weights
    never leave the pool at Stage I.
    """
    nb, G, bs, B = pool_ids.shape
    b, nblk = block_tables.shape
    n = nblk * bs
    nc = cfg.num_centroids()
    with jax.named_scope(phases.TIER_TABLE):
        cs = centroids.centroid_scores(q_sub, cfg.m)       # (b, G, Hg, B, 2^m)
        n_valid = jnp.maximum(enc_end - cfg.sink_size, 0)  # (b,)
        table = tier_weight_table(cs, counts[:, :, None],
                                  n_valid[:, None, None], cfg)
    with jax.named_scope(phases.STAGE1):
        safe = jnp.clip(block_tables, 0, nb - 1)
        ids = pool_ids[safe]                               # (b, nblk, G, bs, B)
        ids = jnp.moveaxis(ids, 2, 1).reshape(b, G, n, B)
        # same flat (B·2^m) lookup as collision_scores
        table_flat = table.reshape(table.shape[:-2] + (-1,))  # (b,G,Hg,B·2^m)
        offsets = jnp.arange(B, dtype=jnp.int32) * nc
        idx_flat = (ids.astype(jnp.int32) + offsets).reshape(b, G, 1, n * B)
        idx_flat = jnp.broadcast_to(idx_flat,
                                    table_flat.shape[:-1] + (n * B,))
        per_key = jnp.take_along_axis(table_flat, idx_flat, axis=-1)
        scores = per_key.reshape(per_key.shape[:-1] + (n, B)).sum(-1)
        pos = jnp.arange(n)
        valid = (pos[None] >= cfg.sink_size) & (pos[None] < enc_end[:, None])
        return jnp.where(valid[:, None, None, :], scores, -1)


def rerank_paged(pool_codes: jax.Array, pool_w: jax.Array,
                 phys_rows: jax.Array, cand_idx: jax.Array,
                 qt: QueryTransform, enc_end: jax.Array,
                 cfg: ParisKVConfig) -> jax.Array:
    """Stage-II RSQ-IP estimates gathered by physical pool row — only the
    ≤C candidates' codes/weights ever leave the pool.

    pool_codes/pool_w: (num_blocks, G, bs, B) pool metadata
    phys_rows:         (b, G, Hg, C) int32 flat physical row per candidate
    cand_idx:          (b, G, Hg, C) int32 logical positions (validity)
    qt:                q_sub (b, G, Hg, B, m), q_norm (b, G, Hg)
    → (b, G, Hg, C) float32; invalid candidates masked to -inf.

    Same float-op order as ``rerank`` → bit-identical estimates.
    """
    from repro.core import quantizer

    codes = gather_meta_heads_physical(pool_codes, phys_rows)
    w = gather_meta_heads_physical(pool_w, phys_rows)
    v = quantizer.decode_directions(codes, cfg.m, cfg.magnitude_bits)
    dots = jnp.einsum("...cbm,...bm->...cb", v, qt.q_sub,
                      precision=jax.lax.Precision.HIGHEST)
    est = qt.q_norm[..., None] * jnp.sum(w * dots, axis=-1)
    cand_valid = ((cand_idx >= cfg.sink_size)
                  & (cand_idx < enc_end[:, None, None, None]))
    return jnp.where(cand_valid, est, NEG_INF)


def _block_relative(idx: jax.Array, block_tables: jax.Array, block_size: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Logical positions → (safe physical block, offset, flat phys row),
    with retrieve_paged's clip-at-0 convention for unallocated entries."""
    blk, off = split_block_relative(idx, block_size)
    b = block_tables.shape[0]
    phys_blk = jnp.take_along_axis(
        block_tables, blk.reshape(b, -1), axis=1).reshape(blk.shape)
    safe_blk = jnp.clip(phys_blk, 0, None)
    return safe_blk, off, safe_blk * block_size + off


def _rerank_paged_kernel_batched(pool, phys_rows: jax.Array,
                                 cand_idx: jax.Array, qt: QueryTransform,
                                 enc_end: jax.Array,
                                 cfg: ParisKVConfig) -> jax.Array:
    """Stage II via the Pallas kernel (kernels.rerank.rerank_paged_kernel);
    the invalid-candidate mask matches ``rerank_paged``."""
    from repro.kernels.rerank import rerank_paged_kernel

    est = rerank_paged_kernel(pool.meta_codes, pool.meta_w, phys_rows,
                              qt.q_sub, qt.q_norm, m=cfg.m,
                              bits=cfg.magnitude_bits)
    cand_valid = ((cand_idx >= cfg.sink_size)
                  & (cand_idx < enc_end[:, None, None, None]))
    return jnp.where(cand_valid, est, NEG_INF)


def retrieve_paged_fused(pool, block_tables: jax.Array, qt: QueryTransform,
                         counts: jax.Array, enc_end: jax.Array,
                         cfg: ParisKVConfig, num_candidates: int, top_k: int,
                         bucket_select: bool = True,
                         use_kernels: bool = None) -> PagedRetrievalResult:
    """Fused two-stage retrieval directly over a paged pool — no
    ``paged_meta_view`` materialization anywhere.

    ``pool`` is a cache.PagedLayerKVCache (only meta_ids/meta_codes/meta_w
    are touched); ``counts`` the incrementally maintained (b, G, B, 2^m)
    bucket histogram (cache.bucket_hist_from_meta at admission +
    cache.paged_promote_rows_hist at promotion); ``enc_end`` (b,) the
    per-row retrieval-region end. Token-identical to ``retrieve_paged``
    over the materialized view whenever ``counts`` is exact and
    ``hist_sample == 0`` (the incremental histogram *is* exact, so the
    fused path has no sampled-histogram variant — it gets the exact
    boundaries for free).

    ``use_kernels`` picks the Pallas kernels (``collision_paged_pallas``
    for Stage I, ``rerank_paged_kernel`` for Stage II) over their pure-jnp
    twins. Default None → compiled kernels whenever the platform compiles
    them (TPU) and the twins elsewhere; ``REPRO_PALLAS_INTERPRET=1``
    forces the twins back even on TPU (kernels.resolve_interpret) — the
    serving path never silently runs the python kernel emulator.
    """
    bs = pool.meta_ids.shape[2]
    B = pool.meta_ids.shape[-1]
    b = block_tables.shape[0]
    enc_end = jnp.broadcast_to(jnp.asarray(enc_end, jnp.int32), (b,))
    if use_kernels is None:
        from repro.kernels import resolve_interpret
        use_kernels = not resolve_interpret(None)
    if use_kernels:
        from repro.kernels.collision import collision_scores_paged_kernel
        with jax.named_scope(phases.TIER_TABLE):
            cs = centroids.centroid_scores(qt.q_sub, cfg.m)
            n_valid = jnp.maximum(enc_end - cfg.sink_size, 0)
            table = tier_weight_table(cs, counts[:, :, None],
                                      n_valid[:, None, None], cfg)
        with jax.named_scope(phases.STAGE1):
            coarse = collision_scores_paged_kernel(
                pool.meta_ids, block_tables, table, enc_end, cfg.sink_size)
    else:
        coarse = collision_scores_paged(pool.meta_ids, block_tables,
                                        qt.q_sub, counts, enc_end, cfg)
    with jax.named_scope(phases.SELECT):
        if bucket_select:
            cand = select_candidates_bucket(
                coarse, num_candidates, score_range=max(cfg.tier_weights) * B)
        else:
            cand = select_candidates(coarse, num_candidates)
        _, _, cand_phys = _block_relative(cand, block_tables, bs)
    with jax.named_scope(phases.STAGE2):
        if use_kernels:
            est = _rerank_paged_kernel_batched(pool, cand_phys, cand, qt,
                                               enc_end, cfg)
        else:
            est = rerank_paged(pool.meta_codes, pool.meta_w, cand_phys, cand,
                               qt, enc_end, cfg)
        top_est, top_pos = jax.lax.top_k(est, top_k)
        top_idx = jnp.take_along_axis(cand, top_pos, axis=-1)
        safe_blk, off, phys_rows = _block_relative(top_idx, block_tables, bs)
    return PagedRetrievalResult(
        indices=top_idx, block_ids=safe_blk, offsets=off,
        phys_rows=phys_rows, scores=top_est,
        cand_indices=cand, coarse_scores=coarse)


def retrieve_paged_fused_sharded(pool, block_tables: jax.Array,
                                 qt: QueryTransform, counts: jax.Array,
                                 enc_end: jax.Array, cfg: ParisKVConfig,
                                 num_candidates: int, top_k: int,
                                 axis_name: str,
                                 bucket_select: bool = True,
                                 use_kernels: bool = None
                                 ) -> PagedRetrievalResult:
    """Shard-local fused retrieval + global top-C merge, for use *inside*
    ``jax.shard_map`` over a mesh axis that partitions KV heads.

    ``pool``/``counts``/``qt`` carry this shard's head slice; block tables
    and block numbering are replicated, so each shard's ``phys_rows``
    already address the global (replicated) block space. Stage I and
    Stage II run entirely shard-local via ``retrieve_paged_fused``; the
    merge is a single tiled ``all_gather`` on the head axis of every
    result leaf — a pure per-head concatenation with no float reductions,
    hence bit-identical to the single-device call on the full pool
    (every op in this module is per-head independent)."""
    res = retrieve_paged_fused(pool, block_tables, qt, counts, enc_end,
                               cfg, num_candidates, top_k,
                               bucket_select=bucket_select,
                               use_kernels=use_kernels)
    with jax.named_scope(phases.STAGE2):
        return PagedRetrievalResult(*[
            jax.lax.all_gather(leaf, axis_name, axis=1, tiled=True)
            for leaf in res])


def tiered_winner_rows(phys_rows: jax.Array, dev_map: jax.Array,
                       block_size: int) -> Tuple[jax.Array, jax.Array]:
    """Winner → staging-row translation for a tiered pool (ISSUE 6).

    Stage II's ``phys_rows`` address the *host* block space (they come
    from the host block tables). The K/V staging pool only holds the
    blocks in ``dev_map`` (num_blocks,) int32 (host block → staging
    block, -1 = not staged). → (resident, stag_rows): ``resident``
    (same shape) marks winners whose block is staged; ``stag_rows``
    gives their flat staging-pool row (garbage where not resident —
    callers must route those through the host fetch path instead)."""
    host_blk = phys_rows // block_size
    off = phys_rows % block_size
    stag = dev_map[jnp.clip(host_blk, 0, dev_map.shape[0] - 1)]
    return stag >= 0, jnp.maximum(stag, 0) * block_size + off


def exact_topk(keys: jax.Array, q: jax.Array, valid: jax.Array, top_k: int):
    """Oracle: exact inner-product Top-k over full-precision keys."""
    ip = jnp.einsum("...nd,...d->...n", keys.astype(jnp.float32),
                    q.astype(jnp.float32))
    ip = jnp.where(valid, ip, NEG_INF)
    vals, idx = jax.lax.top_k(ip, top_k)
    return idx.astype(jnp.int32), vals


def recall_at_k(retrieved: jax.Array, oracle: jax.Array) -> jax.Array:
    """|retrieved ∩ oracle| / |oracle| along the last axis."""
    hits = (retrieved[..., :, None] == oracle[..., None, :]).any(axis=-1)
    return hits.mean(axis=-1)
