"""Attention computation: full (oracle), blockwise prefill, retrieval-sparse decode.

``sparse_decode_attention`` realizes paper Eq. (2)-(3): the softmax is
restricted to the union Sink ∪ Retrieved-top-k ∪ Local∪Buffer window, which
are disjoint index ranges by construction (see core.cache). Full-precision
K/V for the retrieved set are *gathered* from the (sharded-HBM) retrieval
region — the TPU analogue of the paper's UVA on-demand fetch (DESIGN.md §2).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(x / cap)
    return x


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array] = None, *, sm_scale: float,
                   softcap: float = 0.0) -> jax.Array:
    """Oracle dense attention.

    q: (b, S, H, hd); k/v: (b, T, G, hd); mask: broadcastable (b, H, S, T).
    GQA: H queries share H//G-grouped kv heads. Returns (b, S, H, hd).
    """
    b, S, H, hd = q.shape
    T, G = k.shape[1], k.shape[2]
    qg = q.reshape(b, S, G, H // G, hd)
    scores = jnp.einsum("bsgqd,btgd->bgqst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    scores = _softcap(scores, softcap)
    if mask is not None:
        m = mask.reshape(b, G, H // G, S, T) if mask.ndim == 4 else mask
        scores = jnp.where(m, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqst,btgd->bsgqd", p, v.astype(jnp.float32))
    return out.reshape(b, S, H, hd)


def blockwise_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                               sm_scale: float, softcap: float = 0.0,
                               q_chunk: int = 1024, kv_chunk: int = 2048,
                               sliding_window: int = 0) -> jax.Array:
    """Flash-style two-level online-softmax causal attention (prefill path).

    Memory-bounded: never materializes the (S, T) score matrix — the working
    set is (q_chunk, kv_chunk) per head. Pure JAX; XLA fuses the inner scan.
    q: (b, S, H, hd), k/v: (b, S, G, hd) → (b, S, H, hd).
    """
    b, S, H, hd = q.shape
    G = k.shape[2]
    vd = v.shape[3]  # value head dim may differ from q/k (MLA)
    assert S % q_chunk == 0 and S % kv_chunk == 0, (S, q_chunk, kv_chunk)
    nq, nk = S // q_chunk, S // kv_chunk
    qg = q.reshape(b, nq, q_chunk, G, H // G, hd).astype(jnp.float32)
    kc = k.reshape(b, nk, kv_chunk, G, hd).astype(jnp.float32)
    vc = v.reshape(b, nk, kv_chunk, G, vd).astype(jnp.float32)

    q_pos = jnp.arange(S).reshape(nq, q_chunk)
    k_pos = jnp.arange(S).reshape(nk, kv_chunk)

    def one_q_chunk(qi, q_blk):
        # q_blk: (b, q_chunk, G, Hg, hd)
        def kv_step(carry, inputs):
            acc, m_run, l_run = carry
            k_blk, v_blk, kp = inputs
            s = jnp.einsum("bqghd,bkgd->bgqhk",
                           q_blk, k_blk) * sm_scale  # (b, G, qc, Hg, kc)
            s = _softcap(s, softcap)
            causal = q_pos[qi][None, None, :, None, None] >= kp[None, None, None, None, :]
            if sliding_window:
                inside = (q_pos[qi][None, None, :, None, None]
                          - kp[None, None, None, None, :]) < sliding_window
                causal = jnp.logical_and(causal, inside)
            s = jnp.where(causal, s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            scale = jnp.exp(m_run - m_new)
            l_new = l_run * scale + p.sum(axis=-1)
            acc = acc * scale[..., None] + jnp.einsum("bgqhk,bkgd->bgqhd", p, v_blk)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, G, q_chunk, H // G, vd), jnp.float32)
        m0 = jnp.full((b, G, q_chunk, H // G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, G, q_chunk, H // G), jnp.float32)
        # REPRO_UNROLL_ATTN=1: unroll inner scans so HLO cost analysis sees
        # every block (while bodies are otherwise counted once — dryrun
        # trip-count correction, EXPERIMENTS.md §Roofline methodology).
        unroll = os.environ.get("REPRO_UNROLL_ATTN") == "1"
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), k_pos),
            unroll=True if unroll else 1)
        out = acc / jnp.maximum(l_run, 1e-20)[..., None]
        return jnp.moveaxis(out, 1, 2).reshape(b, q_chunk, H, vd)

    if os.environ.get("REPRO_UNROLL_ATTN") == "1":
        outs = jnp.stack([one_q_chunk(i, qg[:, i]) for i in range(nq)])
    else:
        outs = jax.lax.map(lambda i: one_q_chunk(i, qg[:, i]), jnp.arange(nq))
    return jnp.moveaxis(outs, 0, 1).reshape(b, S, H, vd)


def gather_kv_heads(cache: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather per-(kv-head, query-head) selected tokens from the KV store.

    cache: (b, n, G, hd);  idx: (b, G, Q, k) positions → (b, G, Q, k, hd).
    This is the UVA-fetch analogue (see kernels/gather_kv for the Pallas
    version operating on the sequence-sharded store).
    """
    c = jnp.moveaxis(cache, 2, 1)                     # (b, G, n, hd)
    b, G, n, hd = c.shape
    _, _, Q, k = idx.shape
    flat = idx.reshape(b, G, Q * k)
    out = jnp.take_along_axis(c, flat[..., None], axis=2)
    return out.reshape(b, G, Q, k, hd)


def sparse_decode_attention(q: jax.Array,
                            k_cache: jax.Array, v_cache: jax.Array,
                            top_idx: jax.Array,
                            window_start: jax.Array, pos: jax.Array,
                            enc_end: jax.Array, *,
                            sink_size: int, window_size: int,
                            sm_scale: float, softcap: float = 0.0,
                            k_ret: Optional[jax.Array] = None,
                            v_ret: Optional[jax.Array] = None) -> jax.Array:
    """Decode-step attention over Sink ∪ Retrieved ∪ Local/Buffer (Eq. 2-3).

    q:        (b, H, hd) — single new-token query per sequence
    k_cache:  (b, n_max, G, hd) (same for v_cache)
    top_idx:  (b, G, Hg, k) retrieved positions (∈ [sink, enc_end))
    window_start: (b,) int32 (scalar broadcasts) — per-row static-size dense
              window [ws[i], ws[i]+window_size)
    pos:      (b,) int32 (scalar broadcasts) — per-row current token
              position (row i attends ≤ pos[i])
    enc_end:  (b,) int32 (scalar broadcasts) — per-row retrieval-region end;
              window positions < enc_end[i] are masked out (they are covered
              by retrieval instead)
    """
    b, H, hd = q.shape
    G = k_cache.shape[2]
    Hg = H // G
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    enc_end = jnp.broadcast_to(jnp.asarray(enc_end, jnp.int32), (b,))
    window_start = jnp.broadcast_to(jnp.asarray(window_start, jnp.int32), (b,))
    if k_ret is None:  # rows may arrive pre-fetched (distributed retrieval)
        k_ret = gather_kv_heads(k_cache, top_idx)      # (b, G, Hg, k, hd)
        v_ret = gather_kv_heads(v_cache, top_idx)
    qg = q.reshape(b, G, Hg, hd).astype(jnp.float32)

    k_sink = k_cache[:, :sink_size]                    # (b, sink, G, hd)
    v_sink = v_cache[:, :sink_size]

    def slice_window(c):
        return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
            row, s, window_size, axis=0))(c, window_start)
    k_loc = slice_window(k_cache)                      # (b, W, G, hd)
    v_loc = slice_window(v_cache)

    return _segment_attention(
        qg, k_sink, v_sink, k_ret, v_ret, k_loc, v_loc, top_idx,
        window_start, pos, enc_end, sink_size=sink_size,
        window_size=window_size, sm_scale=sm_scale, softcap=softcap
    ).reshape(b, H, hd)


def _isolated(fn, *args):
    """``fn(*args)`` fenced by optimization barriers on both sides.

    The decode engines hand the attention math operands from different
    producers — pool gathers, the host-fetch hit/miss blend, gathers
    hoisted by the overlapped pipeline. Left alone, XLA fuses each
    producer into the contractions differently per program, and the TPU
    then tiles and sums them differently: the resident and offloaded
    engines' tokens drift apart after a few steps. Behind the barriers
    every program compiles this math from the same isolated graph.
    """
    out = fn(*jax.lax.optimization_barrier(args))
    return jax.lax.optimization_barrier(out)


def dense_segment_scores(qg: jax.Array, k_sink: jax.Array,
                         k_loc: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Raw (unmasked, unscaled) sink/window score einsums.

    Split out of ``_segment_attention`` so the overlapped fetch pipeline
    (ISSUE 9) can run these two einsums while the host K/V fetch is in
    flight — they depend only on staging-resident keys. Both the hoisted
    and the inline path call this exact function (behind ``_isolated``),
    so the scores are bit-identical regardless of where they were
    scheduled.

    qg: (b, G, Hg, hd) float32 → s_sink (b, G, Hg, sink), s_loc
    (b, G, Hg, W).
    """
    def scores(qg, k_sink, k_loc):
        s_sink = jnp.einsum("bghd,bsgd->bghs", qg, k_sink.astype(jnp.float32))
        s_loc = jnp.einsum("bghd,bwgd->bghw", qg, k_loc.astype(jnp.float32))
        return s_sink, s_loc

    return _isolated(scores, qg, k_sink, k_loc)


def _joint_softmax(qg, k_ret, v_sink, v_ret, v_loc, s_sink, s_loc,
                   sink_valid, ret_valid, loc_valid, *, sm_scale: float,
                   softcap: float) -> jax.Array:
    """The float math of ``_segment_attention``: retrieved scores, joint
    masked softmax, value mix.

    Each query head attends to its own k winners, but scoring them per
    head is a batched mat-vec, which the TPU backend lowers to a VPU
    multiply-reduce whose summation order follows operand layouts that
    the barriers do not pin. So every query head of a kv head scores the
    union of the group's Hg·k winners in one MXU contraction — the form
    of the sink/window segments — and is masked to its own k: the other
    heads' winners get exactly zero probability.
    """
    b, G, Hg, k_sz, hd = k_ret.shape
    k_ret = k_ret.reshape(b, G, Hg * k_sz, hd)
    v_ret = v_ret.reshape(b, G, Hg * k_sz, hd)
    s_ret = jnp.einsum("bghd,bgnd->bghn", qg, k_ret.astype(jnp.float32))
    own = (jnp.arange(Hg * k_sz) // k_sz)[None, :] == jnp.arange(Hg)[:, None]
    ret_valid = own & ret_valid.reshape(b, G, 1, Hg * k_sz)
    s_ret = jnp.where(ret_valid, s_ret, NEG_INF)
    s_sink = jnp.where(sink_valid[:, None, None, :], s_sink, NEG_INF)
    s_loc = jnp.where(loc_valid[:, None, None, :], s_loc, NEG_INF)

    scores = jnp.concatenate([s_sink, s_ret, s_loc], axis=-1) * sm_scale
    scores = _softcap(scores, softcap)
    p = jax.nn.softmax(scores, axis=-1)
    sink_size = s_sink.shape[-1]
    p_sink, p_ret, p_loc = jnp.split(p, [sink_size, sink_size + Hg * k_sz],
                                     axis=-1)
    out = jnp.einsum("bghs,bsgd->bghd", p_sink, v_sink.astype(jnp.float32))
    out += jnp.einsum("bghn,bgnd->bghd", p_ret, v_ret.astype(jnp.float32))
    out += jnp.einsum("bghw,bwgd->bghd", p_loc, v_loc.astype(jnp.float32))
    return out


def _segment_attention(qg: jax.Array,
                       k_sink: jax.Array, v_sink: jax.Array,
                       k_ret: jax.Array, v_ret: jax.Array,
                       k_loc: jax.Array, v_loc: jax.Array,
                       top_idx: jax.Array, window_start: jax.Array,
                       pos: jax.Array, enc_end: jax.Array, *,
                       sink_size: int, window_size: int,
                       sm_scale: float, softcap: float,
                       s_sink: Optional[jax.Array] = None,
                       s_loc: Optional[jax.Array] = None,
                       ret_keep: Optional[jax.Array] = None) -> jax.Array:
    """Joint softmax over the three gathered segments (Eq. 2-3 core).

    The segments may come from a contiguous per-row cache *or* from a
    paged block pool — the validity masks depend only on logical
    positions, so both layouts produce identical attention (values at
    masked slots are garbage in either layout and receive exactly-zero
    probability; pools hold only zeros/real activations, never NaN).

    qg: (b, G, Hg, hd) float32; k_sink/v_sink: (b, sink, G, hd);
    k_ret/v_ret: (b, G, Hg, k, hd) — head h's k winners; k_loc/v_loc:
    (b, W, G, hd).
    ``s_sink``/``s_loc`` may arrive precomputed (see
    ``dense_segment_scores``); masking always happens here.
    ``ret_keep`` (b, G, Hg, k) bool, optional: extra validity on the
    retrieved segment — the tiered degraded-mode mask (ISSUE 10) drops
    winners whose host fetch exhausted its retries, so the step falls
    back to sink + window + whatever was resident instead of attending
    to zeroed garbage. All-True (or None) is bit-identical.
    → (b, G, Hg, hd) float32.
    """
    # guard: only positions actually inside the Retrieval region count —
    # with an empty region (early decode) Stage-II returns arbitrary indices
    ret_valid = (top_idx >= sink_size) & (top_idx < enc_end[:, None, None, None])
    if ret_keep is not None:
        ret_valid = ret_valid & ret_keep
    sink_valid = jnp.arange(sink_size)[None] <= pos[:, None]    # (b, sink)
    # local + update-buffer window
    w_pos = window_start[:, None] + jnp.arange(window_size)     # (b, W)
    loc_valid = ((w_pos >= enc_end[:, None]) & (w_pos >= sink_size)
                 & (w_pos <= pos[:, None]))

    if s_sink is None:
        s_sink, s_loc = dense_segment_scores(qg, k_sink, k_loc)
    return _isolated(
        functools.partial(_joint_softmax, sm_scale=sm_scale, softcap=softcap),
        qg, k_ret, v_sink, v_ret, v_loc, s_sink, s_loc, sink_valid,
        ret_valid, loc_valid)


def sparse_decode_attention_paged(q: jax.Array, pool_k: jax.Array,
                                  pool_v: jax.Array, block_tables: jax.Array,
                                  top_idx: jax.Array, window_start: jax.Array,
                                  pos: jax.Array, enc_end: jax.Array, *,
                                  sink_size: int, window_size: int,
                                  sm_scale: float, softcap: float = 0.0,
                                  k_ret: Optional[jax.Array] = None,
                                  v_ret: Optional[jax.Array] = None,
                                  k_sink: Optional[jax.Array] = None,
                                  v_sink: Optional[jax.Array] = None,
                                  k_loc: Optional[jax.Array] = None,
                                  v_loc: Optional[jax.Array] = None,
                                  s_sink: Optional[jax.Array] = None,
                                  s_loc: Optional[jax.Array] = None,
                                  ret_keep: Optional[jax.Array] = None
                                  ) -> jax.Array:
    """Paged twin of ``sparse_decode_attention``: all three segments are
    gathered from the shared block pool through per-row block tables
    (kernels/gather_kv provides the Pallas fast path for these gathers).

    pool_k/pool_v: (num_blocks, block_size, G, hd); block_tables:
    (b, n_logical // block_size) int32; ``top_idx`` holds *logical*
    positions (as produced by retrieval over the logical metadata view) —
    the retrieved rows themselves may arrive pre-fetched via
    ``k_ret``/``v_ret`` (retrieve_paged hands out block-relative physical
    rows, so the caller can gather without a second table lookup), and
    the dense sink/window segments likewise via ``k_sink``/``v_loc``/…
    (the overlapped fetch pipeline hoists those gathers into the window
    between its begin and collect callbacks — gather placement never
    changes the math). Masks are identical to the contiguous path, so
    the result is token-identical for the same cache contents.
    """
    from repro.core import cache as CC

    b, H, hd = q.shape
    G = pool_k.shape[2]
    Hg = H // G
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    enc_end = jnp.broadcast_to(jnp.asarray(enc_end, jnp.int32), (b,))
    window_start = jnp.broadcast_to(jnp.asarray(window_start, jnp.int32), (b,))
    qg = q.reshape(b, G, Hg, hd).astype(jnp.float32)

    if k_ret is None:
        k_ret = CC.paged_gather_heads(pool_k, block_tables, top_idx)
        v_ret = CC.paged_gather_heads(pool_v, block_tables, top_idx)

    if k_sink is None:
        sink_idx = jnp.broadcast_to(jnp.arange(sink_size)[None],
                                    (b, sink_size))
        k_sink = CC.paged_gather_rows(pool_k, block_tables, sink_idx)
        v_sink = CC.paged_gather_rows(pool_v, block_tables, sink_idx)

    if k_loc is None:
        w_idx = window_start[:, None] + jnp.arange(window_size)[None]
        k_loc = CC.paged_gather_rows(pool_k, block_tables, w_idx)
        v_loc = CC.paged_gather_rows(pool_v, block_tables, w_idx)

    return _segment_attention(
        qg, k_sink, v_sink, k_ret, v_ret, k_loc, v_loc, top_idx,
        window_start, pos, enc_end, sink_size=sink_size,
        window_size=window_size, sm_scale=sm_scale, softcap=softcap,
        s_sink=s_sink, s_loc=s_loc, ret_keep=ret_keep
    ).reshape(b, H, hd)


def sparse_decode_attention_tiered(q: jax.Array, pool_k: jax.Array,
                                   pool_v: jax.Array,
                                   block_tables: jax.Array,
                                   dev_map: jax.Array,
                                   top_idx: jax.Array,
                                   window_start: jax.Array,
                                   pos: jax.Array, enc_end: jax.Array, *,
                                   sink_size: int, window_size: int,
                                   sm_scale: float, softcap: float = 0.0,
                                   k_ret: Optional[jax.Array] = None,
                                   v_ret: Optional[jax.Array] = None,
                                   k_sink: Optional[jax.Array] = None,
                                   v_sink: Optional[jax.Array] = None,
                                   k_loc: Optional[jax.Array] = None,
                                   v_loc: Optional[jax.Array] = None,
                                   s_sink: Optional[jax.Array] = None,
                                   s_loc: Optional[jax.Array] = None,
                                   ret_keep: Optional[jax.Array] = None
                                   ) -> jax.Array:
    """Tiered twin of ``sparse_decode_attention_paged`` (ISSUE 6): the
    dense sink/window gathers are indirected through the **staging map**
    instead of the raw pool — ``pool_k``/``pool_v`` are the bounded
    staging leaves and the host block tables are composed with
    ``dev_map`` (host block → staging block) before any K/V read. The
    engine pins sink + window blocks staging-resident, so these gathers
    always hit; the retrieved segment must arrive pre-fetched via
    ``k_ret``/``v_ret`` (hit/miss-blended by the caller — winners may
    live on either tier). The overlapped fetch pipeline (ISSUE 9) also
    pre-gathers sink/window via ``k_sink``/``k_loc``/… so the dense
    reads run while the host fetch is in flight."""
    from repro.core import cache as CC

    assert k_ret is not None and v_ret is not None, \
        "tiered attention needs the hit/miss-blended retrieved rows"
    bt_dev = CC.tiered_kv_tables(block_tables, dev_map)
    return sparse_decode_attention_paged(
        q, pool_k, pool_v, bt_dev, top_idx, window_start, pos, enc_end,
        sink_size=sink_size, window_size=window_size, sm_scale=sm_scale,
        softcap=softcap, k_ret=k_ret, v_ret=v_ret, k_sink=k_sink,
        v_sink=v_sink, k_loc=k_loc, v_loc=v_loc, s_sink=s_sink,
        s_loc=s_loc, ret_keep=ret_keep)


def chunk_fill_attention(q: jax.Array, k_pref: jax.Array, v_pref: jax.Array,
                         pref_pos: jax.Array, k_new: jax.Array,
                         v_new: jax.Array, q_pos: jax.Array,
                         new_pos: jax.Array, *, sm_scale: float,
                         softcap: float = 0.0,
                         sliding_window: int = 0) -> jax.Array:
    """Prefill-chunk attention for the mixed prefill+decode step: P prompt
    tokens of one filling slot attend to that slot's already-cached prefix
    plus the chunk itself under one joint softmax.

    Masking is purely positional, so the prefix may come from any cache
    layout (contiguous row, ring buffer, paged gather) as long as the
    caller supplies each prefix key's logical position:

    q:             (b, P, H, hd) — the chunk's queries
    k_pref/v_pref: (b, n, G, hd) — cached prefix view
    pref_pos:      (b, n) int32  — logical position per prefix key,
                   < 0 = invalid (unwritten / stale / evicted-from-ring)
    k_new/v_new:   (b, P, G, hd) — the chunk's own keys/values
    q_pos:         (b, P) int32  — query positions
    new_pos:       (b, P) int32  — chunk key positions, < 0 = invalid
                   (the final partial chunk's pad tail)

    Chunk-causal: key j (prefix or chunk) is visible to query t iff
    0 ≤ pos_j ≤ q_pos_t (and within ``sliding_window`` when set). The
    same key set a solo prefill's causal attention sees — token-identical
    up to float summation order.
    """
    b, P, H, hd = q.shape
    G = k_pref.shape[2]
    qg = q.reshape(b, P, G, H // G, hd).astype(jnp.float32)

    def seg(k, v, pos):
        s = jnp.einsum("bpghd,bngd->bghpn", qg, k.astype(jnp.float32))
        ok = (pos[:, None, :] >= 0) & (pos[:, None, :] <= q_pos[:, :, None])
        if sliding_window:
            ok &= (q_pos[:, :, None] - pos[:, None, :]) < sliding_window
        return jnp.where(ok[:, None, None], s, NEG_INF), v.astype(jnp.float32)

    s_pref, vp = seg(k_pref, v_pref, pref_pos)
    s_self, vs = seg(k_new, v_new, new_pos)
    scores = jnp.concatenate([s_pref, s_self], axis=-1) * sm_scale
    scores = _softcap(scores, softcap)
    p = jax.nn.softmax(scores, axis=-1)
    p_pref, p_self = jnp.split(p, [k_pref.shape[1]], axis=-1)
    out = jnp.einsum("bghpn,bngd->bpghd", p_pref, vp)
    out += jnp.einsum("bghpt,btgd->bpghd", p_self, vs)
    return out.reshape(b, P, H, hd)


def dense_decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                           pos: jax.Array, *, sm_scale: float,
                           softcap: float = 0.0,
                           sliding_window: int = 0) -> jax.Array:
    """Full-cache decode attention (baseline / local-layer path).

    q: (b, H, hd); caches (b, n_max, G, hd); row i attends to positions
    ≤ pos[i] (``pos`` (b,) int32; scalar broadcasts), optionally within a
    sliding window."""
    b, H, hd = q.shape
    n, G = k_cache.shape[1], k_cache.shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qg = q.reshape(b, G, H // G, hd).astype(jnp.float32)
    s = jnp.einsum("bghd,bngd->bghn", qg,
                   k_cache.astype(jnp.float32)) * sm_scale
    s = _softcap(s, softcap)
    positions = jnp.arange(n)[None]                      # (1, n)
    valid = positions <= pos[:, None]                    # (b, n)
    if sliding_window:
        valid &= positions > (pos[:, None] - sliding_window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bghn,bngd->bghd", p,
                    v_cache.astype(jnp.float32))
    return out.reshape(b, H, hd)
