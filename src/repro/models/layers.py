"""Shared transformer building blocks (pure functional, pytree params).

Every ``init_*`` returns a dict pytree of arrays; every ``*_fwd`` consumes it.
Sharding is attached later by name-based rules (launch/mesh.py), so there is
no module framework — just conventions:

  * weight names: wq/wk/wv/wo (attention), wi_gate/wi_up/wo_mlp (MLP),
    experts_* (MoE), embed / unembed.
  * matmul weights are stored (in_dim, out_dim).

Attention supports the paper's serving integration: ``decode`` mode routes
global layers through ParisKV two-stage retrieval (core.retrieval) and local
(sliding-window) layers through a dense ring-buffer window.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention as A
from repro.core import cache as C
from repro.core import encode as E
from repro.core import phases
from repro.core import retrieval as R
from repro.core.config import ParisKVConfig


# ----------------------------------------------------------------- helpers --
def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled — the serving
    specs mark replicated outputs that the checker's static analysis
    cannot prove (e.g. values equalized by all_gather)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def truncated_normal(key, shape, std=0.02, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    s = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(jnp.float32)
    return (y * s).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1·m·ln s + 1`` (1 at s ≤ 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, ys) -> np.ndarray:
    """YaRN's per-pair rotary frequencies (``ys``: a config.YarnRope), as
    the DeepSeek-V2 modeling code builds them: pair ``i`` of ``dim`` keeps
    its rope frequency below the ``beta_fast`` correction dimension,
    takes the ``factor``-interpolated one above the ``beta_slow`` one, and
    ramps linearly in between. float32 (dim // 2,)."""
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) * 2 / dim)
    inter = extra / ys.factor
    L0 = ys.original_max_position_embeddings

    def corr(beta):
        return dim * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(ys.beta_fast)), 0)
    high = min(math.ceil(corr(ys.beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling=None) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, hd); positions: (..., seq).
    ``scaling`` (a config.YarnRope) switches to YaRN's frequencies, with
    cos/sin scaled by ``mscale(s, mscale) / mscale(s, mscale_all_dim)``."""
    hd = x.shape[-1]
    half = hd // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_inv_freq(hd, theta, scaling))
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., seq, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scaling is not None:
        f = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if f != 1.0:
            cos, sin = cos * f, sin * f
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> np.ndarray:
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    return np.concatenate([np.sin(ang), np.cos(ang)], -1).astype(np.float32)


# ------------------------------------------------------------------- MLP ----
def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": truncated_normal(k1, (d_model, d_ff)).astype(dtype),
        "wi_up": truncated_normal(k2, (d_model, d_ff)).astype(dtype),
        "wo_mlp": truncated_normal(k3, (d_ff, d_model)).astype(dtype),
    }


def mlp_fwd(p: dict, x: jax.Array, act=jax.nn.silu) -> jax.Array:
    h = act(x @ p["wi_gate"]) * (x @ p["wi_up"])
    return h @ p["wo_mlp"]


# ------------------------------------------------------------- attention ----
@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static per-layer attention behaviour."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    softcap: float = 0.0
    sliding_window: int = 0      # >0 → local layer (ring-buffer decode cache)
    qk_norm: bool = False
    sm_scale: float = 0.0        # 0 → 1/sqrt(head_dim)
    causal: bool = True          # False for encoder / cross attention

    def scale(self) -> float:
        return self.sm_scale or (1.0 / float(np.sqrt(self.head_dim)))


def init_attn(key, d_model: int, spec: AttnSpec, dtype) -> dict:
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal(ks[0], (d_model, H * hd)).astype(dtype),
        "wk": truncated_normal(ks[1], (d_model, G * hd)).astype(dtype),
        "wv": truncated_normal(ks[2], (d_model, G * hd)).astype(dtype),
        "wo": truncated_normal(ks[3], (H * hd, d_model)).astype(dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((G * hd,), dtype)
        p["bv"] = jnp.zeros((G * hd,), dtype)
    if spec.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p: dict, x: jax.Array, spec: AttnSpec,
                 positions: Optional[jax.Array]):
    """x: (b, s, d) → q (b,s,H,hd), k/v (b,s,G,hd), rope applied."""
    b, s, _ = x.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, H, hd)
    k = k.reshape(b, s, G, hd)
    v = v.reshape(b, s, G, hd)
    if spec.qk_norm:
        q = rms_norm(q, p["q_norm"], plus_one=True)
        k = rms_norm(k, p["k_norm"], plus_one=True)
    if positions is not None:
        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
    return q, k, v


def attn_train(p: dict, x: jax.Array, spec: AttnSpec,
               positions: jax.Array) -> jax.Array:
    """Causal training/prefill attention (blockwise, memory-bounded)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, spec, positions)
    q_chunk = min(1024, s)
    kv_chunk = min(2048, s)
    out = A.blockwise_causal_attention(
        q, k, v, sm_scale=spec.scale(), softcap=spec.softcap,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
        sliding_window=spec.sliding_window)
    return out.reshape(b, s, -1) @ p["wo"]


def attn_encoder(p: dict, x: jax.Array, spec: AttnSpec) -> jax.Array:
    """Bidirectional (encoder) attention, no rope (whisper encoder)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, spec, None)
    out = A.full_attention(q, k, v, None, sm_scale=spec.scale())
    return out.reshape(b, s, -1) @ p["wo"]


def attn_cross(p: dict, x: jax.Array, kv_src: jax.Array,
               spec: AttnSpec) -> jax.Array:
    """Cross attention: queries from x (b,s,d), keys/values from kv_src
    (b,t,d). Used by whisper decoder and llama-vision cross layers."""
    b, s, _ = x.shape
    t = kv_src.shape[1]
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(b, s, H, hd)
    k = (kv_src @ p["wk"]).reshape(b, t, G, hd)
    v = (kv_src @ p["wv"]).reshape(b, t, G, hd)
    if spec.qk_norm:
        q = rms_norm(q, p["q_norm"], plus_one=True)
        k = rms_norm(k, p["k_norm"], plus_one=True)
    out = A.full_attention(q, k, v, None, sm_scale=spec.scale())
    return out.reshape(b, s, -1) @ p["wo"]


def attn_prefill(p: dict, x: jax.Array, spec: AttnSpec, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Like attn_train but also returns (k, v) for cache population."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, x, spec, positions)
    out = A.blockwise_causal_attention(
        q, k, v, sm_scale=spec.scale(), softcap=spec.softcap,
        q_chunk=min(1024, s), kv_chunk=min(2048, s),
        sliding_window=spec.sliding_window)
    return out.reshape(b, s, -1) @ p["wo"], k, v


def _decode_qkv(p: dict, x_t: jax.Array, spec: AttnSpec, pos: jax.Array):
    """x_t: (b, d) single token → q (b,H,hd), k/v (b,G,hd) with rope at the
    per-row position ``pos`` ((b,) int32; scalar broadcasts)."""
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    with jax.named_scope(phases.DENSE):
        q = x_t @ p["wq"]
        k = x_t @ p["wk"]
        v = x_t @ p["wv"]
        if spec.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(b, 1, H, hd)
        k = k.reshape(b, 1, G, hd)
        v = v.reshape(b, 1, G, hd)
        if spec.qk_norm:
            q = rms_norm(q, p["q_norm"], plus_one=True)
            k = rms_norm(k, p["k_norm"], plus_one=True)
        pos_arr = jnp.broadcast_to(jnp.asarray(pos), (b,))[:, None]
        q = rope(q, pos_arr, spec.rope_theta)
        k = rope(k, pos_arr, spec.rope_theta)
        return q[:, 0], k[:, 0], v[:, 0]


def _decode_out(p: dict, out: jax.Array, dtype) -> jax.Array:
    """Output projection of one decoded token's attention heads."""
    with jax.named_scope(phases.DENSE):
        return out.reshape(out.shape[0], -1).astype(dtype) @ p["wo"]


def attn_fill_chunk(p: dict, x: jax.Array, spec: AttnSpec, q_pos: jax.Array,
                    k_pref: jax.Array, v_pref: jax.Array,
                    pref_pos: jax.Array, new_pos: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer of one prefill chunk (mixed prefill+decode step): project
    the chunk's qkv at its true per-token positions ``q_pos`` (b, P) —
    rope is per-row, so the chunk shares a batched step with single-token
    decode rows at entirely different positions — and attend chunk-causally
    to the cached prefix + the chunk itself. Returns (y, k, v): the caller
    writes k/v (and ParisKV metadata) into the filling slot's cache."""
    b, P, _ = x.shape
    q, k, v = _project_qkv(p, x, spec, q_pos)
    out = A.chunk_fill_attention(
        q, k_pref, v_pref, pref_pos, k, v, q_pos, new_pos,
        sm_scale=spec.scale(), softcap=spec.softcap,
        sliding_window=spec.sliding_window)
    return out.reshape(b, P, -1) @ p["wo"], k, v


def attn_decode_dense(p: dict, x_t: jax.Array, kv: Tuple[jax.Array, jax.Array],
                      pos: jax.Array, spec: AttnSpec
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Dense decode over a (possibly ring-buffered) cache.

    kv: (k_cache, v_cache) each (b, n, G, hd). ``pos`` is (b,) int32 (scalar
    broadcasts). For sliding-window layers the cache length n equals the
    window and indices wrap per row (pos[i] % n)."""
    k_cache, v_cache = kv
    n = k_cache.shape[1]
    b = x_t.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    slot = pos % n if spec.sliding_window else pos
    upd = jax.vmap(lambda c, t, s: jax.lax.dynamic_update_slice_in_dim(
        c, t[None], s, axis=0))
    k_cache = upd(k_cache, k_t.astype(k_cache.dtype), slot)
    v_cache = upd(v_cache, v_t.astype(v_cache.dtype), slot)
    if spec.sliding_window and spec.sliding_window <= n:
        # ring buffer: all n slots valid once pos[i] >= n-1; before, ≤ pos[i]
        valid = ((jnp.arange(n)[None] <= pos[:, None])
                 | (pos[:, None] >= n))                   # (b, n)
        b, H, hd = q.shape
        G = k_cache.shape[2]
        qg = q.reshape(b, G, H // G, hd).astype(jnp.float32)
        s = jnp.einsum("bghd,bngd->bghn", qg, k_cache.astype(jnp.float32))
        s = s * spec.scale()
        if spec.softcap:
            s = spec.softcap * jnp.tanh(s / spec.softcap)
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        prob = jax.nn.softmax(s, -1)
        out = jnp.einsum("bghn,bngd->bghd", prob, v_cache.astype(jnp.float32))
        out = out.reshape(b, H * hd)
    else:
        out = A.dense_decode_attention(
            q, k_cache, v_cache, pos, sm_scale=spec.scale(),
            softcap=spec.softcap, sliding_window=spec.sliding_window)
        out = out.reshape(out.shape[0], -1)
    return out.astype(x_t.dtype) @ p["wo"], (k_cache, v_cache)


def distributed_retrieve_fetch(q_grp: jax.Array, layer_cache: C.LayerKVCache,
                               regions: C.CacheRegions, pcfg: ParisKVConfig,
                               signs: jax.Array, mesh, seq_axes, batch_axes
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Hierarchical (context-parallel) two-stage retrieval + row fetch.

    Beyond-paper first-class feature (EXPERIMENTS §Perf E1/E2): the
    retrieval region is sequence-sharded; each shard scores its local
    metadata, takes a local top-k, the per-shard winners are all-gathered
    (k·shards rows — tiny) and merged into an exact global top-k; each
    shard contributes its owned K/V rows via masked gather + psum instead
    of GSPMD's cache-scale all-gathers.

    q_grp: (b, G, Hg, hd) → (top_idx (b,G,Hg,k) global positions,
                             k_ret, v_ret (b,G,Hg,k,hd)).
    """
    from repro.core.attention import gather_kv_heads
    seq_tuple = seq_axes if isinstance(seq_axes, tuple) else (seq_axes,)
    n = layer_cache.k.shape[1]
    n_shards = int(np.prod([mesh.shape[a] for a in seq_tuple]))
    n_loc = n // n_shards
    k_top = pcfg.top_k
    C_loc = min(pcfg.candidate_count(n_loc), n_loc)

    def local(q, k_cache, v_cache, ids, codes, w, pos, enc_end):
        base = jax.lax.axis_index(seq_tuple) * n_loc
        meta = E.KeyMetadata(ids[:, :, None], codes[:, :, None],
                             w[:, :, None])
        qt = E.encode_query(q, pcfg, signs)
        gpos = base + jnp.arange(n_loc)
        # enc_end is per-row (b,): each sequence has its own region boundary
        enc_b = jnp.broadcast_to(jnp.asarray(enc_end, jnp.int32),
                                 (q.shape[0],))
        valid = (gpos[None] >= pcfg.sink_size) & (gpos[None] < enc_b[:, None])
        valid = jnp.broadcast_to(valid[:, None, None, :],
                                 (q.shape[0], q.shape[1], 1, n_loc))
        res = R.retrieve(meta, qt, valid, pcfg, C_loc, k_top,
                         hist_sample=pcfg.hist_sample)
        with jax.named_scope(phases.STAGE2):
            glob_idx = res.indices + base
            all_scores = jnp.moveaxis(
                jax.lax.all_gather(res.scores, seq_tuple), 0, -2).reshape(
                    res.scores.shape[:-1] + (n_shards * k_top,))
            all_idx = jnp.moveaxis(
                jax.lax.all_gather(glob_idx, seq_tuple), 0, -2).reshape(
                    glob_idx.shape[:-1] + (n_shards * k_top,))
            _, ppos = jax.lax.top_k(all_scores, k_top)
            final_idx = jnp.take_along_axis(all_idx, ppos, -1)

        with jax.named_scope(phases.ATTEND):
            loc_idx = final_idx - base
            mine = (loc_idx >= 0) & (loc_idx < n_loc)
            safe = jnp.clip(loc_idx, 0, n_loc - 1)
            k_rows = gather_kv_heads(k_cache, safe) * mine[..., None]
            v_rows = gather_kv_heads(v_cache, safe) * mine[..., None]
            k_sel = jax.lax.psum(k_rows.astype(jnp.float32), seq_tuple)
            v_sel = jax.lax.psum(v_rows.astype(jnp.float32), seq_tuple)
        return final_idx, k_sel, v_sel

    P = jax.sharding.PartitionSpec
    ba = batch_axes
    in_specs = (P(ba, None, None, None),            # q replicated over seq
                P(ba, seq_axes, None, None),        # k cache
                P(ba, seq_axes, None, None),        # v cache
                P(ba, None, seq_axes, None),        # ids
                P(ba, None, seq_axes, None),        # codes
                P(ba, None, seq_axes, None),        # w
                P(ba), P(ba))                       # per-row pos / enc_end
    out_specs = (P(ba, None, None, None),
                 P(ba, None, None, None, None),
                 P(ba, None, None, None, None))
    fn = shard_map_compat(local, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs)
    b = q_grp.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,))
    enc_b = jnp.broadcast_to(jnp.asarray(regions.enc_end, jnp.int32), (b,))
    return fn(q_grp, layer_cache.k, layer_cache.v, layer_cache.meta_ids,
              layer_cache.meta_codes, layer_cache.meta_w, pos_b, enc_b)


def attn_decode_pariskv_paged_fused(p: dict, x_t: jax.Array,
                                    pool: C.PagedLayerKVCache,
                                    hist: jax.Array,
                                    block_tables: jax.Array,
                                    regions: C.CacheRegions, spec: AttnSpec,
                                    pcfg: ParisKVConfig, signs: jax.Array,
                                    num_candidates: int
                                    ) -> Tuple[jax.Array,
                                               C.PagedLayerKVCache]:
    """Fused paged ParisKV decode — the default paged path (ISSUE 4).

    Token-identical to ``attn_decode_pariskv_paged`` but **never
    materializes the logical metadata view**: Stage I scores the pool's
    uint8 centroid ids through the block table against tier weights built
    from the incrementally maintained bucket histogram ``hist``
    (b, G, B, 2^m) — cache state, updated at admission/promotion — and
    Stage II gathers only the ≤C candidates' codes/weights by physical
    row. ``hist`` is read-only here (appends don't encode metadata); the
    caller updates it at promotion via ``paged_promote_rows_hist``.
    """
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    with jax.named_scope(phases.ATTEND):
        pool = C.paged_decode_append(pool, block_tables, k_t, v_t, pos)

    q_grp = q.reshape(b, G, H // G, hd)
    qt = E.encode_query(q_grp, pcfg, signs)
    enc_b = jnp.broadcast_to(jnp.asarray(regions.enc_end, jnp.int32), (b,))
    res = R.retrieve_paged_fused(pool, block_tables, qt, hist, enc_b, pcfg,
                                 num_candidates, pcfg.top_k)
    with jax.named_scope(phases.ATTEND):
        k_ret = C.gather_heads_physical(pool.k, res.phys_rows)
        v_ret = C.gather_heads_physical(pool.v, res.phys_rows)
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)
        out = A.sparse_decode_attention_paged(
            q, pool.k, pool.v, block_tables, res.indices, ws, pos,
            regions.enc_end, sink_size=pcfg.sink_size, window_size=W,
            sm_scale=spec.scale(), softcap=spec.softcap,
            k_ret=k_ret, v_ret=v_ret)
    return _decode_out(p, out, x_t.dtype), pool


def attn_decode_pariskv_paged(p: dict, x_t: jax.Array,
                              pool: C.PagedLayerKVCache,
                              block_tables: jax.Array,
                              regions: C.CacheRegions, spec: AttnSpec,
                              pcfg: ParisKVConfig, signs: jax.Array,
                              num_candidates: int
                              ) -> Tuple[jax.Array, C.PagedLayerKVCache]:
    """ParisKV decode over a paged block pool (vLLM-style block tables) —
    the **meta-view fallback** path (``PagedServingEngine(fused=False)``).

    Identical math to ``attn_decode_pariskv`` — the token is appended
    through the block table, two-stage retrieval runs over the logical
    metadata view (candidates come back block-relative), and the three
    attention segments are gathered from the pool — so for the same cache
    contents the outputs are token-identical to the contiguous layout.
    The default paged path is ``attn_decode_pariskv_paged_fused``, which
    skips the per-step ``paged_meta_view`` materialization entirely.
    """
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    with jax.named_scope(phases.ATTEND):
        pool = C.paged_decode_append(pool, block_tables, k_t, v_t, pos)

    bs = C.paged_block_size(pool)
    n_log = block_tables.shape[1] * bs
    q_grp = q.reshape(b, G, H // G, hd)
    with jax.named_scope(phases.STAGE1):
        ids, codes, w = C.paged_meta_view(pool, block_tables)  # (b,G,n_log,B)
        meta = E.KeyMetadata(ids, codes, w)
        valid = C.retrieval_valid_mask(n_log, regions, pcfg)
        if valid.ndim == 1:
            valid = valid[None]
        valid = jnp.broadcast_to(valid[:, None, None, :], (b, G, 1, n_log))
    qt = E.encode_query(q_grp, pcfg, signs)
    meta_b = jax.tree.map(lambda a: a[:, :, None], meta)   # (b, G, 1, n, B)
    res = R.retrieve_paged(meta_b, qt, valid, pcfg, num_candidates,
                           pcfg.top_k, block_tables, bs,
                           hist_sample=pcfg.hist_sample)
    with jax.named_scope(phases.ATTEND):
        k_ret = C.gather_heads_physical(pool.k, res.phys_rows)
        v_ret = C.gather_heads_physical(pool.v, res.phys_rows)
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)
        out = A.sparse_decode_attention_paged(
            q, pool.k, pool.v, block_tables, res.indices, ws, pos,
            regions.enc_end, sink_size=pcfg.sink_size, window_size=W,
            sm_scale=spec.scale(), softcap=spec.softcap,
            k_ret=k_ret, v_ret=v_ret)
    return _decode_out(p, out, x_t.dtype), pool


def attn_decode_pariskv_paged_sharded(p: dict, x_t: jax.Array,
                                      pool: C.PagedLayerKVCache,
                                      hist: jax.Array,
                                      block_tables: jax.Array,
                                      regions: C.CacheRegions,
                                      spec: AttnSpec, pcfg: ParisKVConfig,
                                      signs: jax.Array, num_candidates: int,
                                      axis_name: str, fused: bool = True
                                      ) -> Tuple[jax.Array,
                                                 C.PagedLayerKVCache]:
    """Paged ParisKV decode *inside* ``jax.shard_map`` over a mesh axis
    that partitions KV heads (serve.ShardedPagedDist).

    ``pool``/``hist`` carry this shard's head slice; params, ``x_t`` and
    block tables are replicated. The replicated qkv projection is sliced
    to the local head range (heads are contiguous per shard: query head
    h = g·Hg + j), the append + Stage I + Stage II + sparse attention all
    run shard-local — every ParisKV op is per-head independent, so each
    shard computes exactly its head-slice of the single-device result —
    and the only collective is one tiled ``all_gather`` of the attention
    output heads before the (replicated) output projection. With
    ``fused=False`` Stage I runs over the shard-local metadata view
    instead (the ``fused=False`` engine fallback), same merge."""
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G_loc = pool.k.shape[2]
    Hg = H // G
    g0 = jax.lax.axis_index(axis_name) * G_loc
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    with jax.named_scope(phases.ATTEND):
        k_loc = jax.lax.dynamic_slice_in_dim(k_t, g0, G_loc, axis=1)
        v_loc = jax.lax.dynamic_slice_in_dim(v_t, g0, G_loc, axis=1)
        pool = C.paged_decode_append(pool, block_tables, k_loc, v_loc, pos)

    q_grp = q.reshape(b, G, Hg, hd)
    q_loc = jax.lax.dynamic_slice_in_dim(q_grp, g0, G_loc, axis=1)
    qt = E.encode_query(q_loc, pcfg, signs)
    enc_b = jnp.broadcast_to(jnp.asarray(regions.enc_end, jnp.int32), (b,))
    if fused:
        res = R.retrieve_paged_fused(pool, block_tables, qt, hist, enc_b,
                                     pcfg, num_candidates, pcfg.top_k)
    else:
        bs = C.paged_block_size(pool)
        n_log = block_tables.shape[1] * bs
        with jax.named_scope(phases.STAGE1):
            ids, codes, w = C.paged_meta_view(pool, block_tables)
            meta = E.KeyMetadata(ids, codes, w)
            valid = C.retrieval_valid_mask(n_log, regions, pcfg)
            if valid.ndim == 1:
                valid = valid[None]
            valid = jnp.broadcast_to(valid[:, None, None, :],
                                     (b, G_loc, 1, n_log))
        meta_b = jax.tree.map(lambda a: a[:, :, None], meta)
        res = R.retrieve_paged(meta_b, qt, valid, pcfg, num_candidates,
                               pcfg.top_k, block_tables, bs,
                               hist_sample=pcfg.hist_sample)
    with jax.named_scope(phases.ATTEND):
        k_ret = C.gather_heads_physical(pool.k, res.phys_rows)
        v_ret = C.gather_heads_physical(pool.v, res.phys_rows)
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)
        out = A.sparse_decode_attention_paged(
            q_loc.reshape(b, G_loc * Hg, hd), pool.k, pool.v, block_tables,
            res.indices, ws, pos, regions.enc_end, sink_size=pcfg.sink_size,
            window_size=W, sm_scale=spec.scale(), softcap=spec.softcap,
            k_ret=k_ret, v_ret=v_ret)
        out = jax.lax.all_gather(out, axis_name, axis=1,
                                 tiled=True)                # (b, H, hd)
    return _decode_out(p, out, x_t.dtype), pool


def attn_fill_chunk_sharded(p: dict, x: jax.Array, spec: AttnSpec,
                            q_pos: jax.Array, k_pref: jax.Array,
                            v_pref: jax.Array, pref_pos: jax.Array,
                            new_pos: jax.Array, axis_name: str
                            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``attn_fill_chunk`` inside ``jax.shard_map`` over a KV-head mesh
    axis: the prefix k/v arrive as this shard's head slice (gathered from
    the local pool), the replicated chunk projection is sliced to the
    local heads, chunk attention runs shard-local, and the output heads
    are all-gathered before the output projection. Returns the **local**
    k/v (b, P, G_loc, hd) — the caller writes them (and metadata encoded
    from them) straight into the shard-local pool."""
    b, P, _ = x.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G_loc = k_pref.shape[2]
    Hg = H // G
    g0 = jax.lax.axis_index(axis_name) * G_loc
    q, k, v = _project_qkv(p, x, spec, q_pos)
    q_loc = jax.lax.dynamic_slice_in_dim(
        q.reshape(b, P, G, Hg, hd), g0, G_loc, axis=2
    ).reshape(b, P, G_loc * Hg, hd)
    k_loc = jax.lax.dynamic_slice_in_dim(k, g0, G_loc, axis=2)
    v_loc = jax.lax.dynamic_slice_in_dim(v, g0, G_loc, axis=2)
    out = A.chunk_fill_attention(
        q_loc, k_pref, v_pref, pref_pos, k_loc, v_loc, q_pos, new_pos,
        sm_scale=spec.scale(), softcap=spec.softcap,
        sliding_window=spec.sliding_window)
    out = jax.lax.all_gather(out, axis_name, axis=2, tiled=True)
    return out.reshape(b, P, -1) @ p["wo"], k_loc, v_loc


def attn_decode_pariskv_tiered(p: dict, x_t: jax.Array,
                               pool: C.PagedLayerKVCache, hist: jax.Array,
                               block_tables: jax.Array, dev_map: jax.Array,
                               fetch, rep: jax.Array,
                               regions: C.CacheRegions, spec: AttnSpec,
                               pcfg: ParisKVConfig, signs: jax.Array,
                               num_candidates: int, fused: bool = True
                               ) -> Tuple[jax.Array, C.PagedLayerKVCache,
                                          dict]:
    """ParisKV decode over a **tiered** pool (ISSUE 6): metadata and
    Stage I/II exactly as the paged paths (host block tables), K/V
    through the staging pool.

    The append and the dense sink/window gathers go through the composed
    tables ``tiered_kv_tables(bt, dev_map)`` — those blocks are pinned
    staging-resident by the engine, so they always hit. Stage-II winners
    are resolved against ``dev_map``: resident rows gather from staging,
    misses fetch from the host pool (``rep`` is the stage-repeat index
    selecting the host arrays' leading axis). The hit/miss blend is
    exact — a winner's K/V is bit-identical whichever tier serves it —
    so staging policy and prefetch quality affect bytes moved, never
    tokens.

    ``fetch`` selects the fetch discipline (ISSUE 9): a synchronous
    ``offload.EntryFetch`` blocks on one gather callback; a
    ``offload.PipelinedEntryFetch`` (``pipelined=True``) issues
    ``begin_heads`` immediately after Stage II, runs the staging-hit
    gather plus the dense sink/window gathers while the host worker
    copies, and ``collect``s last. True data deps pin the schedule:
    ``fetch.fence`` folds the ticket into the gather indices and the
    dense outputs ride into ``collect_heads`` as extra operands — the
    data is identical either way, only the schedule moves.

    When a miss fetch exhausts its retry budget (ISSUE 10) the callback
    returns zeroed buffers with ``ok=0`` and this step **degrades**:
    the failed miss rows are masked out of the retrieved segment
    (``ret_keep``) so attention falls back to sink + window + resident
    staged winners — recall is sacrificed for that step, never
    correctness or liveness.

    → (y, pool, fetch-stat increments {"touched": (num_blocks,) winner
    references per host block — the prefetch predictor's signal;
    "rows": (b, 3) [winner rows, staging hits, host fetches];
    "stall": () seconds the step blocked on the host fetch;
    "calls": () host callbacks this step;
    "retries"/"timeouts": () fetch re-issues / deadline expiries;
    "degraded": (b,) 1 per row whose misses were dropped this step}).
    """
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    with jax.named_scope(phases.ATTEND):
        bt_dev = C.tiered_kv_tables(block_tables, dev_map)
        pool = C.paged_decode_append(pool, bt_dev, k_t, v_t, pos)

    bs = C.paged_block_size(pool)
    q_grp = q.reshape(b, G, H // G, hd)
    qt = E.encode_query(q_grp, pcfg, signs)
    enc_b = jnp.broadcast_to(jnp.asarray(regions.enc_end, jnp.int32), (b,))
    if fused:
        res = R.retrieve_paged_fused(pool, block_tables, qt, hist, enc_b,
                                     pcfg, num_candidates, pcfg.top_k)
    else:
        n_log = block_tables.shape[1] * bs
        with jax.named_scope(phases.STAGE1):
            ids, codes, w = C.paged_meta_view(pool, block_tables)
            meta = E.KeyMetadata(ids, codes, w)
            valid = C.retrieval_valid_mask(n_log, regions, pcfg)
            if valid.ndim == 1:
                valid = valid[None]
            valid = jnp.broadcast_to(valid[:, None, None, :],
                                     (b, G, 1, n_log))
        meta_b = jax.tree.map(lambda a: a[:, :, None], meta)
        res = R.retrieve_paged(meta_b, qt, valid, pcfg, num_candidates,
                               pcfg.top_k, block_tables, bs,
                               hist_sample=pcfg.hist_sample)

    with jax.named_scope(phases.ATTEND):
        resident, stag_rows = R.tiered_winner_rows(res.phys_rows, dev_map, bs)
        ret_valid = ((res.indices >= pcfg.sink_size)
                     & (res.indices < enc_b[:, None, None, None]))
        hit = ret_valid & resident
        miss = ret_valid & ~resident
        miss_rows = jnp.where(miss, res.phys_rows, -1).astype(jnp.int32)
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)

        if getattr(fetch, "pipelined", False):
            # ---- overlapped path: begin → dense work → collect ------------
            ticket = fetch.begin_heads(miss_rows, rep)
            # fence: add the ticket-derived 0 to every dense gather's indices
            # (bit-exact) so the gathers truly depend on the begin callback —
            # optimization_barrier does NOT survive into the schedule …
            z = fetch.fence(ticket)
            sink_idx = jnp.broadcast_to(jnp.arange(pcfg.sink_size)[None],
                                        (b, pcfg.sink_size)) + z
            w_idx = ws[:, None] + jnp.arange(W)[None] + z
            k_hit = C.gather_heads_physical(pool.k, stag_rows + z)
            v_hit = C.gather_heads_physical(pool.v, stag_rows + z)
            k_sink = C.paged_gather_rows(pool.k, bt_dev, sink_idx)
            v_sink = C.paged_gather_rows(pool.v, bt_dev, sink_idx)
            k_loc = C.paged_gather_rows(pool.k, bt_dev, w_idx)
            v_loc = C.paged_gather_rows(pool.v, bt_dev, w_idx)
            # the sink/window score einsums only need staging-resident keys,
            # so they run in the overlap window too — same function the
            # attention kernel would call, so the values are bit-identical
            s_sink, s_loc = A.dense_segment_scores(
                q_grp.astype(jnp.float32), k_sink, k_loc)
            # … and the collect takes the dense outputs as extra callback
            # operands, so it schedules after the work hiding the host copy
            k_miss, v_miss, stall, retries, timeouts, f_ok = \
                fetch.collect_heads(ticket, miss_rows.shape,
                                    k_hit, v_hit, v_sink, v_loc, s_sink, s_loc)
            calls = jnp.int32(2)
        else:
            k_hit = C.gather_heads_physical(pool.k, stag_rows)
            v_hit = C.gather_heads_physical(pool.v, stag_rows)
            k_miss, v_miss, stall, retries, timeouts, f_ok = \
                fetch.heads(miss_rows, rep)
            k_sink = v_sink = k_loc = v_loc = s_sink = s_loc = None
            calls = jnp.int32(1)
        sel = resident[..., None]
        k_ret = jnp.where(sel, k_hit, k_miss.astype(k_hit.dtype))
        v_ret = jnp.where(sel, v_hit, v_miss.astype(v_hit.dtype))
        # degraded-mode mask: ok=0 means the miss buffers are
        # zeros — drop those winners from attention instead of mixing in
        # garbage. All-resident steps are unaffected even when ok=0.
        ret_keep = resident | (f_ok > 0)
        degraded = ((miss.sum(axis=(1, 2, 3)) > 0)
                    & (f_ok == 0)).astype(jnp.int32)

        nb = dev_map.shape[0]
        host_blk = res.phys_rows // bs
        touched = jnp.zeros((nb,), jnp.int32).at[
            jnp.where(ret_valid, host_blk, nb)].add(1, mode="drop")
        rows = jnp.stack([ret_valid.sum(axis=(1, 2, 3)).astype(jnp.int32),
                          hit.sum(axis=(1, 2, 3)).astype(jnp.int32),
                          miss.sum(axis=(1, 2, 3)).astype(jnp.int32)], axis=-1)

        out = A.sparse_decode_attention_tiered(
            q, pool.k, pool.v, block_tables, dev_map, res.indices, ws, pos,
            regions.enc_end, sink_size=pcfg.sink_size, window_size=W,
            sm_scale=spec.scale(), softcap=spec.softcap,
            k_ret=k_ret, v_ret=v_ret, k_sink=k_sink, v_sink=v_sink,
            k_loc=k_loc, v_loc=v_loc, s_sink=s_sink, s_loc=s_loc,
            ret_keep=ret_keep)
    y = _decode_out(p, out, x_t.dtype)
    return y, pool, {"touched": touched, "rows": rows,
                     "stall": stall.astype(jnp.float32), "calls": calls,
                     "retries": retries, "timeouts": timeouts,
                     "degraded": degraded}


def attn_decode_pariskv(p: dict, x_t: jax.Array, layer_cache: C.LayerKVCache,
                        regions: C.CacheRegions, spec: AttnSpec,
                        pcfg: ParisKVConfig, signs: jax.Array,
                        num_candidates: int, dist=None
                        ) -> Tuple[jax.Array, C.LayerKVCache]:
    """ParisKV decode path (paper Fig. 2 B.1→B.3) for one global layer.

    Appends the token, runs two-stage retrieval over the Retrieval region,
    attends over Sink ∪ Top-k ∪ Local/Buffer, and (caller-side) the promote
    step refreshes metadata every update_interval steps.
    """
    b, _ = x_t.shape
    H, G, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q, k_t, v_t = _decode_qkv(p, x_t, spec, pos)
    with jax.named_scope(phases.ATTEND):
        layer_cache = C.decode_append(layer_cache, k_t, v_t, pos)

    n_max = layer_cache.k.shape[1]
    q_grp = q.reshape(b, G, H // G, hd)
    k_ret = v_ret = None
    if dist is not None:
        # context-parallel hierarchical retrieval (DESIGN.md §8 #1)
        mesh, seq_axes, batch_axes = dist
        top_idx, k_ret, v_ret = distributed_retrieve_fetch(
            q_grp, layer_cache, regions, pcfg, signs, mesh, seq_axes,
            batch_axes)
    else:
        meta = E.KeyMetadata(layer_cache.meta_ids, layer_cache.meta_codes,
                             layer_cache.meta_w)
        with jax.named_scope(phases.STAGE1):
            valid = C.retrieval_valid_mask(n_max, regions, pcfg)
            if valid.ndim == 1:                   # scalar-region call site
                valid = valid[None]
            valid = jnp.broadcast_to(valid[:, None, None, :],
                                     (b, G, 1, n_max))
        qt = E.encode_query(q_grp, pcfg, signs)
        meta_b = jax.tree.map(lambda a: a[:, :, None], meta)  # (b,G,1,n,B)
        res = R.retrieve(meta_b, qt, valid, pcfg, num_candidates, pcfg.top_k,
                         hist_sample=pcfg.hist_sample)
        top_idx = res.indices

    with jax.named_scope(phases.ATTEND):
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)
        out = A.sparse_decode_attention(
            q, layer_cache.k, layer_cache.v, top_idx, ws, pos,
            regions.enc_end, sink_size=pcfg.sink_size, window_size=W,
            sm_scale=spec.scale(), softcap=spec.softcap,
            k_ret=k_ret, v_ret=v_ret)
    return _decode_out(p, out, x_t.dtype), layer_cache
