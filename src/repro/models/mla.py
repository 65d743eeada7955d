"""Multi-head Latent Attention (DeepSeek-V2), with ParisKV in latent space.

Train/prefill use the *decompressed* form (materialize per-head K/V).
Decode and the chunked fill use the *absorbed* form: the cache holds only
the latent ``c_kv ∈ R^{r}`` plus the shared decoupled-rope key ``k_r ∈
R^{dr}`` per token (r=512, dr=64 for v2-lite), one row of r + dr per
token, read as both key and value: a ``core.cache`` latent cache
(``LayerKVCache`` or, paged, ``PagedLayerKVCache`` with ``v`` None and
G = 1). Scores become

    s(i) = (q_nope W_UK) · c_kv[i] + q_rope · k_r[i]

so the *retrieval vector* is the concatenation [c_kv; k_r] ∈ R^{576} and
the *query vector* is [q_eff; q_rope]; the attended latent's c_kv part
decompresses through W_UV. ParisKV indexes ONE latent cache shared by all
heads (G = 1, Hg = H; a beyond-paper adaptation: the paper's per-head
scheme would decompress; indexing the latent keeps metadata H× smaller
and the estimator still targets the exact pre-softmax score).

Rope is YaRN where the config sets ``rope_scaling`` (v2-lite: factor 40
over 4096 positions), on the dr rope dims of q and k_r, with the softmax
scale ``(dn + dr)^-0.5 · mscale(s, mscale_all_dim)²`` (``softmax_scale``).
The published code's interleaved-to-half rope permutation is a fixed
permutation of the rope columns of ``wq`` and ``w_dkv``, which random
weights absorb.

Decode scopes (core.phases): the latent down-projection, the W_UK absorb
and the W_UV decompress run under ``mla.latent`` inside ``model.dense``;
the latent append and the attention under ``pariskv.attend``; retrieval
under its own phases.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as A
from repro.core import cache as C
from repro.core import encode as E
from repro.core import phases
from repro.core import retrieval as R
from repro.core.config import ModelConfig
from repro.models.layers import rms_norm, rope, truncated_normal, yarn_mscale


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dv = cfg.head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        # queries: direct projection (v2-lite has no q-lora)
        "wq": truncated_normal(ks[0], (d, H * (dn + dr))).astype(dtype),
        # kv down-projection to latent + shared rope key
        "w_dkv": truncated_normal(ks[1], (d, r + dr)).astype(dtype),
        "kv_norm": jnp.ones((r,), dtype),
        # up-projections
        "w_uk": truncated_normal(ks[2], (r, H * dn)).astype(dtype),
        "w_uv": truncated_normal(ks[3], (r, H * dv)).astype(dtype),
        "wo": truncated_normal(ks[4], (H * dv, d)).astype(dtype),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-0.5``, times ``mscale(s, mscale_all_dim)²`` under YaRN."""
    s = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        s *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return s


def _rope(x, positions, cfg: ModelConfig):
    return rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def _split_q(p, x, cfg: ModelConfig, positions):
    """x (b, s, d) → (q_nope (b,s,H,dn), rope'd q_rope (b,s,H,dr))."""
    b, s, _ = x.shape
    H, dn, dr = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim
    q = (x @ p["wq"]).reshape(b, s, H, dn + dr)
    return q[..., :dn], _rope(q[..., dn:], positions, cfg)


def latent(p, x, cfg: ModelConfig, positions) -> jax.Array:
    """x (b, s, d) → the cached rows [c_kv normalized; k_rope rope'd]
    (b, s, r + dr)."""
    r = cfg.kv_lora_rank
    ckv = x @ p["w_dkv"]
    c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_r = _rope(ckv[..., r:][..., None, :], positions, cfg)[..., 0, :]
    return jnp.concatenate([c, k_r.astype(c.dtype)], -1)


def _absorb(p, q_n, cfg: ModelConfig):
    """q_nope (..., H, dn) → q_nope W_UK^T per head (..., H, r), float32."""
    w_uk = p["w_uk"].reshape(cfg.kv_lora_rank, cfg.num_heads, cfg.head_dim)
    return jnp.einsum("...hd,rhd->...hr", q_n.astype(jnp.float32),
                      w_uk.astype(jnp.float32))


def _decompress(p, att, cfg: ModelConfig):
    """Attended latents (..., H, r + dr) → per-head values (..., H, dv)
    through W_UV (only the c_kv part carries values), float32."""
    r = cfg.kv_lora_rank
    w_uv = p["w_uv"].reshape(r, cfg.num_heads, cfg.v_head_dim)
    return jnp.einsum("...hr,rhv->...hv", att[..., :r].astype(jnp.float32),
                      w_uv.astype(jnp.float32))


def mla_prefill(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Decompressed causal attention over a whole prompt → (y, the
    prompt's latent rows (b, s, r + dr) for the cache)."""
    b, s, _ = x.shape
    H, dn, dr, dv = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_n, q_r = _split_q(p, x, cfg, positions)
    lat = latent(p, x, cfg, positions)
    c, k_r = lat[..., :r], lat[..., r:]
    k_n = (c @ p["w_uk"]).reshape(b, s, H, dn)
    v = (c @ p["w_uv"]).reshape(b, s, H, dv)
    q = jnp.concatenate([q_n, q_r], -1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, :, None], (b, s, H, dr))], -1)
    out = A.blockwise_causal_attention(
        q, k, v, sm_scale=softmax_scale(cfg), q_chunk=min(1024, s),
        kv_chunk=min(2048, s))
    return out.reshape(b, s, H * dv) @ p["wo"], lat


def mla_train(p: dict, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array) -> jax.Array:
    """Decompressed causal attention for train/prefill."""
    return mla_prefill(p, x, cfg, positions)[0]


# ------------------------------------------------------------- decode -------
def _decode_in(p, x_t, cfg: ModelConfig, pos):
    """One decoded token per row, x_t (b, d) at positions ``pos`` (b,) →
    (latent-space query [q_eff; q_rope] (b, H, r + dr) float32, the
    token's latent row (b, r + dr))."""
    with jax.named_scope(phases.DENSE):
        q_n, q_r = _split_q(p, x_t[:, None], cfg, pos[:, None])
        with jax.named_scope(phases.MLA_LATENT):
            q_eff = _absorb(p, q_n[:, 0], cfg)
            lat_t = latent(p, x_t[:, None], cfg, pos[:, None])[:, 0]
        q_lat = jnp.concatenate([q_eff, q_r[:, 0].astype(jnp.float32)], -1)
    return q_lat, lat_t


def _decode_out(p, att, cfg: ModelConfig, dtype):
    """Attended latents (b, H, r + dr) → the layer's output (b, d)."""
    with jax.named_scope(phases.DENSE):
        with jax.named_scope(phases.MLA_LATENT):
            out = _decompress(p, att, cfg)
        return out.reshape(out.shape[0], -1).astype(dtype) @ p["wo"]


def mla_decode(p: dict, x_t: jax.Array, kv: C.LayerKVCache,
               regions: C.CacheRegions, cfg: ModelConfig, signs: jax.Array,
               num_candidates: int, use_pariskv: bool = True
               ) -> Tuple[jax.Array, C.LayerKVCache]:
    """Absorbed-form decode over a contiguous latent cache with
    latent-space ParisKV retrieval (``use_pariskv=False``: dense).

    ``regions`` fields are per-row (b,) int32 (scalars broadcast)."""
    b, _ = x_t.shape
    pcfg = cfg.pariskv
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q_lat, lat_t = _decode_in(p, x_t, cfg, pos)
    with jax.named_scope(phases.ATTEND):
        kv = C.decode_append(kv, lat_t[:, None], None, pos)
    n_max = kv.k.shape[1]
    sm = softmax_scale(cfg)

    if use_pariskv:
        meta = E.KeyMetadata(kv.meta_ids, kv.meta_codes, kv.meta_w)
        valid = C.retrieval_valid_mask(n_max, regions, pcfg)
        if valid.ndim == 1:                       # scalar-region call site
            valid = valid[None]
        valid = jnp.broadcast_to(valid[:, None, None, :], (b, 1, 1, n_max))
        qt = E.encode_query(q_lat[:, None], pcfg, signs)     # group dim = 1
        meta_b = jax.tree.map(lambda a: a[:, :, None], meta)
        res = R.retrieve(meta_b, qt, valid, pcfg, num_candidates,
                         pcfg.top_k, hist_sample=pcfg.hist_sample)
        with jax.named_scope(phases.ATTEND):
            W = C.window_size(pcfg)
            ws = jnp.maximum(pos + 1 - W, 0)
            att = A.sparse_decode_attention(
                q_lat, kv.k, kv.k, res.indices, ws, pos, regions.enc_end,
                sink_size=pcfg.sink_size, window_size=W, sm_scale=sm)
    else:
        with jax.named_scope(phases.ATTEND):
            att = A.dense_decode_attention(q_lat, kv.k, kv.k, pos,
                                           sm_scale=sm)
    return _decode_out(p, att, cfg, x_t.dtype), kv


def mla_decode_paged(p: dict, x_t: jax.Array, pool: C.PagedLayerKVCache,
                     hist: jax.Array, block_tables: jax.Array,
                     regions: C.CacheRegions, cfg: ModelConfig,
                     signs: jax.Array, num_candidates: int
                     ) -> Tuple[jax.Array, C.PagedLayerKVCache]:
    """Absorbed-form decode over a paged latent pool, on the fused path
    of ``layers.attn_decode_pariskv_paged_fused``: the latent row is
    appended through the block table, Stage I scores the pool's ids
    against tier weights from the slot's incremental histogram ``hist``
    (b, 1, B, 2^m), Stage II reranks the candidates, and the winners,
    sink and window are attended with the latent as K and V. ``hist`` is
    read-only here; the caller advances it at promotion."""
    b, _ = x_t.shape
    pcfg = cfg.pariskv
    pos = jnp.broadcast_to(jnp.asarray(regions.pos, jnp.int32), (b,)) + 1
    q_lat, lat_t = _decode_in(p, x_t, cfg, pos)
    with jax.named_scope(phases.ATTEND):
        pool = C.paged_decode_append(pool, block_tables, lat_t[:, None], None,
                                     pos)
    qt = E.encode_query(q_lat[:, None], pcfg, signs)         # group dim = 1
    enc_b = jnp.broadcast_to(jnp.asarray(regions.enc_end, jnp.int32), (b,))
    res = R.retrieve_paged_fused(pool, block_tables, qt, hist, enc_b, pcfg,
                                 num_candidates, pcfg.top_k)
    with jax.named_scope(phases.ATTEND):
        k_ret = C.gather_heads_physical(pool.k, res.phys_rows)
        W = C.window_size(pcfg)
        ws = jnp.maximum(pos + 1 - W, 0)
        att = A.sparse_decode_attention_paged(
            q_lat, pool.k, pool.k, block_tables, res.indices, ws, pos,
            regions.enc_end, sink_size=pcfg.sink_size, window_size=W,
            sm_scale=softmax_scale(cfg), k_ret=k_ret, v_ret=k_ret)
    return _decode_out(p, att, cfg, x_t.dtype), pool


# ------------------------------------------------------- chunked fill -------
def mla_fill_chunk(p: dict, x: jax.Array, cfg: ModelConfig,
                   q_pos: jax.Array, new_pos: jax.Array, start: jax.Array,
                   read: Callable[[jax.Array], jax.Array], kb: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """One prefill chunk of one slot in absorbed form: the chunk's P
    queries attend chunk-causally to the slot's cached latent prefix
    [0, start) and to the chunk itself, the prefix never decompressed.

    x (1, P, d) at positions ``q_pos`` (1, P); ``new_pos`` (1, P) is -1 at
    the pad tail. ``read(j)`` returns the slot's cached latent rows
    [j·kb, (j+1)·kb) as (kb, r + dr): the prefix is read in ceil(start /
    kb) blocks under an online softmax, so the scores held at once are
    H·P·kb whatever the context. → (y (1, P, d), the chunk's latent rows
    (1, P, r + dr) for the cache)."""
    H, r, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.v_head_dim
    P = x.shape[1]
    sm = softmax_scale(cfg)
    q_n, q_r = _split_q(p, x, cfg, q_pos)
    with jax.named_scope(phases.MLA_LATENT):
        lat = latent(p, x, cfg, q_pos)
        q = jnp.concatenate([_absorb(p, q_n[0], cfg),
                             q_r[0].astype(jnp.float32)], -1)  # (P, H, r+dr)
    qp = q_pos[0]

    def scores(k, kpos):                 # k (n, r+dr), kpos (n,) → (H, P, n)
        s = jnp.einsum("phc,nc->hpn", q, k.astype(jnp.float32)) * sm
        ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qp[:, None])
        return jnp.where(ok[None], s, A.NEG_INF)

    def values(pr, k):                   # (H, P, n), (n, r+dr) → (P, H, r)
        return jnp.einsum("hpn,nr->phr", pr, k[:, :r].astype(jnp.float32))

    s = scores(lat[0], new_pos[0])
    m = s.max(-1)
    pr = jnp.exp(s - m[..., None])
    carry = (m, pr.sum(-1), values(pr, lat[0]))

    def block(j, carry):
        m, l, acc = carry
        k = read(j)
        kpos = j * kb + jnp.arange(kb)
        s = scores(k, jnp.where(kpos < start, kpos, -1))
        m_new = jnp.maximum(m, s.max(-1))
        a = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None])
        return (m_new, l * a + pr.sum(-1),
                acc * a.T[..., None] + values(pr, k))

    _, l, acc = jax.lax.fori_loop(0, (start + kb - 1) // kb, block, carry)
    att = acc / l.T[..., None]                               # (P, H, r)
    with jax.named_scope(phases.MLA_LATENT):
        out = _decompress(p, att, cfg)                       # (P, H, dv)
    y = out.reshape(1, P, H * dv).astype(x.dtype) @ p["wo"]
    return y, lat
