"""Serving-side forward passes: cache construction, prefill, decode step.

The decode cache mirrors the stage structure of models.model: one pytree per
stage, stacked over the stage's repeats, with a per-LayerDef cache kind:

  attn + ParisKV      → core.cache.LayerKVCache (full store + metadata)
  attn sliding-window → (k, v) ring buffers of the window size
  cross (vlm/whisper) → (k_media, v_media), static after prefill
  mla                 → core.cache.LayerKVCache with v None: one latent
                        row per token (G = 1), key and value alike
  ssm                 → models.ssm.SSMCache (O(1) recurrent state)
  hybrid              → {"kv": LayerKVCache, "ssm": SSMCache}

All layers share one CacheRegions whose ``pos``/``enc_end`` are **per-row
(b,) vectors**: each sequence in the batch advances independently
(continuous batching admits requests into cache slots mid-flight, so rows
are never in lockstep). The sliding-window metadata promotion triggers
per row; the block encode runs under a single "any row triggered" lax.cond
and is applied only to triggered rows (amortized update, §4.2.1/D.2).

Prompts are LEFT-aligned: ``prefill(..., lengths=)`` accepts per-row true
prompt lengths, gathers last-token logits per row, and sets per-row
regions; pad positions beyond a row's length are never attended and are
overwritten as the row decodes. ``decode_chunk`` scans ``decode_step`` N
steps on-device (argmax sampling + per-slot active mask) so a serving host
syncs once per chunk instead of once per token.

With ``prefill_budget > 0`` the chunk scans **mixed prefill+decode
steps** (ISSUE 5): prompts are not prefilled solo at admission but copied
to a device buffer (``SlotState.prompt``) and consumed ``prefill_budget``
tokens per step for the at-most-one *filling* slot
(``fill_pos < fill_len``), chunk-causally attending to the already-written
prefix, inside the same stage scans as the decode rows — ending prefill
head-of-line blocking. The first token is emitted the step a fill
completes; the solo path survives as ``prefill_budget=0`` and is
token-identical (tests/test_chunked_prefill.py).

Chunked prefill is also the substrate for **block-granular prefix
sharing** (ISSUE 7): ``admit_fill(fill_start=...)`` starts a fill past a
prefix the serving engine mapped from its pool-level prefix index,
deriving the slot's histograms from the shared blocks' metadata
(``bucket_hist_from_paged_meta``). ``share_support_reason`` gates the
feature to architectures whose every layer is ParisKV attention or MLA —
sliding-window layers keep slot-local ring buffers a mapped prefix
cannot populate.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cache as CC
from repro.core import phases
from repro.core import srht
from repro.core.config import ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.model import (LayerDef, _dtype, _embed, _unembed,
    encoder_fwd, layer_plan)


class ServeState(NamedTuple):
    caches: Any              # list of per-stage stacked cache pytrees
    regions: CC.CacheRegions


def rotation_signs(cfg: ModelConfig) -> jax.Array:
    pcfg = cfg.pariskv
    return jnp.asarray(srht.rademacher_signs(
        pcfg.padded_dim(cfg.retrieval_dim()), pcfg.srht_seed))


def _ring_len(ld: LayerDef, n_max: int) -> int:
    return min(ld.attn.sliding_window, n_max)


def _layer_cache_spec(cfg: ModelConfig, ld: LayerDef, batch: int, n_max: int,
                      as_spec: bool) -> Any:
    dt = _dtype(cfg)
    pcfg = cfg.pariskv
    mk = jax.ShapeDtypeStruct if as_spec else (
        lambda shape, dtype: jnp.zeros(shape, dtype))

    def kv_cache():
        if as_spec:
            return CC.cache_spec(batch, n_max, cfg.num_kv_heads, cfg.head_dim,
                                 pcfg, dt)
        return CC.init_layer_cache(batch, n_max, cfg.num_kv_heads,
                                   cfg.head_dim, pcfg, dt)

    out: Dict[str, Any] = {}
    if ld.mixer == "attn":
        if ld.use_pariskv:
            out["kv"] = kv_cache()
        else:
            w = _ring_len(ld, n_max)
            g, hd = cfg.num_kv_heads, cfg.head_dim
            out["kv"] = (mk((batch, w, g, hd), dt), mk((batch, w, g, hd), dt))
    elif ld.mixer == "cross":
        t = cfg.num_media_tokens
        g, hd = cfg.num_kv_heads, cfg.head_dim
        out["media_kv"] = (mk((batch, t, g, hd), dt), mk((batch, t, g, hd), dt))
    elif ld.mixer == "mla":
        make = CC.cache_spec if as_spec else CC.init_layer_cache
        out["kv"] = make(batch, n_max, 1, cfg.retrieval_dim(), pcfg, dt,
                         values=False)
    elif ld.mixer == "ssm":
        out["ssm"] = (SSM.ssm_cache_spec(batch, cfg, dt) if as_spec
                      else SSM.init_ssm_cache(batch, cfg, dt))
    elif ld.mixer == "hybrid":
        out["kv"] = kv_cache()
        out["ssm"] = (SSM.ssm_cache_spec(batch, cfg, dt) if as_spec
                      else SSM.init_ssm_cache(batch, cfg, dt))
    if ld.cross:  # whisper decoder cross-attn over encoder output
        t = cfg.encoder_seq
        g, hd = cfg.num_kv_heads, cfg.head_dim
        out["media_kv"] = (mk((batch, t, g, hd), dt), mk((batch, t, g, hd), dt))
    return out


def _stack_spec(tree, repeat: int, as_spec: bool):
    if as_spec:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((repeat,) + s.shape, s.dtype), tree)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (repeat,) + a.shape), tree)


def make_caches(cfg: ModelConfig, batch: int, n_max: int,
                as_spec: bool = False):
    """Build (or spec) the full decode cache for every stage."""
    caches = []
    for stage in layer_plan(cfg):
        stage_cache = {
            f"l{i}": _stack_spec(
                _layer_cache_spec(cfg, ld, batch, n_max, as_spec),
                stage.repeat, as_spec)
            for i, ld in enumerate(stage.layers)}
        caches.append(stage_cache)
    return caches


class UnsupportedPagedConfig(NotImplementedError):
    """A config whose cache structure the paged/offloaded pool cannot
    serve. Carries the config name and the offending (stage, layer,
    mixer) so callers and logs can point at the exact config key rather
    than a bare "not implemented"."""

    def __init__(self, cfg: ModelConfig, stage: int, layer: int,
                 mixer: str, hint: str):
        self.config_name = getattr(cfg, "name", cfg.family)
        self.stage = stage
        self.layer = layer
        self.mixer = mixer
        super().__init__(
            f"config {self.config_name!r}: stage {stage} layer {layer} "
            f"uses mixer={mixer!r}, which the paged block pool does not "
            f"serve — {hint}")


class UnsupportedShardedConfig(NotImplementedError):
    """A config/feature combination the sharded (mesh) serving path cannot
    run — the structured twin of ``UnsupportedPagedConfig``. Carries the
    config name and the offending feature so callers and logs can point
    at the exact conflict instead of silently falling back to a
    single-device engine."""

    def __init__(self, cfg: ModelConfig, feature: str, hint: str):
        self.config_name = getattr(cfg, "name", cfg.family)
        self.feature = feature
        super().__init__(
            f"config {self.config_name!r}: {feature} cannot run on a "
            f"sharded device mesh — {hint}")


class ShardedPagedDist(NamedTuple):
    """Marker threaded as ``dist=`` through the **paged** decode/fill path
    when the call runs *inside* ``jax.shard_map`` over a 1-D mesh whose
    axis partitions KV heads (ISSUE 8).

    The shard_map in_specs (``sharded_state_specs``) deliver each shard
    its head slice of every pool/metadata/histogram leaf; params, block
    tables, prompts and per-slot scalars are replicated, and block/
    physical-row numbering is replicated too (only heads shard), so
    shard-local retrieval returns globally valid rows. Layer functions
    slice their replicated qkv projections to the local head range, run
    retrieval + attention shard-local, and all-gather only the attention
    output heads (layers.attn_decode_pariskv_paged_sharded /
    attn_fill_chunk_sharded). Contiguous caches keep the original tuple
    ``dist=(mesh, seq_axes, batch_axes)`` — the two forms never mix."""
    axis_name: str
    num_shards: int


def make_paged_caches(cfg: ModelConfig, batch: int, num_blocks: int,
                      block_size: int, n_max: int, as_spec: bool = False,
                      num_device_blocks: Optional[int] = None):
    """Build the decode cache with ParisKV KV stores replaced by a shared
    block pool (one PagedLayerKVCache per attn/hybrid-attn layer, stacked
    over the stage repeat). Bounded-size state — sliding-window ring
    buffers, SSM recurrent state, media K/V — stays slot-local (batch,
    ...) because it neither fragments nor grows with context. An MLA
    layer pages its latent: a pool with one (num_blocks, block_size, 1,
    r + dr) latent leaf and no ``v`` (the latent is key and value), its
    metadata and ``hist`` at G = 1 over the latent's subspaces. The
    tiered pool (``num_device_blocks``) does not take it
    (``offload_support_reason``).

    Every paged ParisKV layer also carries ``hist``: the slot-local
    (batch, G, B, 2^m) int32 incremental bucket histogram the fused
    retrieval path reads instead of recomputing an O(n) scatter-add per
    step (batch · G · B · 2^m · 4 bytes per layer of extra state). It is
    maintained even when the engine falls back to the meta-view path, so
    the flag can toggle freely.

    ``num_device_blocks`` (ISSUE 6) switches the pool to the **tiered**
    layout: metadata leaves keep all ``num_blocks`` blocks on device but
    the K/V leaves shrink to a ``num_device_blocks``-block staging pool
    (the full K/V pool lives host-side, serving.offload.HostKVPool).
    Tiered ParisKV layers additionally carry ``fetch`` stats leaves —
    ``touched`` (num_blocks,) winner references per host block (the
    prefetch predictor's input), ``rows`` (batch, 4) int32
    [winner rows, staging hits, host fetches, fill-prefix fetches],
    ``stall`` () float32 seconds the jitted step spent blocked on host
    fetch callbacks, and ``calls`` () int32 host callbacks issued
    (ISSUE 9 observability) — zeroed at each decode_chunk entry and
    read back by the engine."""
    pcfg = cfg.pariskv
    dt = _dtype(cfg)

    def paged_kv(ld):
        if ld.mixer == "mla":
            make = CC.paged_cache_spec if as_spec else CC.init_paged_cache
            return make(num_blocks, block_size, 1, cfg.retrieval_dim(), pcfg,
                        dt, values=False)
        if num_device_blocks is not None:
            if as_spec:
                return CC.tiered_cache_spec(num_blocks, num_device_blocks,
                                            block_size, cfg.num_kv_heads,
                                            cfg.head_dim, pcfg, dt)
            return CC.init_tiered_cache(num_blocks, num_device_blocks,
                                        block_size, cfg.num_kv_heads,
                                        cfg.head_dim, pcfg, dt)
        if as_spec:
            return CC.paged_cache_spec(num_blocks, block_size,
                                       cfg.num_kv_heads, cfg.head_dim,
                                       pcfg, dt)
        return CC.init_paged_cache(num_blocks, block_size, cfg.num_kv_heads,
                                   cfg.head_dim, pcfg, dt)

    def hist(ld):
        g, hd = ((1, cfg.retrieval_dim()) if ld.mixer == "mla"
                 else (cfg.num_kv_heads, cfg.head_dim))
        shape = (batch, g, pcfg.num_subspaces(hd), pcfg.num_centroids())
        if as_spec:
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        return jnp.zeros(shape, jnp.int32)

    def fetch_stats():
        shapes = {"touched": ((num_blocks,), jnp.int32),
                  "rows": ((batch, 4), jnp.int32),
                  "stall": ((), jnp.float32),
                  "calls": ((), jnp.int32),
                  "retries": ((), jnp.int32),
                  "timeouts": ((), jnp.int32),
                  "degraded": ((batch,), jnp.int32)}
        if as_spec:
            return {k: jax.ShapeDtypeStruct(s, d)
                    for k, (s, d) in shapes.items()}
        return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}

    caches = []
    for si, stage in enumerate(layer_plan(cfg)):
        stage_cache = {}
        for i, ld in enumerate(stage.layers):
            if ld.mixer == "mla" and num_device_blocks is not None:
                raise UnsupportedPagedConfig(
                    cfg, si, i, ld.mixer, offload_support_reason(cfg))
            entry = _layer_cache_spec(cfg, ld, batch, n_max, as_spec)
            if ld.mixer in ("attn", "hybrid", "mla") and ld.use_pariskv:
                entry = {**entry, "kv": paged_kv(ld), "hist": hist(ld)}
                if num_device_blocks is not None:
                    entry["fetch"] = fetch_stats()
            stage_cache[f"l{i}"] = _stack_spec(entry, stage.repeat, as_spec)
        caches.append(stage_cache)
    return caches


def regions_spec(batch: int, as_spec: bool = False) -> CC.CacheRegions:
    if as_spec:
        s = jax.ShapeDtypeStruct((batch,), jnp.int32)
        return CC.CacheRegions(pos=s, enc_end=s)
    return CC.CacheRegions(pos=jnp.full((batch,), -1, jnp.int32),
                           enc_end=jnp.zeros((batch,), jnp.int32))


# ------------------------------------------------------------- prefill -----
def _ring_prefill(kv, k_new, v_new, lengths):
    """Fill a ring-buffer cache from a LEFT-aligned padded prompt.

    Ring layout: position t sits at slot t % w. Per row, slot j must hold
    the *latest real* position p < lengths[i] with p ≡ j (mod w); slots
    with no such position stay zero (masked at decode by pos-bounded
    validity). Gather-based so rows with different lengths vectorize.
    """
    w = kv[0].shape[1]
    b, S = k_new.shape[:2]
    if lengths is None:
        lengths = jnp.full((b,), S, jnp.int32)
    j = jnp.arange(w)[None]                                  # (1, w)
    last = (lengths - 1)[:, None]                            # (b, 1)
    p_src = last - (last - j) % w                            # (b, w)
    ok = p_src >= 0
    src = jnp.clip(p_src, 0, S - 1)[..., None, None]
    kc = jnp.where(ok[..., None, None],
                   jnp.take_along_axis(k_new, src, axis=1), 0)
    vc = jnp.where(ok[..., None, None],
                   jnp.take_along_axis(v_new, src, axis=1), 0)
    return kc.astype(kv[0].dtype), vc.astype(kv[1].dtype)


def _layer_prefill(p, x, ld: LayerDef, cfg: ModelConfig, positions, media,
                   cache, signs, lengths=None, token_valid=None):
    """Layer forward over the full prompt; fills this layer's cache.

    ``lengths`` (b,) / ``token_valid`` (b, S) describe LEFT-aligned per-row
    prompt lengths (None → every row uses the full padded length). Causal
    attention already hides a row's pad tail from its real tokens; SSM
    state scans have no such masking, so the pad steps are skipped exactly
    inside ssm_prefill (dt = 0 there).
    """
    pcfg = cfg.pariskv
    h = L.rms_norm(x, p["norm_attn"], cfg.norm_eps)
    if ld.mixer == "attn":
        y, k_new, v_new = L.attn_prefill(p["attn"], h, ld.attn, positions)
        if ld.use_pariskv:
            kvc, _ = CC.prefill_write(cache["kv"], k_new, v_new, pcfg, signs,
                                      lengths=lengths)
            cache = {**cache, "kv": kvc}
        else:
            cache = {**cache,
                     "kv": _ring_prefill(cache["kv"], k_new, v_new, lengths)}
    elif ld.mixer == "mla":
        y, lat = MLA.mla_prefill(p["attn"], h, cfg, positions)
        kvc, _ = CC.prefill_write(cache["kv"], lat[:, :, None], None, pcfg,
                                  signs, lengths=lengths)
        cache = {**cache, "kv": kvc}
    elif ld.mixer == "cross":
        y = jnp.tanh(p["cross_gate"]) * L.attn_cross(p["attn"], h, media, ld.attn)
        g, hd = cfg.num_kv_heads, cfg.head_dim
        b, t = media.shape[0], media.shape[1]
        km = (media @ p["attn"]["wk"]).reshape(b, t, g, hd)
        vm = (media @ p["attn"]["wv"]).reshape(b, t, g, hd)
        cache = {**cache, "media_kv": (km.astype(_dtype(cfg)),
                                       vm.astype(_dtype(cfg)))}
    elif ld.mixer == "ssm":
        y, sc = SSM.ssm_prefill(p["ssm"], h, cfg, token_valid=token_valid,
                                lengths=lengths)
        cache = {**cache, "ssm": sc}
    elif ld.mixer == "hybrid":
        ya, k_new, v_new = L.attn_prefill(p["attn"], h, ld.attn, positions)
        ys, sc = SSM.ssm_prefill(p["ssm"], h, cfg, token_valid=token_valid,
                                 lengths=lengths)
        kvc, _ = CC.prefill_write(cache["kv"], k_new, v_new, pcfg, signs,
                                  lengths=lengths)
        y = 0.5 * (ya + ys)
        cache = {**cache, "kv": kvc, "ssm": sc}
    x = x + y.astype(x.dtype)
    if ld.cross:
        h = L.rms_norm(x, p["norm_cross"], cfg.norm_eps)
        x = x + L.attn_cross(p["cross"], h, media, ld.attn).astype(x.dtype)
        g, hd = cfg.num_kv_heads, cfg.head_dim
        b, t = media.shape[0], media.shape[1]
        km = (media @ p["cross"]["wk"]).reshape(b, t, g, hd)
        vm = (media @ p["cross"]["wv"]).reshape(b, t, g, hd)
        cache = {**cache, "media_kv": (km.astype(_dtype(cfg)),
                                       vm.astype(_dtype(cfg)))}
    if ld.ffn != "none":
        h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        if ld.ffn == "moe":
            y, _ = MOE.moe_fwd(p["moe"], h, cfg.experts_per_token,
                               cfg.norm_topk_prob)
        else:
            y = L.mlp_fwd(p["mlp"], h)
        x = x + y.astype(x.dtype)
    return x, cache


def prefill(params, cfg: ModelConfig, tokens: jax.Array, n_max: int,
            media: Optional[jax.Array] = None,
            lengths: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, ServeState]:
    """Process the prompt; returns last-position logits + populated caches.

    ``lengths`` (b,) int32: true prompt length per row for LEFT-aligned
    padded batches (None → every row spans the full S). Logits are gathered
    at each row's last real token and regions are per-row.
    """
    b, S = tokens.shape
    signs = rotation_signs(cfg)
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (b, S))
    lens_b = None
    token_valid = None
    if lengths is not None:
        lens_b = jnp.asarray(lengths, jnp.int32)
        token_valid = jnp.arange(S)[None] < lens_b[:, None]
    if cfg.family == "audio":
        media = encoder_fwd(params, cfg, media)
    caches = make_caches(cfg, b, n_max)
    new_caches = []
    for stage, sp, sc in zip(layer_plan(cfg), params["stages"], caches):

        def body(x, slices):
            p_slice, c_slice = slices
            new_c = {}
            for i, ld in enumerate(stage.layers):
                x, new_c[f"l{i}"] = _layer_prefill(
                    p_slice[f"l{i}"], x, ld, cfg, positions, media,
                    c_slice[f"l{i}"], signs, lengths=lens_b,
                    token_valid=token_valid)
            return x, new_c

        x, filled = jax.lax.scan(body, x, (sp, sc))
        new_caches.append(filled)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lens_b is None:
        x_last = x[:, -1]
        lens_b = jnp.full((b,), S, jnp.int32)
    else:
        x_last = jnp.take_along_axis(
            x, (lens_b - 1)[:, None, None], axis=1)[:, 0]
    logits = _unembed(params, cfg, x_last)
    return logits, ServeState(new_caches,
                              CC.initial_regions(lens_b, cfg.pariskv))


# --------------------------------------------------------------- decode ----
def _layer_decode(p, x_t, ld: LayerDef, cfg: ModelConfig, cache, regions,
                  signs, num_candidates: int, will_promote, media=None,
                  dist=None, block_tables=None, paged_fused: bool = True,
                  dev_map=None, fetch=None, rep=None):
    """One layer of one decode step.

    ``regions`` fields and ``will_promote`` are per-row (b,) vectors: each
    row promotes its own block when *its* window fills; the block encode is
    guarded by a single any-row lax.cond so quiet steps stay cheap.
    ``block_tables`` (b, nblk) routes ParisKV layers through the paged
    block pool (the cache leaf is then a PagedLayerKVCache); paged layers
    take the fused retrieval path (no per-step meta-view gather, Stage-I
    histogram from the ``hist`` cache entry) unless ``paged_fused`` is
    False. ``hist`` is maintained at promotion on *both* paged paths, so
    the flag can flip between runs without invalidating state; its cost
    shows under the ``pariskv.promote`` scope (core.phases). An MLA
    layer's latent pool always takes the fused path
    (``mla.mla_decode_paged``).

    ``dev_map`` switches paged ParisKV layers to the **tiered** pool
    (ISSUE 6): retrieval runs unchanged over the device-resident
    metadata, winner K/V rows come from the staging pool when resident
    and from the host tier (``fetch`` — an offload.EntryFetch) when not,
    and promotion gathers K through the composed staging tables. The
    per-step fetch stats land in the ``fetch`` cache leaves."""
    pcfg = cfg.pariskv
    b = x_t.shape[0]
    with jax.named_scope(phases.DENSE):
        h = L.rms_norm(x_t[:, None], p["norm_attn"], cfg.norm_eps)[:, 0]
    pos = regions.pos + 1
    promote_mask = jnp.broadcast_to(jnp.asarray(will_promote), (b,))

    @jax.named_scope(phases.PROMOTE)
    def maybe_promote_rows(c):
        return jax.lax.cond(
            jnp.any(promote_mask),
            lambda cc: CC.promote_rows(cc, regions.enc_end, promote_mask,
                                       pcfg, signs),
            lambda cc: cc, c)

    @jax.named_scope(phases.PROMOTE)
    def maybe_promote_paged(c, hist):
        kvt = (None if dev_map is None
               else CC.tiered_kv_tables(block_tables, dev_map))
        return jax.lax.cond(
            jnp.any(promote_mask),
            lambda ch: CC.paged_promote_rows_hist(
                ch[0], ch[1], block_tables, regions.enc_end, promote_mask,
                pcfg, signs, kv_tables=kvt),
            lambda ch: ch, (c, hist))

    fetch_delta = None

    def pariskv_decode(kv):
        nonlocal fetch_delta
        if isinstance(kv, CC.PagedLayerKVCache):
            if dev_map is not None:
                y, kvc, fetch_delta = L.attn_decode_pariskv_tiered(
                    p["attn"], h, kv, cache["hist"], block_tables, dev_map,
                    fetch, rep, regions, ld.attn, pcfg, signs,
                    num_candidates, fused=paged_fused)
                return y, kvc
            if isinstance(dist, ShardedPagedDist):
                return L.attn_decode_pariskv_paged_sharded(
                    p["attn"], h, kv, cache["hist"], block_tables, regions,
                    ld.attn, pcfg, signs, num_candidates, dist.axis_name,
                    fused=paged_fused)
            if paged_fused:
                return L.attn_decode_pariskv_paged_fused(
                    p["attn"], h, kv, cache["hist"], block_tables, regions,
                    ld.attn, pcfg, signs, num_candidates)
            return L.attn_decode_pariskv_paged(
                p["attn"], h, kv, block_tables, regions, ld.attn, pcfg,
                signs, num_candidates)
        return L.attn_decode_pariskv(
            p["attn"], h, kv, regions, ld.attn, pcfg, signs,
            num_candidates,
            dist=None if isinstance(dist, ShardedPagedDist) else dist)

    def promote_and_store(kvc):
        """Post-attention promotion, paged (kv + hist) or contiguous."""
        if isinstance(kvc, CC.PagedLayerKVCache):
            kvc, hist = maybe_promote_paged(kvc, cache["hist"])
            return {"kv": kvc, "hist": hist}
        return {"kv": maybe_promote_rows(kvc)}

    def merge_fetch_stats(cache):
        """Accumulate the tiered step's fetch counters into the entry's
        ``fetch`` leaves (rows cols 0..2; col 3 belongs to fill)."""
        if fetch_delta is None or "fetch" not in cache:
            return cache
        f = cache["fetch"]
        return {**cache, "fetch": {
            "touched": f["touched"] + fetch_delta["touched"],
            "rows": f["rows"].at[:, :3].add(fetch_delta["rows"]),
            "stall": f["stall"] + fetch_delta["stall"],
            "calls": f["calls"] + fetch_delta["calls"],
            "retries": f["retries"] + fetch_delta["retries"],
            "timeouts": f["timeouts"] + fetch_delta["timeouts"],
            "degraded": f["degraded"] + fetch_delta["degraded"]}}

    if ld.mixer == "attn":
        if ld.use_pariskv:
            y, kvc = pariskv_decode(cache["kv"])
            cache = merge_fetch_stats({**cache, **promote_and_store(kvc)})
        elif isinstance(cache["kv"], CC.LayerKVCache):
            # baseline full-attention decode over the ParisKV store
            y, kv = L.attn_decode_dense(
                p["attn"], h, (cache["kv"].k, cache["kv"].v), pos, ld.attn)
            cache = {**cache,
                     "kv": cache["kv"]._replace(k=kv[0], v=kv[1])}
        else:
            y, kv = L.attn_decode_dense(p["attn"], h, cache["kv"], pos, ld.attn)
            cache = {**cache, "kv": kv}
    elif ld.mixer == "mla":
        if isinstance(cache["kv"], CC.PagedLayerKVCache):
            y, kvc = MLA.mla_decode_paged(
                p["attn"], h, cache["kv"], cache["hist"], block_tables,
                regions, cfg, signs, num_candidates)
        else:
            y, kvc = MLA.mla_decode(p["attn"], h, cache["kv"], regions, cfg,
                                    signs, num_candidates,
                                    use_pariskv=ld.use_pariskv)
        cache = {**cache, **promote_and_store(kvc)}
    elif ld.mixer == "cross":
        km, vm = cache["media_kv"]
        q = (h @ p["attn"]["wq"]).reshape(h.shape[0], ld.attn.num_heads,
                                          ld.attn.head_dim)
        from repro.core.attention import full_attention
        out = full_attention(q[:, None], km, vm, None,
                             sm_scale=ld.attn.scale())[:, 0]
        y = jnp.tanh(p["cross_gate"]) * (
            out.reshape(h.shape[0], -1) @ p["attn"]["wo"])
    elif ld.mixer == "ssm":
        y, sc = SSM.ssm_decode(p["ssm"], h, cache["ssm"], cfg)
        cache = {**cache, "ssm": sc}
    elif ld.mixer == "hybrid":
        ya, kvc = pariskv_decode(cache["kv"])
        ys, sc = SSM.ssm_decode(p["ssm"], h, cache["ssm"], cfg)
        y = 0.5 * (ya + ys)
        cache = merge_fetch_stats({**cache, **promote_and_store(kvc),
                                   "ssm": sc})
    with jax.named_scope(phases.DENSE):
        x_t = x_t + y.astype(x_t.dtype)
    if ld.cross:
        h = L.rms_norm(x_t[:, None], p["norm_cross"], cfg.norm_eps)[:, 0]
        km, vm = cache["media_kv"]
        from repro.core.attention import full_attention
        q = (h @ p["cross"]["wq"]).reshape(h.shape[0], ld.attn.num_heads,
                                           ld.attn.head_dim)
        out = full_attention(q[:, None], km, vm, None,
                             sm_scale=ld.attn.scale())[:, 0]
        x_t = x_t + (out.reshape(h.shape[0], -1) @ p["cross"]["wo"]).astype(x_t.dtype)
    if ld.ffn != "none":
        with jax.named_scope(phases.DENSE):
            h = L.rms_norm(x_t[:, None], p["norm_mlp"], cfg.norm_eps)[:, 0]
            if ld.ffn == "moe":
                y = MOE.moe_decode(p["moe"], h, cfg.experts_per_token,
                                   cfg.norm_topk_prob)
            else:
                y = L.mlp_fwd(p["mlp"], h)
            x_t = x_t + y.astype(x_t.dtype)
    return x_t, cache


# prefix rows an MLA fill chunk scores at once (mla.mla_fill_chunk)
MLA_FILL_BLOCK = 2048


class FillCtx(NamedTuple):
    """Static-shape description of one prefill chunk of the (single)
    filling slot inside a mixed prefill+decode step (ISSUE 5).

    slot/start/valid_n are traced scalars: which slot fills, its frontier
    before the step, and how many of the chunk's P token positions are
    real prompt tokens (the rest are dropped pad tail)."""
    slot: jax.Array      # () int32 — the filling slot's batch row
    start: jax.Array     # () int32 — fill frontier (tokens already written)
    q_pos: jax.Array     # (1, P) int32 — the chunk's token positions
    valid: jax.Array     # (1, P) bool — t < valid_n
    valid_n: jax.Array   # () int32 — real tokens in this chunk
    bt_row: Any = None   # (nblk,) int32 — paged mode: the slot's table row
    dev_row: Any = None  # (nblk,) int32 — tiered mode: composed staging row


def _layer_fill(p, x_f, ld: LayerDef, cfg: ModelConfig, cache, fctx: FillCtx,
                signs, fetch=None, rep=None, dist=None):
    """One layer of one prefill chunk for the filling slot.

    Mirrors ``_layer_prefill``'s math chunk-by-chunk: qkv at the chunk's
    true positions, chunk-causal attention against the already-written
    prefix (gathered from whatever layout the cache uses: contiguous row,
    ring buffer, or block pool), K/V + ParisKV metadata scattered into the
    filling row, and — on the paged path — the slot's incremental bucket
    histogram advanced so it stays exact *mid-fill*, not just at
    completion. Attention and MLA mixers support chunked fill
    (``fill_supported``); an MLA chunk attends in absorbed form to the
    latent prefix (``mla.mla_fill_chunk``) and writes latent rows the
    way attention writes K. SSM/cross prompts still prefill solo."""
    if ld.mixer not in ("attn", "mla"):
        raise NotImplementedError(
            f"chunked prefill supports attention and MLA mixers only, got "
            f"{ld.mixer!r} (use prefill_budget=0)")
    pcfg = cfg.pariskv
    P = fctx.q_pos.shape[1]
    h = L.rms_norm(x_f, p["norm_attn"], cfg.norm_eps)
    new_pos = jnp.where(fctx.valid, fctx.q_pos, -1)

    def row1(a):
        return jax.lax.dynamic_slice_in_dim(a, fctx.slot, 1, axis=0)

    kv = cache["kv"]
    fill_fetched = fill_stall = fill_calls = None
    fill_retries = fill_timeouts = fill_deg = fill_keep = None
    if ld.mixer == "mla":
        if isinstance(kv, CC.PagedLayerKVCache):
            n_log = fctx.bt_row.shape[0] * CC.paged_block_size(kv)
            kb = math.gcd(n_log, MLA_FILL_BLOCK)

            def read(j):
                lidx = (j * kb + jnp.arange(kb))[None]
                return CC.paged_gather_rows(kv.k, fctx.bt_row[None],
                                            lidx)[0, :, 0]
        else:
            k_row = row1(kv.k)[0, :, 0]                  # (n_max, r + dr)
            kb = math.gcd(k_row.shape[0], MLA_FILL_BLOCK)

            def read(j):
                return jax.lax.dynamic_slice_in_dim(k_row, j * kb, kb)
        y, lat = MLA.mla_fill_chunk(p["attn"], h, cfg, fctx.q_pos, new_pos,
                                    fctx.start, read, kb)
        k_new = v_new = lat[:, :, None]         # a latent pool holds no v
    elif isinstance(kv, CC.PagedLayerKVCache):
        bs = CC.paged_block_size(kv)
        nblk = fctx.bt_row.shape[0]
        idx = jnp.arange(nblk * bs)[None]
        if fctx.dev_row is not None:
            # tiered: the chunk-causal prefix read is dense over the whole
            # already-written prompt — staging rows where resident, host
            # fetch (pure_callback) for the rest. Blended exactly like the
            # decode winner path, so prefetch quality never changes tokens.
            blk = idx[0] // bs
            resident = (fctx.dev_row[blk] >= 0)[None]
            need = (idx < fctx.start) & ~resident
            host_blk = fctx.bt_row[blk][None]
            host_rows = jnp.where(need & (host_blk >= 0),
                                  host_blk * bs + idx % bs,
                                  -1).astype(jnp.int32)
            if getattr(fetch, "pipelined", False):
                # overlapped (ISSUE 9): issue the host prefix fetch,
                # read the staging rows while it's in flight, collect
                # last — same fence/operand ordering as the decode path
                ticket = fetch.begin_rows(host_rows, rep)
                idx_b = idx + fetch.fence(ticket)
                k_stag = CC.paged_gather_rows(kv.k, fctx.dev_row[None],
                                              idx_b)
                v_stag = CC.paged_gather_rows(kv.v, fctx.dev_row[None],
                                              idx_b)
                (k_host, v_host, fill_stall, fill_retries, fill_timeouts,
                 f_ok) = fetch.collect_rows(
                    ticket, host_rows.shape, k_stag, v_stag)
                fill_calls = jnp.int32(2)
            else:
                k_stag = CC.paged_gather_rows(kv.k, fctx.dev_row[None], idx)
                v_stag = CC.paged_gather_rows(kv.v, fctx.dev_row[None], idx)
                (k_host, v_host, fill_stall, fill_retries, fill_timeouts,
                 f_ok) = fetch.rows(host_rows, rep)
                fill_calls = jnp.int32(1)
            sel = resident[..., None, None]
            k_pref = jnp.where(sel, k_stag, k_host.astype(k_stag.dtype))
            v_pref = jnp.where(sel, v_stag, v_host.astype(v_stag.dtype))
            fill_fetched = (host_rows >= 0).sum().astype(jnp.int32)
            # degraded fill step: the host prefix fetch exhausted its
            # retries, so the failed (zeroed) host rows are masked out of
            # the chunk-causal prefix instead of attending to garbage
            fill_keep = (host_rows < 0) | (f_ok > 0)
            fill_deg = ((fill_fetched > 0)
                        & (f_ok == 0)).astype(jnp.int32)
        else:
            k_pref = CC.paged_gather_rows(kv.k, fctx.bt_row[None], idx)
            v_pref = CC.paged_gather_rows(kv.v, fctx.bt_row[None], idx)
        pref_pos = jnp.where(idx < fctx.start, idx, -1)
        if fill_keep is not None:
            pref_pos = jnp.where(fill_keep, pref_pos, -1)
    elif isinstance(kv, CC.LayerKVCache):
        k_pref, v_pref = row1(kv.k), row1(kv.v)
        idx = jnp.arange(k_pref.shape[1])[None]
        pref_pos = jnp.where(idx < fctx.start, idx, -1)
    else:                                    # sliding-window ring buffer
        k_pref, v_pref = row1(kv[0]), row1(kv[1])
        w = k_pref.shape[1]
        last = fctx.start - 1
        p_s = last - (last - jnp.arange(w)) % w  # latest pos < start ≡ s
        pref_pos = jnp.where(p_s >= 0, p_s, -1)[None]

    if ld.mixer == "attn":
        if isinstance(dist, ShardedPagedDist) and isinstance(
                kv, CC.PagedLayerKVCache):
            # prefix k/v came from the shard-local pool; k_new/v_new come
            # back head-local too, so the block writes below stay local
            y, k_new, v_new = L.attn_fill_chunk_sharded(
                p["attn"], h, ld.attn, fctx.q_pos, k_pref, v_pref, pref_pos,
                new_pos, dist.axis_name)
        else:
            y, k_new, v_new = L.attn_fill_chunk(p["attn"], h, ld.attn,
                                                fctx.q_pos, k_pref, v_pref,
                                                pref_pos, new_pos)

    if isinstance(kv, (CC.PagedLayerKVCache, CC.LayerKVCache)):
        meta = None
        if ld.use_pariskv:
            meta = jax.tree.map(lambda a: a[0],
                                CC._encode_block(k_new, pcfg, signs))
        if isinstance(kv, CC.PagedLayerKVCache):
            if fctx.dev_row is not None:
                kvc = CC.tiered_fill_chunk_write(
                    kv, fctx.bt_row, fctx.dev_row, fctx.start, k_new[0],
                    v_new[0], fctx.valid[0], meta)
                if "fetch" in cache:
                    f = cache["fetch"]
                    cache = {**cache, "fetch": {
                        **f,
                        "rows": f["rows"].at[fctx.slot, 3].add(fill_fetched),
                        "stall": f["stall"] + fill_stall,
                        "calls": f["calls"] + fill_calls,
                        "retries": f["retries"] + fill_retries,
                        "timeouts": f["timeouts"] + fill_timeouts,
                        "degraded": f["degraded"].at[fctx.slot].add(
                            fill_deg)}}
            else:
                kvc = CC.paged_fill_chunk_write(
                    kv, fctx.bt_row, fctx.start, k_new[0], v_new[0],
                    fctx.valid[0], meta)
            cache = {**cache, "kv": kvc}
            if ld.use_pariskv and "hist" in cache:
                hrow = CC.paged_fill_hist_update(
                    kvc, cache["hist"][fctx.slot], fctx.bt_row, fctx.start,
                    fctx.start + fctx.valid_n, pcfg, P)
                cache = {**cache, "hist": cache["hist"].at[fctx.slot].set(
                    hrow.astype(cache["hist"].dtype))}
        else:
            cache = {**cache, "kv": CC.fill_chunk_write(
                kv, fctx.slot, fctx.start, k_new[0], v_new[0],
                fctx.valid[0], meta)}
    else:
        w = kv[0].shape[1]
        # a chunk can wrap the ring: keep only the last write per slot
        keep = fctx.valid[0] & (jnp.arange(P) + w >= fctx.valid_n)
        slot_idx = jnp.where(keep, (fctx.start + jnp.arange(P)) % w, w)
        rows = jnp.full((P,), fctx.slot, jnp.int32)
        cache = {**cache, "kv": (
            kv[0].at[rows, slot_idx].set(k_new[0].astype(kv[0].dtype),
                                         mode="drop"),
            kv[1].at[rows, slot_idx].set(v_new[0].astype(kv[1].dtype),
                                         mode="drop"))}

    x_f = x_f + y.astype(x_f.dtype)
    if ld.ffn != "none":
        h = L.rms_norm(x_f, p["norm_mlp"], cfg.norm_eps)
        if ld.ffn == "moe":
            y, _ = MOE.moe_fwd(p["moe"], h, cfg.experts_per_token,
                               cfg.norm_topk_prob)
        else:
            y = L.mlp_fwd(p["mlp"], h)
        x_f = x_f + y.astype(x_f.dtype)
    return x_f, cache


def fill_support_reason(cfg: ModelConfig) -> Optional[str]:
    """Why chunked prefill canNOT serve this architecture, or None when it
    can. Engines log the reason when they fall back to solo prefill, so a
    silent perf cliff becomes an explained one."""
    name = getattr(cfg, "name", cfg.family)
    if cfg.family in ("vlm", "audio"):
        return (f"config {name!r}: family {cfg.family!r} computes media "
                f"K/V in one encoder pass, so prompts prefill solo")
    for si, stage in enumerate(layer_plan(cfg)):
        for i, ld in enumerate(stage.layers):
            if ld.mixer not in ("attn", "mla"):
                return (f"config {name!r}: stage {si} layer {i} mixer "
                        f"{ld.mixer!r} has no chunk-resumable prefill "
                        f"(attention and MLA mixers only)")
            if ld.cross:
                return (f"config {name!r}: stage {si} layer {i} has a "
                        f"cross-attention sublayer, which reads "
                        f"encoder-pass media K/V")
    return None


def fill_supported(cfg: ModelConfig) -> bool:
    """Whether chunked prefill can serve this architecture: every mixer is
    plain attention (ParisKV or sliding-window) or MLA, with no cross
    sublayer. SSM/hybrid recurrences and media cross-attention still
    prefill solo (ROADMAP). See ``fill_support_reason`` for *why* a
    config falls back."""
    return fill_support_reason(cfg) is None


def share_support_reason(cfg: ModelConfig) -> Optional[str]:
    """Why block-granular prefix sharing canNOT serve this architecture,
    or None when it can (ISSUE 7). Sharing maps already-cached *pool*
    blocks into a new slot's table and skips the fill over them, so every
    layer's prompt-position state must live in the shared pool: chunked
    prefill must be supported (the unshared suffix fills through the
    table) and every attention layer must be a ParisKV layer — a
    sliding-window ring buffer is *slot-local*, so a slot that skipped
    the prefix fill would face an empty ring where the donor's window
    should be."""
    r = fill_support_reason(cfg)
    if r is not None:
        return r
    name = getattr(cfg, "name", cfg.family)
    for si, stage in enumerate(layer_plan(cfg)):
        for i, ld in enumerate(stage.layers):
            if not ld.use_pariskv:
                return (f"config {name!r}: stage {si} layer {i} caches its "
                        f"window in a slot-local ring buffer, which a "
                        f"shared prefix cannot populate (ParisKV-attention "
                        f"layers only)")
    return None


def share_supported(cfg: ModelConfig) -> bool:
    return share_support_reason(cfg) is None


def offload_support_reason(cfg: ModelConfig) -> Optional[str]:
    """Why the tiered host-offloaded pool canNOT serve this architecture,
    or None when it can. The tiered pool splits ParisKV attention K/V
    between a device staging pool and the host; its host pool, staging
    and fetch paths hold K and V leaves, not the paged latent pool of an
    MLA layer."""
    name = getattr(cfg, "name", cfg.family)
    for si, stage in enumerate(layer_plan(cfg)):
        for i, ld in enumerate(stage.layers):
            if ld.mixer == "mla":
                return (f"config {name!r}: stage {si} layer {i} mixer "
                        f"'mla' pages a latent pool, which the host tier "
                        f"does not handle yet (ROADMAP)")
    return None


def offload_supported(cfg: ModelConfig) -> bool:
    return offload_support_reason(cfg) is None


def sharded_support_reason(cfg: ModelConfig) -> Optional[str]:
    """Why mesh-sharded paged serving canNOT serve this architecture, or
    None when it can (ISSUE 8). The mesh partitions the paged ParisKV
    pool on the KV-head axis; bounded slot-local state (ring buffers,
    SSM, media K/V) is replicated and its compute runs identically on
    every shard. An MLA latent pool has one shared head, so that axis
    cannot split it."""
    name = getattr(cfg, "name", cfg.family)
    for si, stage in enumerate(layer_plan(cfg)):
        for i, ld in enumerate(stage.layers):
            if ld.mixer == "mla":
                return (f"config {name!r}: stage {si} layer {i} mixer "
                        f"'mla' pages one latent head, which the mesh's "
                        f"head partition does not handle yet (ROADMAP)")
    return None


def sharded_supported(cfg: ModelConfig) -> bool:
    return sharded_support_reason(cfg) is None


def _stage_pass(params, cfg: ModelConfig, x_t, caches, regions, signs,
                num_candidates, will_promote, use_pariskv, dist,
                block_tables, paged_fused, x_f=None, fctx=None,
                any_fill=None, dev_map=None, fetch=None):
    """Run one step's layer stack: every stage's repeat-scan advances the
    decode token for all rows and — when ``x_f`` is given — one prefill
    chunk for the filling slot under an any-fill ``lax.cond``, inside the
    *same* scan body, so a mixed step reads each layer's weights once.

    Tiered mode (``dev_map``/``fetch`` given) additionally feeds each
    layer its host-fetch namespace: the cache-entry name ``s{si}.l{i}``
    is resolved against the HostKVPool *at trace time* (the stage loop
    and layer loop are python), and the repeat index rides the scan xs so
    the callback knows which stacked repeat's host pool to read."""
    new_caches = []
    for si, (stage, sp, sc) in enumerate(
            zip(layer_plan(cfg), params["stages"], caches)):

        def body(carry, slices, stage=stage, si=si):
            x_t, x_f = carry
            p_slice, c_slice, rep = slices
            new_c = {}
            for i, ld in enumerate(stage.layers):
                ld_eff = ld if use_pariskv else dataclasses_replace_nopk(ld)
                lc = c_slice[f"l{i}"]
                fe = (fetch.entry(f"s{si}.l{i}")
                      if fetch is not None and "fetch" in lc else None)
                x_t, c = _layer_decode(
                    p_slice[f"l{i}"], x_t, ld_eff, cfg, lc,
                    regions, signs, num_candidates, will_promote, dist=dist,
                    block_tables=block_tables, paged_fused=paged_fused,
                    dev_map=dev_map, fetch=fe, rep=rep)
                if x_f is not None:
                    with jax.named_scope(phases.FILL):
                        x_f, c = jax.lax.cond(
                            any_fill,
                            lambda op, p_l=p_slice[f"l{i}"], ld_l=ld_eff,
                                   fe_l=fe, rep_l=rep:
                                _layer_fill(p_l, op[0], ld_l, cfg, op[1],
                                            fctx, signs, fetch=fe_l,
                                            rep=rep_l, dist=dist),
                            lambda op: op, (x_f, c))
                new_c[f"l{i}"] = c
            return (x_t, x_f), new_c

        xs = (sp, sc, jnp.arange(stage.repeat))
        (x_t, x_f), filled = jax.lax.scan(body, (x_t, x_f), xs)
        new_caches.append(filled)
    return x_t, x_f, new_caches


def decode_step(params, cfg: ModelConfig, token: jax.Array, state: ServeState,
                use_pariskv: bool = True, dist=None, active=None,
                block_tables=None, paged_fused: bool = True,
                dev_map=None, fetch=None) -> Tuple[jax.Array, ServeState]:
    """One decode step: token (b,) int32 → (logits (b, v), new state).

    Rows advance independently (per-row regions). ``active`` (b,) bool
    gates advancement: inactive rows (free/finished slots in a continuous-
    batching engine) keep their ``pos``/``enc_end`` frozen and never
    promote — their compute still runs (SPMD) but writes only touch the
    already-dead position pos+1, so their committed cache state is
    untouched until the slot is re-admitted.

    dist: optional (mesh, seq_axes, batch_axes) — enables the context-
    parallel hierarchical retrieval (EXPERIMENTS §Perf E1/E2) on ParisKV
    layers when the cache is sequence-sharded.

    block_tables: (b, nblk) int32 — paged mode (caches built by
    make_paged_caches); ParisKV reads/writes go through the block table
    and the logical capacity is nblk · block_size per row.
    ``paged_fused=False`` falls back to the per-step meta-view gather
    (token-identical; the fused default skips that materialization)."""
    pcfg = cfg.pariskv
    b = token.shape[0]
    signs = rotation_signs(cfg)
    with jax.named_scope(phases.DENSE):
        x_t = _embed(params, cfg, token[:, None])[:, 0]
    pos_b = jnp.broadcast_to(jnp.asarray(state.regions.pos, jnp.int32), (b,))
    enc_b = jnp.broadcast_to(jnp.asarray(state.regions.enc_end, jnp.int32),
                             (b,))
    regions = CC.CacheRegions(pos=pos_b, enc_end=enc_b)
    act = (jnp.ones((b,), bool) if active is None
           else jnp.broadcast_to(active, (b,)))
    will_promote = CC.promote_trigger(regions, pcfg) & act
    if block_tables is not None:
        assert dist is None or isinstance(dist, ShardedPagedDist), (
            "paged decode takes dist=ShardedPagedDist (mesh head sharding "
            "under shard_map); the contiguous (mesh, seq_axes, batch_axes) "
            "tuple is for contiguous caches only")
        assert use_pariskv, "paged decode serves the ParisKV path only"
        n_max = block_tables.shape[1] * _pool_block_size(state.caches)
    else:
        n_max = _cache_n_max(cfg, state.caches)
    num_candidates = pcfg.candidate_count(n_max)

    x_t, _, new_caches = _stage_pass(
        params, cfg, x_t, state.caches, regions, signs, num_candidates,
        will_promote, use_pariskv, dist, block_tables, paged_fused,
        dev_map=dev_map, fetch=fetch)

    with jax.named_scope(phases.DENSE):
        x_t = L.rms_norm(x_t[:, None], params["final_norm"],
                         cfg.norm_eps)[:, 0]
        logits = _unembed(params, cfg, x_t)
    new_regions = CC.CacheRegions(
        pos=jnp.where(act, pos_b + 1, pos_b),
        enc_end=jnp.where(will_promote, enc_b + pcfg.update_interval, enc_b))
    return logits, ServeState(new_caches, new_regions)


def decode_fill_step(params, cfg: ModelConfig, token: jax.Array,
                     state: ServeState, fill_tokens: jax.Array,
                     fctx: FillCtx, any_fill: jax.Array,
                     use_pariskv: bool = True, dist=None, active=None,
                     block_tables=None, paged_fused: bool = True,
                     dev_map=None, fetch=None
                     ) -> Tuple[jax.Array, jax.Array, ServeState]:
    """One mixed prefill+decode step (ISSUE 5): ``decode_step``'s math for
    every active row *plus* one ``P``-token prompt chunk for the filling
    slot, fused into the same stage scans (the fill side is guarded by an
    any-fill ``lax.cond``, so pure-decode steps pay nothing).

    Returns (decode logits (b, v), fill logits (1, v) — the filling
    slot's last-valid-token logits, garbage when nothing fills —, state).
    The caller owns the fill bookkeeping (frontier advance, per-slot
    regions, first-token emission on completion)."""
    pcfg = cfg.pariskv
    b = token.shape[0]
    signs = rotation_signs(cfg)
    with jax.named_scope(phases.DENSE):
        x_t = _embed(params, cfg, token[:, None])[:, 0]
    # the fill-side embed only runs when something fills — steady-state
    # pure-decode steps (all fills complete) skip it along with the
    # per-layer fill branches and the fill-logits head below
    with jax.named_scope(phases.FILL):
        emb_sds = jax.eval_shape(lambda t: _embed(params, cfg, t),
                                 fill_tokens)
        x_f = jax.lax.cond(
            any_fill, lambda t: _embed(params, cfg, t),
            lambda t: jnp.zeros(emb_sds.shape, emb_sds.dtype), fill_tokens)
    pos_b = jnp.broadcast_to(jnp.asarray(state.regions.pos, jnp.int32), (b,))
    enc_b = jnp.broadcast_to(jnp.asarray(state.regions.enc_end, jnp.int32),
                             (b,))
    regions = CC.CacheRegions(pos=pos_b, enc_end=enc_b)
    act = (jnp.ones((b,), bool) if active is None
           else jnp.broadcast_to(active, (b,)))
    will_promote = CC.promote_trigger(regions, pcfg) & act
    if block_tables is not None:
        assert dist is None or isinstance(dist, ShardedPagedDist), (
            "paged decode takes dist=ShardedPagedDist (mesh head sharding "
            "under shard_map); the contiguous (mesh, seq_axes, batch_axes) "
            "tuple is for contiguous caches only")
        assert use_pariskv, "paged decode serves the ParisKV path only"
        n_max = block_tables.shape[1] * _pool_block_size(state.caches)
    else:
        n_max = _cache_n_max(cfg, state.caches)
    num_candidates = pcfg.candidate_count(n_max)

    x_t, x_f, new_caches = _stage_pass(
        params, cfg, x_t, state.caches, regions, signs, num_candidates,
        will_promote, use_pariskv, dist, block_tables, paged_fused,
        x_f=x_f, fctx=fctx, any_fill=any_fill, dev_map=dev_map, fetch=fetch)

    with jax.named_scope(phases.DENSE):
        x_t = L.rms_norm(x_t[:, None], params["final_norm"],
                         cfg.norm_eps)[:, 0]
        logits = _unembed(params, cfg, x_t)

    def fill_head(xf):
        x_fn = L.rms_norm(xf, params["final_norm"], cfg.norm_eps)
        x_last = jax.lax.dynamic_slice_in_dim(
            x_fn, jnp.maximum(fctx.valid_n - 1, 0), 1, axis=1)[:, 0]
        return _unembed(params, cfg, x_last)

    with jax.named_scope(phases.FILL):
        fl_sds = jax.eval_shape(fill_head, x_f)
        fill_logits = jax.lax.cond(
            any_fill, fill_head,
            lambda xf: jnp.zeros(fl_sds.shape, fl_sds.dtype), x_f)
    new_regions = CC.CacheRegions(
        pos=jnp.where(act, pos_b + 1, pos_b),
        enc_end=jnp.where(will_promote, enc_b + pcfg.update_interval, enc_b))
    return logits, fill_logits, ServeState(new_caches, new_regions)


# ---------------------------------------------------- chunked decode --------
class SlotState(NamedTuple):
    """Device-resident state of a slot-based continuous-batching engine.

    caches/regions span ``max_batch`` cache slots; ``cur_tok`` is the last
    emitted token per slot and ``remaining`` the number of tokens each slot
    still has to emit (0 ⇒ slot idle/free).

    The last three fields exist only under chunked prefill
    (``prefill_budget > 0``; None otherwise): ``prompt`` holds each slot's
    raw prompt tokens on-device and ``fill_pos``/``fill_len`` track the
    fill frontier — a slot with ``fill_pos < fill_len`` is *filling*: it
    consumes ``prefill_budget`` prompt tokens per mixed step instead of
    decoding, and emits its first token the step its fill completes.
    """
    caches: Any
    regions: CC.CacheRegions
    cur_tok: jax.Array    # (b,) int32
    remaining: jax.Array  # (b,) int32
    fill_pos: Any = None  # (b,) int32 — prompt tokens already written
    fill_len: Any = None  # (b,) int32 — total prompt length (0 ⇒ no fill)
    prompt: Any = None    # (b, n_max + P) int32 — device prompt buffer


def _fill_state(batch: int, n_max: int, prefill_budget: int):
    if prefill_budget <= 0:
        return dict(fill_pos=None, fill_len=None, prompt=None)
    return dict(
        fill_pos=jnp.zeros((batch,), jnp.int32),
        fill_len=jnp.zeros((batch,), jnp.int32),
        # width n_max + P so the last chunk's dynamic_slice never clamps
        prompt=jnp.zeros((batch, n_max + prefill_budget), jnp.int32))


def init_slot_state(cfg: ModelConfig, batch: int, n_max: int,
                    prefill_budget: int = 0) -> SlotState:
    return SlotState(
        caches=make_caches(cfg, batch, n_max),
        regions=regions_spec(batch),
        cur_tok=jnp.zeros((batch,), jnp.int32),
        remaining=jnp.zeros((batch,), jnp.int32),
        **_fill_state(batch, n_max, prefill_budget))


def init_paged_slot_state(cfg: ModelConfig, batch: int, num_blocks: int,
                          block_size: int, n_max: int,
                          prefill_budget: int = 0,
                          num_device_blocks: Optional[int] = None
                          ) -> SlotState:
    """Slot state over a shared block pool: same per-slot scalar vectors,
    but ParisKV cache leaves are PagedLayerKVCache pools (no batch dim).
    The matching block tables are host-managed (serving engine) and passed
    into decode_chunk per call — they change at admission/allocation/
    eviction boundaries, never inside a chunk. ``num_device_blocks``
    builds the tiered (host-offloaded) pool instead — K/V leaves sized to
    the staging pool, metadata full-size, plus fetch-stat leaves."""
    return SlotState(
        caches=make_paged_caches(cfg, batch, num_blocks, block_size, n_max,
                                 num_device_blocks=num_device_blocks),
        regions=regions_spec(batch),
        cur_tok=jnp.zeros((batch,), jnp.int32),
        remaining=jnp.zeros((batch,), jnp.int32),
        **_fill_state(batch, n_max, prefill_budget))


def sharded_state_specs(caches, prefill_budget: int = 0,
                        axis_name: str = "kv") -> SlotState:
    """PartitionSpec tree matching a paged SlotState, for ``jax.shard_map``
    in/out_specs and ``NamedSharding`` placement (ISSUE 8). ``caches`` may
    be real caches or a ``make_paged_caches(..., as_spec=True)`` tree —
    only the structure is read.

    Partitioned on the KV-head axis: pool K/V (stacked
    (repeat, nb, bs, G, hd) → heads at axis 3), pool metadata
    ((repeat, nb, G, bs, B) → axis 2) and per-slot histograms
    ((repeat, batch, G, B, 2^m) → axis 2). Everything else — regions,
    scalars, prompts, ring/SSM/media leaves — is replicated: those layers
    compute identically on every shard, and block numbering stays global
    so shard-local retrieval returns globally valid physical rows."""
    P = jax.sharding.PartitionSpec

    def entry_specs(lc):
        out = {}
        for key, val in lc.items():
            if key == "kv" and isinstance(val, CC.PagedLayerKVCache):
                out[key] = CC.PagedLayerKVCache(
                    k=P(None, None, None, axis_name),
                    v=P(None, None, None, axis_name),
                    meta_ids=P(None, None, axis_name),
                    meta_codes=P(None, None, axis_name),
                    meta_w=P(None, None, axis_name))
            elif key == "hist":
                out[key] = P(None, None, axis_name)
            else:
                out[key] = jax.tree.map(lambda _: P(), val)
        return out

    fill = P() if prefill_budget > 0 else None
    return SlotState(
        caches=[{ln: entry_specs(lc) for ln, lc in sc.items()}
                for sc in caches],
        regions=CC.CacheRegions(pos=P(), enc_end=P()),
        cur_tok=P(), remaining=P(),
        fill_pos=fill, fill_len=fill, prompt=fill)


def _zero_fetch_leaves(caches):
    """Fresh fetch-stat leaves at a chunk boundary: the engine reads the
    per-chunk deltas back after each chunk, so counters restart at 0."""
    return [
        {ln: {key: (jax.tree.map(jnp.zeros_like, val) if key == "fetch"
                    else val)
              for key, val in lc.items()}
         for ln, lc in sc.items()}
        for sc in caches]


def decode_chunk(params, cfg: ModelConfig, state: SlotState, num_steps: int,
                 use_pariskv: bool = True, eos_id: Optional[int] = None,
                 dist=None, block_tables=None, paged_fused: bool = True,
                 prefill_budget: int = 0, dev_map=None, fetch=None
                 ) -> Tuple[jax.Array, SlotState]:
    """Run ``num_steps`` decode steps fully on-device (lax.scan): greedy
    argmax sampling, per-slot active masking, one host sync per chunk.

    Returns (tokens (b, num_steps) int32 with -1 at inactive steps, state).
    Argmax emits only non-negative ids, so -1 is an unambiguous sentinel;
    with ``prefill_budget == 0`` the valid tokens form a prefix per row,
    with chunked prefill a filling slot's row can *lead* with -1s (steps
    spent filling) before its first token appears.

    ``block_tables`` (paged mode) is constant across the chunk — the
    serving engine pre-allocates every block the chunk's appends can
    reach before launching it (lazy allocation at chunk granularity).

    ``prefill_budget`` > 0 turns the scan into **mixed prefill+decode
    steps** (ISSUE 5): each step additionally consumes up to that many
    prompt tokens for the (at most one) slot whose ``fill_pos <
    fill_len``, writing K/V + metadata through the same caches/tables,
    and emits the slot's first token the step its fill completes —
    admitted prompts no longer stall every decoding slot for a full solo
    prefill. 0 keeps the pure-decode step (the solo-prefill A/B path).

    ``dev_map``/``fetch`` (tiered mode, ISSUE 6) route ParisKV winner
    K/V through the staging pool + host fetch path; the map is frozen
    for the chunk (residency changes only at chunk boundaries) and the
    fetch-stat cache leaves are zeroed here so the engine reads clean
    per-chunk deltas."""
    if dev_map is not None:
        state = state._replace(caches=_zero_fetch_leaves(state.caches))
    if prefill_budget <= 0:
        def step(st, _):
            active = st.remaining > 0
            logits, new = decode_step(params, cfg, st.cur_tok,
                                      ServeState(st.caches, st.regions),
                                      use_pariskv=use_pariskv, dist=dist,
                                      active=active,
                                      block_tables=block_tables,
                                      paged_fused=paged_fused,
                                      dev_map=dev_map, fetch=fetch)
            with jax.named_scope(phases.DENSE):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                emit = jnp.where(active, nxt, -1)
                rem = st.remaining - active.astype(jnp.int32)
                if eos_id is not None:
                    rem = jnp.where(active & (nxt == eos_id), 0, rem)
                cur = jnp.where(active, nxt, st.cur_tok)
            return st._replace(caches=new.caches, regions=new.regions,
                               cur_tok=cur, remaining=rem), emit

        final, emitted = jax.lax.scan(step, state, None, length=num_steps)
        return jnp.moveaxis(emitted, 0, 1), final

    P = int(prefill_budget)
    assert state.prompt is not None, \
        "prefill_budget > 0 needs a state built with the same budget"
    pcfg = cfg.pariskv

    def step(st, _):
        with jax.named_scope(phases.FILL):
            filling = (st.fill_len > 0) & (st.fill_pos < st.fill_len)
            any_fill = jnp.any(filling)
            fslot = jnp.argmax(filling).astype(jnp.int32)
            active = (st.remaining > 0) & ~filling
            start = st.fill_pos[fslot]
            flen = st.fill_len[fslot]
            valid_n = jnp.clip(flen - start, 0, P)
            q_pos = (start + jnp.arange(P))[None]
            valid = (jnp.arange(P) < valid_n)[None]
            fill_toks = jax.lax.dynamic_slice(st.prompt, (fslot, start),
                                              (1, P))
            bt_row = None if block_tables is None else block_tables[fslot]
            dev_row = (None if (block_tables is None or dev_map is None)
                       else CC.tiered_kv_tables(bt_row[None], dev_map)[0])
            fctx = FillCtx(slot=fslot, start=start, q_pos=q_pos,
                           valid=valid, valid_n=valid_n, bt_row=bt_row,
                           dev_row=dev_row)
        logits, fill_logits, new = decode_fill_step(
            params, cfg, st.cur_tok, ServeState(st.caches, st.regions),
            fill_toks, fctx, any_fill, use_pariskv=use_pariskv, dist=dist,
            active=active, block_tables=block_tables,
            paged_fused=paged_fused, dev_map=dev_map, fetch=fetch)
        with jax.named_scope(phases.DENSE):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            emit = jnp.where(active, nxt, -1)
            rem = st.remaining - active.astype(jnp.int32)
            if eos_id is not None:
                rem = jnp.where(active & (nxt == eos_id), 0, rem)
            cur = jnp.where(active, nxt, st.cur_tok)

        # --- fill bookkeeping: advance the frontier; on the completing
        # step the last prompt token's logits emit the first new token
        with jax.named_scope(phases.FILL):
            f1 = start + valid_n
            completed = any_fill & (f1 >= flen)
            ftok = jnp.argmax(fill_logits[0], -1).astype(jnp.int32)
            fill_pos = jnp.where(any_fill, st.fill_pos.at[fslot].set(f1),
                                 st.fill_pos)
            pos2 = jnp.where(any_fill,
                             new.regions.pos.at[fslot].set(f1 - 1),
                             new.regions.pos)
            enc2 = jnp.where(
                any_fill,
                new.regions.enc_end.at[fslot].set(CC.fill_enc_end(f1, pcfg)),
                new.regions.enc_end)
            emit = jnp.where(completed, emit.at[fslot].set(ftok), emit)
            cur = jnp.where(completed, cur.at[fslot].set(ftok), cur)
            rem_f = rem[fslot] - 1
            if eos_id is not None:
                rem_f = jnp.where(ftok == eos_id, 0, rem_f)
            rem = jnp.where(completed, rem.at[fslot].set(rem_f), rem)
        return SlotState(new.caches,
                         CC.CacheRegions(pos=pos2, enc_end=enc2),
                         cur, rem, fill_pos, st.fill_len, st.prompt), emit

    final, emitted = jax.lax.scan(step, state, None, length=num_steps)
    return jnp.moveaxis(emitted, 0, 1), final


def dataclasses_replace_nopk(ld: LayerDef) -> LayerDef:
    import dataclasses as _dc
    return _dc.replace(ld, use_pariskv=False)


def _cache_n_max(cfg: ModelConfig, caches) -> int:
    """Recover the static n_max from whichever cache carries a full KV store
    (ring buffers are window-sized and are skipped)."""
    for stage_cache in caches:
        for lc in stage_cache.values():
            if "kv" in lc:
                kv = lc["kv"]
                if isinstance(kv, CC.LayerKVCache):
                    return kv.k.shape[2]  # (repeat, b, n, G, hd) stacked
    return 0


def _pool_block_size(caches) -> int:
    """Block size of the shared pool (stacked leaf: (repeat, nb, bs, G, hd))."""
    for stage_cache in caches:
        for lc in stage_cache.values():
            if "kv" in lc and isinstance(lc["kv"], CC.PagedLayerKVCache):
                return lc["kv"].k.shape[2]
    raise ValueError("no PagedLayerKVCache leaf in caches")


def admit_fill(state: SlotState, slot, prompt_row, length, max_new,
               fill_start=None, bt_row=None, pcfg=None) -> SlotState:
    """Admit a request for **chunked prefill**: copy its prompt into the
    slot's device buffer and arm the fill state — no forward pass happens
    here; decode_chunk's mixed steps consume the prompt ``prefill_budget``
    tokens at a time. One compiled shape serves every prompt length, so
    admission costs one token copy instead of a bucketed prefill compile.

    ``prompt_row`` is the prompt padded to the buffer width. Paged layers'
    incremental histograms are zeroed (a re-admitted slot starts counting
    from an empty retrieval region; eviction already zeroes, this keeps
    the invariant independent of the previous tenant's exit path). Jit
    with the state donated — the fill twin of ``_admit_impl``.

    **Shared-prefix admission** (ISSUE 7): ``fill_start`` (traced scalar)
    starts the fill frontier past a block-granular prefix the engine
    already mapped into the slot's block table — the fill then writes
    only the unshared suffix ``[fill_start, length)``. The regions open
    exactly where a fill that had written those tokens itself would
    stand (``pos = fill_start - 1``, ``enc_end = fill_enc_end``), and the
    slot's histogram is *derived from the shared blocks' metadata*
    (``bucket_hist_from_paged_meta`` over ``bt_row``, which therefore
    must carry the shared mappings, -1 elsewhere) instead of zeroed —
    shared blocks arrive without any fill pass to count them. A traced
    ``fill_start`` of 0 reproduces the unshared path bit-for-bit (empty
    region → zero histogram), so one compiled shape serves both."""
    if fill_start is None:
        f0 = jnp.int32(0)
        caches = [
            {ln: {key: (val.at[:, slot].set(0) if key == "hist" else val)
                  for key, val in lc.items()}
             for ln, lc in stage_cache.items()}
            for stage_cache in state.caches]
        pos0, enc0 = jnp.int32(-1), jnp.int32(0)
    else:
        assert bt_row is not None and pcfg is not None, \
            "shared-prefix admission needs the slot's block-table row + pcfg"
        f0 = jnp.asarray(fill_start, jnp.int32)
        pos0 = f0 - 1
        enc0 = CC.fill_enc_end(f0, pcfg)

        def hist_row(val, kv):
            h = CC.bucket_hist_from_paged_meta(kv, bt_row, enc0, pcfg)
            return val.at[:, slot].set(h.astype(val.dtype))

        caches = [
            {ln: {key: (hist_row(val, lc["kv"]) if key == "hist" else val)
                  for key, val in lc.items()}
             for ln, lc in stage_cache.items()}
            for stage_cache in state.caches]
    return SlotState(
        caches=caches,
        regions=CC.CacheRegions(
            pos=state.regions.pos.at[slot].set(pos0),
            enc_end=state.regions.enc_end.at[slot].set(enc0)),
        cur_tok=state.cur_tok.at[slot].set(0),
        remaining=state.remaining.at[slot].set(max_new),
        fill_pos=state.fill_pos.at[slot].set(f0),
        fill_len=state.fill_len.at[slot].set(length),
        prompt=jax.lax.dynamic_update_slice(
            state.prompt, prompt_row[None].astype(jnp.int32), (slot, 0)))


def cancel_slot(state: SlotState, slot) -> SlotState:
    """Deactivate ``slot`` on-device (mid-flight — possibly mid-*fill* —
    eviction): no more decode steps, no more fill chunks. Cache rows are
    left as-is for the caller (the paged engine zeroes the slot's blocks
    and histogram through its evict path)."""
    fill_pos, fill_len = state.fill_pos, state.fill_len
    if fill_len is not None:
        fill_pos = fill_pos.at[slot].set(0)
        fill_len = fill_len.at[slot].set(0)
    return state._replace(
        remaining=state.remaining.at[slot].set(0),
        fill_pos=fill_pos, fill_len=fill_len)


def admit_paged(state: SlotState, slot, phys_blocks, caches1, regions1,
                tok0, rem, pcfg=None, dist=None) -> SlotState:
    """Install a solo (batch=1) prefill result into a paged slot state.

    Pool leaves scatter whole blocks to the physical ids in ``phys_blocks``
    (n_max // block_size entries; unallocated → out-of-range sentinel,
    dropped); slot-local leaves (ring/SSM/media) scatter into batch row
    ``slot`` exactly like the contiguous engine. ``hist`` entries (absent
    from the contiguous solo-prefill result) are *computed* here — one
    amortized histogram over the admitted row's metadata, the base the
    O(U) promotion updates build on — which needs ``pcfg``. Jit this with
    the state donated — it is the paged twin of ServingEngine._admit_impl.

    ``dist`` (ShardedPagedDist, inside shard_map): the solo prefill runs
    replicated, so ``caches1`` carries full-head KV leaves while the pool
    is head-sharded — each shard scatters (and counts histograms from)
    only its own head slice of the admitted row."""
    def local_kv(pool_entry, kv1):
        """Slice a replicated solo-prefill LayerKVCache to this shard's
        heads (stacked leaves: k/v (repeat, 1, n, G, hd), meta
        (repeat, 1, G, n, B))."""
        if dist is None:
            return kv1
        g_loc = pool_entry.k.shape[-2]
        g0 = jax.lax.axis_index(dist.axis_name) * g_loc
        sl = jax.lax.dynamic_slice_in_dim
        return kv1._replace(
            k=sl(kv1.k, g0, g_loc, axis=3),
            v=sl(kv1.v, g0, g_loc, axis=3),
            meta_ids=sl(kv1.meta_ids, g0, g_loc, axis=2),
            meta_codes=sl(kv1.meta_codes, g0, g_loc, axis=2),
            meta_w=sl(kv1.meta_w, g0, g_loc, axis=2))

    def merge(pool_entry, new_entry):
        if isinstance(pool_entry, CC.PagedLayerKVCache):
            return CC.paged_scatter_prefill(
                pool_entry, local_kv(pool_entry, new_entry), phys_blocks)
        return jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small, slot, axis=1),
            pool_entry, new_entry)

    def admit_hist(hist_entry, kv1):
        h1 = CC.bucket_hist_from_meta(kv1.meta_ids, regions1, pcfg)
        return jax.lax.dynamic_update_slice_in_dim(
            hist_entry, h1.astype(hist_entry.dtype), slot, axis=1)

    caches = [
        {lname: {key: (admit_hist(lcache[key],
                                  local_kv(lcache["kv"],
                                           caches1[si][lname]["kv"]))
                       if key == "hist"
                       else merge(lcache[key], caches1[si][lname][key]))
                 for key in lcache}
         for lname, lcache in stage_cache.items()}
        for si, stage_cache in enumerate(state.caches)]
    return SlotState(
        caches=caches,
        regions=CC.CacheRegions(
            pos=state.regions.pos.at[slot].set(regions1.pos[0]),
            enc_end=state.regions.enc_end.at[slot].set(regions1.enc_end[0])),
        cur_tok=state.cur_tok.at[slot].set(tok0),
        remaining=state.remaining.at[slot].set(rem),
        fill_pos=state.fill_pos, fill_len=state.fill_len,
        prompt=state.prompt)


def admit_tiered(state: SlotState, slot, phys_blocks, caches1, regions1,
                 tok0, rem, pcfg=None) -> SlotState:
    """``admit_paged`` for the tiered pool (ISSUE 6): the device side gets
    **metadata + histogram + slot-local leaves only**. The prompt's K/V
    never lands in device HBM here — the engine writes it straight into
    the HostKVPool (numpy, between chunks) and installs whatever subset
    the staging policy wants via ``tiered_stage_blocks``. ``phys_blocks``
    may cover just the solo prefill's (bucketed) capacity — later logical
    blocks get metadata exclusively through promotion, which runs before
    any position enters the retrieval region. Fetch-stat leaves pass
    through (they are chunk-scoped, zeroed at every chunk entry)."""
    def merge(pool_entry, new_entry):
        if isinstance(pool_entry, CC.PagedLayerKVCache):
            return CC.tiered_scatter_prefill_meta(pool_entry, new_entry,
                                                  phys_blocks)
        return jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small, slot, axis=1),
            pool_entry, new_entry)

    def admit_hist(hist_entry, kv1):
        h1 = CC.bucket_hist_from_meta(kv1.meta_ids, regions1, pcfg)
        return jax.lax.dynamic_update_slice_in_dim(
            hist_entry, h1.astype(hist_entry.dtype), slot, axis=1)

    caches = [
        {lname: {key: (admit_hist(lcache[key], caches1[si][lname]["kv"])
                       if key == "hist"
                       else lcache[key] if key == "fetch"
                       else merge(lcache[key], caches1[si][lname][key]))
                 for key in lcache}
         for lname, lcache in stage_cache.items()}
        for si, stage_cache in enumerate(state.caches)]
    return SlotState(
        caches=caches,
        regions=CC.CacheRegions(
            pos=state.regions.pos.at[slot].set(regions1.pos[0]),
            enc_end=state.regions.enc_end.at[slot].set(regions1.enc_end[0])),
        cur_tok=state.cur_tok.at[slot].set(tok0),
        remaining=state.remaining.at[slot].set(rem),
        fill_pos=state.fill_pos, fill_len=state.fill_len,
        prompt=state.prompt)
