"""Sharded serving on a device mesh (ISSUE 8): shard-local Stage I + global
top-C merge bit-identical to single-device ``retrieve_paged_fused`` across an
80-step drift loop; 4-way-sharded ``PagedServingEngine`` token-identical to
the single-device engine under staggered admission, mid-flight cancel and
evict/readmit (fused + fallback + chunked prefill); structured rejection of
uneven-head meshes, mesh+offload and mesh+MLA.

Runs on CPU by forcing four host devices — the flag must land before jax
initialises, so this module prepends it when jax is not yet imported and
skips (rather than fails) when another test module already pinned a
single-device runtime.
"""
import os
import sys

_FLAG = "--xla_force_host_platform_device_count=4"
if "jax" not in sys.modules and _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " " + _FLAG).strip()

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import (CacheRegions, ParisKVConfig, bucket_hist_from_meta,
                        encode_query, retrieve_paged_fused, srht)
from repro.core import retrieval as R
from repro.core.cache import (PagedLayerKVCache, init_layer_cache,
                              init_paged_cache, paged_decode_append,
                              paged_maybe_promote_hist, paged_scatter_prefill,
                              prefill_write)
from repro.models import layers as L
from repro.models import model as M
from repro.models import serve as SV
from repro.serving.engine import PagedServingEngine, Request, ServingEngine

P = jax.sharding.PartitionSpec
SHARDS = 4

needs_mesh = pytest.mark.skipif(
    jax.device_count() < SHARDS,
    reason=f"needs {SHARDS} devices (XLA_FLAGS={_FLAG})")

CFG = ParisKVConfig(sink_size=16, local_size=64, update_interval=32,
                    top_k=32, min_candidates=64)
D, G, H = 64, 4, 8
SIGNS = jnp.asarray(srht.rademacher_signs(CFG.padded_dim(D), CFG.srht_seed))


def _build_paged(b, bs, nblk, num_blocks, lens, seed=0):
    """Prefill ``b`` rows into a shuffled-block pool + matching hist."""
    n_max = bs * nblk
    S = int(max(np.asarray(lens)))
    k = jax.random.normal(jax.random.PRNGKey(seed), (b, S, G, D)) \
        * jnp.linspace(2.0, 0.2, D)
    v = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, S, G, D))
    pool = init_paged_cache(num_blocks, bs, G, D, CFG)
    perm = np.random.RandomState(seed).permutation(num_blocks)
    bt = np.stack([perm[i * nblk:(i + 1) * nblk] for i in range(b)]
                  ).astype(np.int32)
    regions = None
    hists = []
    for i in range(b):
        c1 = init_layer_cache(1, n_max, G, D, CFG)
        c1, r1 = prefill_write(c1, k[i:i + 1], v[i:i + 1], CFG, SIGNS,
                               lengths=jnp.asarray(lens[i:i + 1]))
        stacked = paged_scatter_prefill(
            PagedLayerKVCache(*jax.tree.map(lambda a: a[None], pool)),
            jax.tree.map(lambda a: a[None], c1), jnp.asarray(bt[i]))
        pool = jax.tree.map(lambda a: a[0], stacked)
        hists.append(bucket_hist_from_meta(c1.meta_ids, r1, CFG))
        regions = (r1 if regions is None else CacheRegions(
            pos=jnp.concatenate([regions.pos, r1.pos]),
            enc_end=jnp.concatenate([regions.enc_end, r1.enc_end])))
    return pool, jnp.asarray(bt), jnp.concatenate(hists), regions


def _sharded_retrieve_fn(mesh, C):
    """shard_map-wrapped shard-local fused retrieval + global merge, with
    the pool/metadata/histogram/query partitioned on the KV-head axis and
    block tables + encoded-region bounds replicated (the engine's layout)."""
    pool_specs = PagedLayerKVCache(
        k=P(None, None, "kv"), v=P(None, None, "kv"),
        meta_ids=P(None, "kv"), meta_codes=P(None, "kv"),
        meta_w=P(None, "kv"))
    qt_specs = jax.tree.map(lambda _: P(None, "kv"),
                            encode_query(jnp.zeros((1, G, H // G, D)),
                                         CFG, SIGNS))
    out_specs = jax.tree.map(
        lambda _: P(),
        R.PagedRetrievalResult(*[0] * len(R.PagedRetrievalResult._fields)))
    return jax.jit(L.shard_map_compat(
        lambda pool, bt, qt, hist, enc: R.retrieve_paged_fused_sharded(
            pool, bt, qt, hist, enc, CFG, C, CFG.top_k, axis_name="kv"),
        mesh=mesh,
        in_specs=(pool_specs, P(), qt_specs, P(None, "kv"), P()),
        out_specs=out_specs))


@needs_mesh
def test_sharded_merge_bit_identical_across_drift():
    """80 decode steps with promotions: at every checkpoint the shard-local
    Stage I + global top-C merge returns exactly the single-device fused
    winners, scores, candidates, coarse scores and physical rows."""
    bs, nblk, num_blocks, b = 32, 8, 20, 2
    n_log = bs * nblk
    lens = [128, 40]
    pool, btj, hist, regions = _build_paged(b, bs, nblk, num_blocks,
                                            np.asarray(lens, np.int32))
    C = CFG.candidate_count(n_log)
    mesh = jax.make_mesh((SHARDS,), ("kv",))
    sharded = _sharded_retrieve_fn(mesh, C)

    @jax.jit
    def step_fn(pool, hist, regions, kt):
        pool = paged_decode_append(pool, btj, kt, kt, regions.pos + 1)
        regions = regions._replace(pos=regions.pos + 1)
        return paged_maybe_promote_hist(pool, hist, btj, regions, CFG, SIGNS)

    ref_fn = jax.jit(lambda pool, qt, hist, enc: retrieve_paged_fused(
        pool, btj, qt, hist, enc, CFG, C, CFG.top_k))

    rng = jax.random.PRNGKey(2)
    promotions = 0
    for step in range(80):
        rng, sub, qr = jax.random.split(rng, 3)
        kt = jax.random.normal(sub, (b, G, D))
        enc_before = np.asarray(regions.enc_end).copy()
        pool, hist, regions = step_fn(pool, hist, regions, kt)
        promotions += int((np.asarray(regions.enc_end) != enc_before).any())

        if step % 16 == 0 or step == 79:
            q = jax.random.normal(qr, (b, G, H // G, D))
            qt = encode_query(q, CFG, SIGNS)
            ref = ref_fn(pool, qt, hist, regions.enc_end)
            got = sharded(pool, btj, qt, hist, regions.enc_end)
            for field in R.PagedRetrievalResult._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, field)),
                    np.asarray(getattr(ref, field)),
                    err_msg=f"{field} diverged at step {step}")
    assert promotions >= 2, "test never exercised post-promotion drift"


# ---------------------------------------------------------------- engines --
def _prompt(rng, n, vocab):
    return rng.randint(0, vocab, size=(n,)).astype(np.int32)


def _run_engine(cfg, params, specs, cancel_uid=None, **kw):
    eng = PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                             block_size=64, chunk_size=4, **kw)
    rng = np.random.RandomState(7)
    for i, (pl, mn) in enumerate(specs):
        eng.submit(Request(uid=i, prompt=_prompt(rng, pl, cfg.vocab_size),
                           max_new_tokens=mn))
    if cancel_uid is not None:
        eng.cancel(cancel_uid)
    return {r.uid: np.asarray(r.output) for r in eng.run()}


@needs_mesh
@pytest.mark.parametrize("kw", [
    {}, {"fused": False}, {"prefill_budget": 8},
    {"prefill_budget": 8, "share_prefixes": True},
], ids=["fused", "fallback", "chunked_prefill", "prefix_share"])
def test_sharded_engine_token_identity(kw):
    """4-way-sharded engine emits exactly the single-device tokens under
    staggered admission and evict/readmit (3 requests through 2 slots)."""
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    specs = [(33, 6), (48, 9), (70, 5)]
    ref = _run_engine(cfg, params, specs, **kw)
    got = _run_engine(cfg, params, specs, mesh_shards=SHARDS, **kw)
    assert set(got) == set(ref)
    for uid in ref:
        np.testing.assert_array_equal(got[uid], ref[uid], err_msg=f"uid {uid}")


@needs_mesh
def test_sharded_engine_cancel_identity():
    """Mid-flight cancel reclaims the slot identically on both engines: the
    surviving requests' tokens match and the cancelled uid's output agrees."""
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    specs = [(33, 24), (48, 9), (70, 5)]
    ref = _run_engine(cfg, params, specs, cancel_uid=0)
    got = _run_engine(cfg, params, specs, cancel_uid=0, mesh_shards=SHARDS)
    assert set(got) == set(ref)
    for uid in ref:
        np.testing.assert_array_equal(got[uid], ref[uid], err_msg=f"uid {uid}")


@needs_mesh
def test_sharded_matches_unpaged_reference():
    """Sharded paged serving also matches the contiguous single-device
    engine end to end (the tier-1 ground truth, not just paged-vs-paged)."""
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    specs = [(33, 6), (48, 9)]
    eng = ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4)
    rng = np.random.RandomState(7)
    for i, (pl, mn) in enumerate(specs):
        eng.submit(Request(uid=i, prompt=_prompt(rng, pl, cfg.vocab_size),
                           max_new_tokens=mn))
    ref = {r.uid: np.asarray(r.output) for r in eng.run()}
    got = _run_engine(cfg, params, specs, mesh_shards=SHARDS)
    for uid in ref:
        np.testing.assert_array_equal(got[uid], ref[uid], err_msg=f"uid {uid}")


# ----------------------------------------------------------- failure edges --
def test_uneven_head_mesh_rejected():
    """A mesh that does not divide num_kv_heads is rejected up front with an
    actionable error, not silently truncated."""
    cfg = configs.smoke("qwen2-1.5b")          # num_kv_heads=2
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="num_kv_heads"):
        PagedServingEngine(cfg, params, n_max=128, max_batch=1,
                           block_size=64, mesh_shards=4)


def test_mesh_plus_offload_rejected():
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(SV.UnsupportedShardedConfig, match="offload"):
        PagedServingEngine(cfg, params, n_max=128, max_batch=1,
                           block_size=64, offload=True, mesh_shards=2)


@needs_mesh
def test_mesh_plus_mla_rejected():
    cfg = configs.smoke("deepseek-v2-lite-16b")
    assert SV.sharded_support_reason(cfg) is not None
    with pytest.raises(SV.UnsupportedShardedConfig, match="mla"):
        PagedServingEngine(cfg, None, n_max=128, max_batch=1,
                           block_size=64, mesh_shards=2)


def test_missing_devices_hint():
    """Asking for more shards than devices names the XLA_FLAGS escape hatch."""
    import dataclasses
    cfg = configs.smoke("stablelm-1.6b")
    too_many = jax.device_count() * 2
    cfg = dataclasses.replace(cfg, num_heads=too_many,
                              num_kv_heads=too_many)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        PagedServingEngine(cfg, None, n_max=128, max_batch=1,
                           block_size=64, mesh_shards=too_many)


@needs_mesh
def test_sharded_chunk_names_every_decode_phase_once():
    """The shard_map'd decode chunk (with chunked prefill) carries every
    phase scope of core.phases, none nested in another."""
    from test_phase_scopes import check_scopes, chunk_op_names
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                             block_size=32, chunk_size=4, prefill_budget=8,
                             mesh_shards=SHARDS)
    check_scopes(chunk_op_names(eng), fill=True)
