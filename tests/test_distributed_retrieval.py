"""Exactness of the hierarchical (distributed) retrieval merge used by the
§Perf shard_map optimization (launch/hillclimb.py E1/E2), plus MLA parity.

The sharded algorithm: each sequence shard takes its local top-k by the
RSQ-IP estimate, the per-shard winners are unioned (all-gather) and the
global top-k is taken from the union. Exact because every member of the
true global top-k is in its own shard's top-k. Simulated here by reshaping
— no mesh needed, same math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:          # optional dev dep — seeded fallback
    HAS_HYPOTHESIS = False

from repro.core import (ParisKVConfig, encode_keys, encode_query, retrieve,
                        srht)

CFG = ParisKVConfig()
D = 128
SIGNS = jnp.asarray(srht.rademacher_signs(CFG.padded_dim(D), CFG.srht_seed))


def _check_hierarchical_topk_merge_is_exact(seed, n_shards):
    n, k = 2048, 50
    n_loc = n // n_shards
    scores = jax.random.normal(jax.random.PRNGKey(seed), (n,))

    # global reference
    _, ref_idx = jax.lax.top_k(scores, k)

    # sharded: local top-k per shard, merge the union
    local = scores.reshape(n_shards, n_loc)
    loc_val, loc_idx = jax.lax.top_k(local, k)           # (shards, k)
    glob_idx = loc_idx + jnp.arange(n_shards)[:, None] * n_loc
    union_val = loc_val.reshape(-1)
    union_idx = glob_idx.reshape(-1)
    _, pos = jax.lax.top_k(union_val, k)
    got_idx = union_idx[pos]

    assert set(np.asarray(got_idx).tolist()) == set(
        np.asarray(ref_idx).tolist())


if HAS_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8, 16]))
    @settings(max_examples=10, deadline=None)
    def test_hierarchical_topk_merge_is_exact(seed, n_shards):
        _check_hierarchical_topk_merge_is_exact(seed, n_shards)
else:
    @pytest.mark.parametrize("seed,n_shards", [(0, 4), (1, 8), (2, 16)])
    def test_hierarchical_topk_merge_is_exact(seed, n_shards):
        _check_hierarchical_topk_merge_is_exact(seed, n_shards)


def test_sharded_retrieve_matches_global():
    """Running retrieve() per sequence shard and merging by estimate equals
    global retrieve() on the same keys (up to estimate ties)."""
    n, n_shards, k = 4096, 4, 32
    n_loc = n // n_shards
    keys = jax.random.normal(jax.random.PRNGKey(0), (n, D)) \
        * jnp.linspace(2, .1, D)
    q = keys[123] + 0.2 * jax.random.normal(jax.random.PRNGKey(1), (D,))
    meta = encode_keys(keys, CFG, SIGNS)
    qt = encode_query(q, CFG, SIGNS)
    res_g = retrieve(meta, qt, jnp.ones((n,), bool), CFG, 512, k)

    per_shard = []
    for s in range(n_shards):
        sl = slice(s * n_loc, (s + 1) * n_loc)
        meta_s = jax.tree.map(lambda a: a[sl], meta)
        r = retrieve(meta_s, qt, jnp.ones((n_loc,), bool), CFG,
                     512 // n_shards, k)
        per_shard.append((r.scores, r.indices + s * n_loc))
    union_val = jnp.concatenate([v for v, _ in per_shard])
    union_idx = jnp.concatenate([i for _, i in per_shard])
    _, pos = jax.lax.top_k(union_val, k)
    got = set(np.asarray(union_idx[pos]).tolist())
    want = set(np.asarray(res_g.indices).tolist())
    # Stage-I candidate pools differ (local vs global β budget) so allow a
    # small symmetric difference; the heavy overlap is the invariant.
    assert len(got & want) >= int(0.8 * k), (len(got & want), k)


def test_mla_decode_matches_train_logits():
    """MLA absorbed-form decode ≈ decompressed train forward (same layer)."""
    from repro import configs
    from repro.models import mla as MLA
    from repro.core import cache as CC
    cfg = configs.smoke("deepseek-v2-lite-16b")
    p = MLA.init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, S = 2, 40
    x = jax.random.normal(jax.random.PRNGKey(1), (b, S, cfg.d_model)) * 0.3
    positions = jnp.broadcast_to(jnp.arange(S), (b, S))
    y_train = MLA.mla_train(p, x, cfg, positions)

    signs = jnp.asarray(srht.rademacher_signs(
        cfg.pariskv.padded_dim(cfg.retrieval_dim()), cfg.pariskv.srht_seed))
    mc = CC.init_layer_cache(b, 128, 1, cfg.retrieval_dim(), cfg.pariskv,
                             jnp.float32, values=False)
    _, lat = MLA.mla_prefill(p, x[:, :S - 1], cfg, positions[:, :S - 1])
    mc, _ = CC.prefill_write(mc, lat[:, :, None], None, cfg.pariskv, signs)
    regions = CC.CacheRegions(pos=jnp.int32(S - 2), enc_end=jnp.int32(0))
    # dense decode (use_pariskv=False): must match the train row exactly
    y_dec, _ = MLA.mla_decode(p, x[:, S - 1], mc, regions, cfg, signs,
                              num_candidates=64, use_pariskv=False)
    np.testing.assert_allclose(np.asarray(y_dec),
                               np.asarray(y_train[:, S - 1]),
                               rtol=2e-2, atol=2e-2)
