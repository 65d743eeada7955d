"""Fused paged retrieval (ISSUE 4): identity with the meta-view reference
across drift states, the incremental-histogram invariant under
append/promote/evict sequences, engine-level token identity of the fused
vs fallback paths, and the new/changed kernel entry points
(collision_paged_pallas, rerank_paged_kernel, tail padding, interpret
autodetect)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CacheRegions, ParisKVConfig, bucket_hist_from_meta,
                        encode_query, retrieve_paged, retrieve_paged_fused,
                        retrieval_valid_mask, srht)
from repro.core import retrieval as R
from repro.core.cache import (PagedLayerKVCache, init_layer_cache,
                              init_paged_cache, paged_decode_append,
                              paged_maybe_promote_hist, paged_meta_view,
                              paged_scatter_prefill, prefill_write)
from repro.core.encode import KeyMetadata

CFG = ParisKVConfig(sink_size=16, local_size=64, update_interval=32,
                    top_k=32, min_candidates=64)
D, G, H = 64, 2, 4
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


def _reference_retrieval(pool, btj, qt, regions, n_log, bs, C):
    ids, codes, w = paged_meta_view(pool, btj)
    meta_b = jax.tree.map(lambda a: a[:, :, None],
                          KeyMetadata(ids, codes, w))
    valid = retrieval_valid_mask(n_log, regions, CFG)
    valid = jnp.broadcast_to(valid[:, None, None, :],
                             (btj.shape[0], G, 1, n_log))
    return retrieve_paged(meta_b, qt, valid, CFG, C, CFG.top_k, btj, bs)


def _recomputed_hist(pool, btj, regions, n_log):
    ids, _, _ = paged_meta_view(pool, btj)
    valid = retrieval_valid_mask(n_log, regions, CFG)
    return R.bucket_histogram(ids, valid[:, None, :], CFG.num_centroids())


def test_fused_identity_and_hist_invariant_across_drift():
    """Decode 80 steps (both rows promote — post-drift metadata): at every
    step the incremental histogram equals a from-scratch recompute, and at
    every checkpoint retrieve_paged_fused returns exactly retrieve_paged's
    winners, scores, candidates and coarse scores."""
    bs, nblk, num_blocks, b = 32, 8, 20, 2
    n_log = bs * nblk
    lens = [128, 40]
    pool, btj, hist, regions = _build_paged(b, bs, nblk, num_blocks,
                                            np.asarray(lens, np.int32))
    C = CFG.candidate_count(n_log)
    rng = jax.random.PRNGKey(2)
    promotions = 0
    for step in range(80):
        rng, sub, qr = jax.random.split(rng, 3)
        kt = jax.random.normal(sub, (b, G, D))
        pool = paged_decode_append(pool, btj, kt, kt, regions.pos + 1)
        regions = regions._replace(pos=regions.pos + 1)
        enc_before = np.asarray(regions.enc_end).copy()
        pool, hist, regions = paged_maybe_promote_hist(
            pool, hist, btj, regions, CFG, SIGNS)
        promotions += int((np.asarray(regions.enc_end) != enc_before).any())

        np.testing.assert_array_equal(
            np.asarray(hist),
            np.asarray(_recomputed_hist(pool, btj, regions, n_log)),
            err_msg=f"hist invariant broke at step {step}")

        if step % 16 == 0 or step == 79:
            q = jax.random.normal(qr, (b, G, H // G, D))
            qt = encode_query(q, CFG, SIGNS)
            ref = _reference_retrieval(pool, btj, qt, regions, n_log, bs, C)
            got = retrieve_paged_fused(pool, btj, qt, hist, regions.enc_end,
                                       CFG, C, CFG.top_k)
            np.testing.assert_array_equal(np.asarray(got.coarse_scores),
                                          np.asarray(ref.coarse_scores))
            np.testing.assert_array_equal(np.asarray(got.cand_indices),
                                          np.asarray(ref.cand_indices))
            np.testing.assert_array_equal(np.asarray(got.indices),
                                          np.asarray(ref.indices))
            np.testing.assert_array_equal(np.asarray(got.scores),
                                          np.asarray(ref.scores))
            np.testing.assert_array_equal(np.asarray(got.phys_rows),
                                          np.asarray(ref.phys_rows))
    assert promotions >= 2, "test never exercised post-promotion drift"


def test_hist_invariant_under_evict_and_readmit():
    """Evicting a row (zeroed blocks + zeroed hist) and re-admitting a new
    request into it restores the invariant; the surviving row's histogram
    is untouched throughout."""
    from repro.core.cache import paged_clear_blocks
    bs, nblk, num_blocks, b = 32, 8, 20, 2
    n_log = bs * nblk
    pool, btj, hist, regions = _build_paged(
        b, bs, nblk, num_blocks, np.asarray([128, 96], np.int32))
    keep = np.asarray(hist[1]).copy()

    # evict row 0: zero its blocks and hist row (engine _evict_impl does;
    # paged_clear_blocks expects stage-stacked (repeat, nb, ...) leaves)
    pool = jax.tree.map(lambda a: a[0], PagedLayerKVCache(*paged_clear_blocks(
        PagedLayerKVCache(*jax.tree.map(lambda a: a[None], pool)), btj[0])))
    hist = hist.at[0].set(0)
    assert (np.asarray(hist[0]) == 0).all()
    np.testing.assert_array_equal(np.asarray(hist[1]), keep)

    # re-admit a different prompt into row 0's blocks
    c1 = init_layer_cache(1, n_log, G, D, CFG)
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 64, G, D))
    c1, r1 = prefill_write(c1, k, k, CFG, SIGNS,
                           lengths=jnp.asarray([64]))
    stacked = paged_scatter_prefill(
        PagedLayerKVCache(*jax.tree.map(lambda a: a[None], pool)),
        jax.tree.map(lambda a: a[None], c1), btj[0])
    pool = jax.tree.map(lambda a: a[0], stacked)
    hist = hist.at[0].set(bucket_hist_from_meta(c1.meta_ids, r1, CFG)[0])
    regions = CacheRegions(
        pos=regions.pos.at[0].set(r1.pos[0]),
        enc_end=regions.enc_end.at[0].set(r1.enc_end[0]))

    np.testing.assert_array_equal(
        np.asarray(hist),
        np.asarray(_recomputed_hist(pool, btj, regions, n_log)))
    np.testing.assert_array_equal(np.asarray(hist[1]), keep)


def test_paged_engine_fused_token_identity():
    """PagedServingEngine with the fused path (default) is token-identical
    to the meta-view fallback (fused=False) and to the contiguous slot
    engine on a staggered-admission workload."""
    from repro import configs
    from repro.models import model as M
    from repro.serving import PagedServingEngine, Request, ServingEngine

    cfg = configs.smoke("qwen2-1.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(2)
    specs = [(33, 6), (48, 9), (70, 5)]
    prompts = [rng.randint(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s, _ in specs]

    def run(make):
        eng = make()
        for i, ((_, gen), p) in enumerate(zip(specs, prompts)):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=gen))
        return {r.uid: r for r in eng.run()}

    ref = run(lambda: ServingEngine(cfg, params, n_max=256, max_batch=2,
                                    chunk_size=4))
    for fused in (True, False):
        got = run(lambda: PagedServingEngine(
            cfg, params, n_max=256, max_batch=2, block_size=64,
            chunk_size=4, fused=fused))
        for uid, (_, gen) in enumerate(specs):
            np.testing.assert_array_equal(
                got[uid].output, ref[uid].output,
                err_msg=f"request {uid} (fused={fused})")


# ------------------------------------------------------- kernel twins ------
def test_collision_paged_kernel_matches_twin_and_oracle():
    """collision_paged_pallas (scalar-prefetch, block-table-indirect) ==
    the pure-jnp twin collision_scores_paged == the materialized oracle."""
    from repro.core import centroids
    from repro.kernels.collision import collision_scores_paged_kernel
    from repro.kernels.collision.ref import collision_scores_paged_ref

    bs, nblk, num_blocks, b = 32, 4, 12, 2
    n_log = bs * nblk
    pool, btj, hist, regions = _build_paged(
        b, bs, nblk, num_blocks, np.asarray([n_log, 70], np.int32), seed=3)
    q = jax.random.normal(jax.random.PRNGKey(4), (b, G, H // G, D))
    qt = encode_query(q, CFG, SIGNS)
    enc = jnp.asarray(regions.enc_end, jnp.int32)

    twin = R.collision_scores_paged(pool.meta_ids, btj, qt.q_sub, hist,
                                    enc, CFG)
    cs = centroids.centroid_scores(qt.q_sub, CFG.m)
    n_valid = jnp.maximum(enc - CFG.sink_size, 0)
    table = R.tier_weight_table(cs, hist[:, :, None],
                                n_valid[:, None, None], CFG)
    got = collision_scores_paged_kernel(pool.meta_ids, btj, table, enc,
                                        CFG.sink_size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))

    # unmasked oracle agreement at the valid positions
    for i in range(b):
        want = collision_scores_paged_ref(pool.meta_ids, btj[i], table[i])
        e = int(enc[i])
        np.testing.assert_array_equal(
            np.asarray(got[i, :, :, CFG.sink_size:e]),
            np.asarray(want)[:, :, CFG.sink_size:e])


def test_rerank_paged_kernel_matches_ref():
    """rerank_paged_kernel (physical-row gather + fused unpack/score) ==
    rerank_ref on the gathered candidates."""
    from repro.kernels.rerank import rerank_paged_kernel
    from repro.kernels.rerank.ref import rerank_ref

    bs, nblk, num_blocks = 32, 4, 8
    n_log = bs * nblk
    pool, btj, _, regions = _build_paged(
        1, bs, nblk, num_blocks, np.asarray([n_log], np.int32), seed=5)
    rng = np.random.RandomState(5)
    Cn = 48
    lidx = rng.choice(n_log, Cn, replace=False).astype(np.int32)
    phys = np.asarray(btj[0])[lidx // bs] * bs + lidx % bs
    phys = jnp.broadcast_to(jnp.asarray(phys)[None], (G, Cn))
    q = jax.random.normal(jax.random.PRNGKey(6), (1, G, D))
    qt = encode_query(q, CFG, SIGNS)

    got = rerank_paged_kernel(pool.meta_codes, pool.meta_w,
                              phys[None, :, None], qt.q_sub[:, :, None],
                              qt.q_norm[:, :, None], m=CFG.m,
                              block_c=32)[0, :, 0]
    ids_v, codes_v, w_v = paged_meta_view(pool, btj)
    want = rerank_ref(codes_v[0][:, lidx], w_v[0][:, lidx], qt.q_sub[0],
                      qt.q_norm[0][:, None], CFG.m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_collision_pallas_arbitrary_n_no_caller_padding():
    """Direct collision_pallas calls no longer require n % block_n == 0:
    the tail is padded and masked inside the kernel wrapper."""
    from repro.kernels.collision.collision import collision_pallas
    from repro.kernels.collision.ref import collision_scores_ref

    rng = np.random.RandomState(7)
    for n in (100, 1000, 1025):
        ids = jnp.asarray(rng.randint(0, 256, size=(n, 8)), jnp.uint8)
        table = jnp.asarray(rng.randint(0, 7, size=(8, 256)), jnp.int32)
        got = collision_pallas(ids, table, block_n=256)
        assert got.shape == (n,)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(collision_scores_ref(ids, table)))


def test_resolve_interpret_env_override(monkeypatch):
    """Platform autodetect with env override: explicit arg > env > backend."""
    from repro import kernels

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert kernels.resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert kernels.resolve_interpret(True) is True
    assert kernels.resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert kernels.resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert kernels.resolve_interpret(None) is False
    # explicit argument still wins over the env
    assert kernels.resolve_interpret(True) is True


def test_hist_sample_knob_is_exactness_note():
    """The fused path ignores hist_sample (its histogram is exact by
    construction); with hist_sample=0 the meta-view path and fused path
    agree — documented behaviour, pinned here."""
    bs, nblk, num_blocks = 32, 4, 8
    n_log = bs * nblk
    pool, btj, hist, regions = _build_paged(
        1, bs, nblk, num_blocks, np.asarray([n_log], np.int32), seed=8)
    q = jax.random.normal(jax.random.PRNGKey(8), (1, G, H // G, D))
    qt = encode_query(q, CFG, SIGNS)
    C = CFG.candidate_count(n_log)
    ref = _reference_retrieval(pool, btj, qt, regions, n_log, bs, C)
    got = retrieve_paged_fused(pool, btj, qt, hist, regions.enc_end, CFG,
                               C, CFG.top_k)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(ref.indices))
