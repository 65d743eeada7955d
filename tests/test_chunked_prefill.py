"""Chunked prefill fused into the decode loop (ISSUE 5): token identity
vs the solo-prefill path (``prefill_budget=0``) on both engines across
staggered admissions, ring-layer fills that wrap the sliding window,
mid-fill eviction reclaiming blocks + histograms, the fused-path
``hist == recomputed-histogram`` invariant at every mixed step, the
serving-path Pallas-kernel wiring, and the allocator fixes (deque free
list, capped ``_bucket``)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import retrieval as R
from repro.core.cache import paged_meta_view, retrieval_valid_mask
from repro.models import model as M
from repro.models import serve as SV
from repro.serving import PagedServingEngine, Request, ServingEngine
from repro.serving.engine import _bucket


def _submit_all(eng, specs, prompts):
    for i, ((_, gen), p) in enumerate(zip(specs, prompts)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=gen))
    return {r.uid: r for r in eng.run()}


def _staggered(seed=2):
    cfg = configs.smoke("qwen2-1.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    specs = [(33, 6), (48, 9), (70, 5)]
    prompts = [rng.randint(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s, _ in specs]
    return cfg, params, specs, prompts


# ------------------------------------------------- engine token identity ----
def test_chunked_matches_solo_contiguous():
    """Slot engine: chunked prefill (several budgets/chunk sizes, fills
    spanning multiple chunks and completing mid-chunk) emits exactly the
    solo-prefill engine's tokens on a staggered-admission workload, and
    reports a real TTFT for every request."""
    cfg, params, specs, prompts = _staggered()
    solo = _submit_all(
        ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4),
        specs, prompts)
    for budget, chunk in ((8, 4), (16, 4), (16, 8)):
        got = _submit_all(
            ServingEngine(cfg, params, n_max=256, max_batch=2,
                          chunk_size=chunk, prefill_budget=budget),
            specs, prompts)
        assert sorted(got) == [0, 1, 2]
        for uid, (_, gen) in enumerate(specs):
            assert got[uid].output.shape == (gen,)
            np.testing.assert_array_equal(
                got[uid].output, solo[uid].output,
                err_msg=f"request {uid} (budget={budget}, chunk={chunk})")
            assert got[uid].ttft_s > 0 and got[uid].decode_s >= 0
            assert len(got[uid].token_times) == gen


def test_chunked_matches_solo_paged_fused_and_fallback():
    """Paged engine: chunked prefill through the block tables is
    token-identical to the solo path on the fused retrieval path, the
    meta-view fallback, and under block backpressure; every block returns
    to the free list."""
    cfg, params, specs, prompts = _staggered()
    solo = _submit_all(
        ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4),
        specs, prompts)
    for fused in (True, False):
        for num_blocks in (None, 3):     # ample pool / backpressured pool
            eng = PagedServingEngine(
                cfg, params, n_max=256, max_batch=2, block_size=64,
                num_blocks=num_blocks, chunk_size=4, fused=fused,
                prefill_budget=16)
            got = _submit_all(eng, specs, prompts)
            for uid in solo:
                np.testing.assert_array_equal(
                    got[uid].output, solo[uid].output,
                    err_msg=f"request {uid} (fused={fused}, "
                            f"num_blocks={num_blocks})")
            assert len(eng._free) == eng.num_blocks


def test_chunked_ring_layers_window_wrap():
    """Local/global architecture (gemma2 smoke): ring-buffer fills stay
    identical to solo even when one chunk wraps the sliding window
    (budget 100 > window 64 — in-chunk ring aliasing must keep the last
    write per slot)."""
    cfg = configs.smoke("gemma2-27b")
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.RandomState(5)
    specs = [(90, 6), (40, 8), (130, 5)]
    prompts = [rng.randint(0, cfg.vocab_size, size=(s,)).astype(np.int32)
               for s, _ in specs]
    solo = _submit_all(
        ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4),
        specs, prompts)
    for budget in (16, 100):
        got = _submit_all(
            ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4,
                          prefill_budget=budget),
            specs, prompts)
        for uid in solo:
            np.testing.assert_array_equal(
                got[uid].output, solo[uid].output,
                err_msg=f"request {uid} (budget={budget})")


def test_chunked_unsupported_arch_raises():
    """Non-attention mixers (SSM here) still need solo prefill: asking for
    a prefill budget is a constructor-time error, not a silent fallback."""
    cfg = configs.smoke("mamba2-780m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert not SV.fill_supported(cfg)
    with pytest.raises(ValueError, match="attention and MLA mixers only"):
        ServingEngine(cfg, params, n_max=128, max_batch=1, prefill_budget=8)


# -------------------------------------------------- mid-fill eviction -------
def test_cancel_mid_fill_reclaims_blocks_and_hist():
    """cancel() while a slot is still filling: the fill stops, the slot's
    blocks return to the free list, its incremental histograms are zeroed,
    and the other in-flight request is unaffected."""
    cfg = configs.smoke("qwen2-1.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    eng = PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                             block_size=32, chunk_size=4, prefill_budget=8)
    prompts = {0: rng.randint(0, cfg.vocab_size, size=(200,)),
               1: rng.randint(0, cfg.vocab_size, size=(20,))}
    eng.submit(Request(uid=0, prompt=prompts[0].astype(np.int32),
                       max_new_tokens=8))
    eng.submit(Request(uid=1, prompt=prompts[1].astype(np.int32),
                       max_new_tokens=6))
    eng.start()
    eng.step_serve()                       # admit uid 0, chunk of filling
    eng.step_serve()
    fp = np.asarray(eng._state.fill_pos)
    assert 0 < fp[0] < 200, "expected uid 0 to still be mid-fill"
    assert len(eng._alloc[0]) > 0

    eng.cancel(0)
    while eng.pending():
        eng.step_serve()
    done = {r.uid: r for r in eng._done}
    assert sorted(done) == [0, 1]
    assert done[0].cancelled and len(done[0].output) == 0
    assert done[1].output.shape == (6,) and not done[1].cancelled
    assert len(eng._free) == eng.num_blocks          # blocks reclaimed
    for stage_cache in eng._state.caches:            # hist rows zeroed:
        for lc in stage_cache.values():              # both requests gone,
            if "hist" in lc:                         # every slot evicted
                assert (np.asarray(lc["hist"]) == 0).all()
    # uid 1 reused the cancelled slot: solo run must agree token-wise
    ref = _submit_all(
        ServingEngine(cfg, params, n_max=256, max_batch=1, chunk_size=4),
        [(20, 6)], [prompts[1].astype(np.int32)])
    np.testing.assert_array_equal(done[1].output, ref[0].output)


def test_cancel_queued_and_decoding_contiguous():
    """cancel() on the contiguous engine: queued requests are dropped,
    in-flight ones evicted with their partial output; survivors match a
    solo run."""
    cfg, params, specs, prompts = _staggered(seed=7)
    eng = ServingEngine(cfg, params, n_max=256, max_batch=2, chunk_size=4,
                        prefill_budget=8)
    for i, ((_, gen), p) in enumerate(zip(specs, prompts)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=gen))
    eng.start()
    eng.cancel(2)                           # still queued → dropped
    eng.step_serve()
    eng.cancel(0)                           # in flight (filling/decoding)
    while eng.pending():
        eng.step_serve()
    done = {r.uid: r for r in eng._done}
    assert sorted(done) == [0, 1, 2]
    assert done[2].cancelled and len(done[2].output) == 0
    assert done[0].cancelled
    solo = _submit_all(
        ServingEngine(cfg, params, n_max=256, max_batch=1, chunk_size=4),
        [specs[1]], [prompts[1]])
    np.testing.assert_array_equal(done[1].output, solo[0].output)


# -------------------------------------- hist invariant at every mixed step --
def _assert_hist_invariant(eng):
    """Every *occupied* slot's incremental histogram equals a from-scratch
    recompute over its logical metadata view at the current regions.
    Freed slots are garbage by design (cleared block table, stale
    regions, zeroed hist) — their rows are skipped, exactly like every
    mask in the serving path skips them."""
    occupied = [i for i, r in enumerate(eng._slots) if r is not None]
    if not occupied:
        return
    bt = jnp.asarray(eng._bt)
    n_log = eng.nblk * eng.block_size
    regions = eng._state.regions
    for si, stage_cache in enumerate(eng._state.caches):
        for ln, lc in stage_cache.items():
            if "hist" not in lc:
                continue
            repeat = lc["hist"].shape[0]
            for r in range(repeat):
                pool = jax.tree.map(lambda a: a[r], lc["kv"])
                ids, _, _ = paged_meta_view(pool, bt)
                valid = retrieval_valid_mask(n_log, regions,
                                             eng.cfg.pariskv)
                want = R.bucket_histogram(ids, valid[:, None, :],
                                          eng.cfg.pariskv.num_centroids())
                np.testing.assert_array_equal(
                    np.asarray(lc["hist"][r])[occupied],
                    np.asarray(want)[occupied],
                    err_msg=f"hist invariant broke (stage {si} {ln} "
                            f"repeat {r})")


def test_fill_hist_invariant_every_mixed_step():
    """Drive the paged engine one mixed step at a time (chunk_size=1):
    after *every* step — mid-fill, at fill completion, across admissions
    and evictions — each slot's incremental bucket histogram equals the
    histogram recomputed from the logical metadata view over
    [sink, enc_end). This is the exactness bar that lets the fused path
    skip the per-query O(n) scatter-add even while a prompt is mid-fill."""
    cfg, params, specs, prompts = _staggered(seed=11)
    eng = PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                             block_size=32, chunk_size=1, prefill_budget=8)
    for i, ((_, gen), p) in enumerate(zip(specs, prompts)):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=gen))
    eng.start()
    steps = 0
    while eng.pending():
        eng.step_serve()
        steps += 1
        _assert_hist_invariant(eng)
        assert steps < 500, "serving loop did not converge"
    assert steps > 20          # plenty of mid-fill steps were checked


# ------------------------------------------------ kernel wiring (serving) --
def test_fused_retrieval_kernel_wiring_matches_twins():
    """retrieve_paged_fused(use_kernels=True) — the path serving takes on
    compiled-kernel platforms — selects exactly the jnp twins' coarse
    scores, candidate sets, winners and physical rows (scores to float
    tolerance: the Pallas rerank accumulates in a different order)."""
    from repro.core import encode_query, retrieve_paged_fused
    from test_paged_fused import CFG, D, G, H, SIGNS, _build_paged

    bs, nblk, num_blocks, b = 32, 4, 12, 2
    n_log = bs * nblk
    pool, btj, hist, regions = _build_paged(
        b, bs, nblk, num_blocks, np.asarray([n_log, 70], np.int32), seed=3)
    q = jax.random.normal(jax.random.PRNGKey(4), (b, G, H // G, D))
    qt = encode_query(q, CFG, SIGNS)
    C = CFG.candidate_count(n_log)

    twin = retrieve_paged_fused(pool, btj, qt, hist, regions.enc_end, CFG,
                                C, CFG.top_k, use_kernels=False)
    kern = retrieve_paged_fused(pool, btj, qt, hist, regions.enc_end, CFG,
                                C, CFG.top_k, use_kernels=True)
    np.testing.assert_array_equal(np.asarray(kern.coarse_scores),
                                  np.asarray(twin.coarse_scores))
    np.testing.assert_array_equal(np.asarray(kern.cand_indices),
                                  np.asarray(twin.cand_indices))
    np.testing.assert_array_equal(np.asarray(kern.indices),
                                  np.asarray(twin.indices))
    np.testing.assert_array_equal(np.asarray(kern.phys_rows),
                                  np.asarray(twin.phys_rows))
    np.testing.assert_allclose(np.asarray(kern.scores),
                               np.asarray(twin.scores), rtol=1e-4,
                               atol=1e-4)


def test_fused_retrieval_env_forces_twins(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 keeps the serving path on the jnp twins
    (use_kernels=None resolves to False), matching the kernels' global
    interpret policy."""
    from repro.kernels import resolve_interpret
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(None) is True      # → twins in serving
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is False     # → kernels in serving


# ------------------------------------------------------- allocator fixes ---
def test_bucket_cap_applies_before_doubling():
    """_bucket caps at n_max before the doubling loop: oversized floors
    (or n beyond the cap) return the cap instead of looping past it."""
    assert _bucket(70) == 128
    assert _bucket(70, cap=96) == 96
    assert _bucket(70, cap=256) == 128
    assert _bucket(200, cap=96) == 96          # n beyond cap: immediate
    assert _bucket(5, floor=1024, cap=96) == 96  # oversized floor clamped
    assert _bucket(5) == 8


def test_paged_free_list_is_deque():
    """The paged allocator's free list is a deque (O(1) _take_block — the
    old list.pop(0) shuffled the whole free list per allocation)."""
    cfg = configs.smoke("qwen2-1.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, n_max=256, max_batch=1,
                             block_size=64)
    assert isinstance(eng._free, collections.deque)
    assert list(eng._free) == list(range(eng.num_blocks))
