"""DeepSeek-V2-style MLA + MoE through the serving engines: YaRN rope,
the paged latent pool, chunked fill, and the support reasons of the paths
that do not take MLA yet."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import cache as CC
from repro.core.config import YarnRope
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import model as M
from repro.models import serve as SV
from repro.serving import PagedServingEngine, Request, ServingEngine


# ------------------------------------------------------------------ YaRN ----
def test_yarn_frequencies_and_softmax_scale_are_the_published_formula():
    """d 64, θ 1e4, factor 40 over 4096 positions, β 32 / 1: pairs up to
    floor(corr(32)) = 10 keep θ^(-2i/d), pairs from ceil(corr(1)) = 23 on
    take it / 40, a linear ramp between; the scale is 192^-0.5 ·
    mscale(40, 0.707)², mscale = 0.1 · 0.707 · ln 40 + 1."""
    cfg = configs.get("deepseek-v2-lite-16b")
    inv = L.yarn_inv_freq(64, 1e4, cfg.rope_scaling)
    want = {0: 1.0, 9: 0.0749894231557846, 10: 0.05623413249850273,
            11: 0.039006926119327545, 16: 0.005499999970197678,
            22: 0.00017782794020604342, 23: 3.333803761051968e-05,
            31: 3.3338035336782923e-06}
    for i, v in want.items():
        assert inv[i] == pytest.approx(v, rel=1e-6), i
    assert L.yarn_mscale(40, 0.707) == pytest.approx(1.2608037774058554,
                                                     rel=1e-12)
    assert MLA.softmax_scale(cfg) == pytest.approx(0.11472138679292611,
                                                   rel=1e-12)


def test_yarn_at_factor_one_is_plain_rope():
    ys = YarnRope(factor=1.0, original_max_position_embeddings=4096,
                  mscale=0.707, mscale_all_dim=0.707)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64))
    pos = jnp.broadcast_to(jnp.arange(100, 109), (2, 9))
    np.testing.assert_allclose(np.asarray(L.rope(x, pos, 1e4, ys)),
                               np.asarray(L.rope(x, pos, 1e4)),
                               rtol=1e-5, atol=1e-5)
    assert MLA.softmax_scale(configs.smoke("deepseek-v2-lite-16b")) \
        == pytest.approx((32 + 32) ** -0.5 * L.yarn_mscale(40, 0.707) ** 2)


# --------------------------------------------------------------- serving ----
def _model():
    """The smoke config with weights spread wide enough that greedy
    decoding does not collapse onto one token."""
    cfg = configs.smoke("deepseek-v2-lite-16b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    leaves = [a * 10 if a.ndim >= 2 and a.shape[-1] > 1
              else a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
              for a, k in zip(leaves, keys)]
    return cfg, jax.tree.unflatten(tree, leaves)


def _serve(eng, prompts, n_new, audit=False):
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.start()
    while eng.pending():
        eng.step_serve()
        if audit:
            eng.verify_invariants()
    return {r.uid: np.asarray(r.output) for r in reqs}


@pytest.mark.parametrize("budget", [0, 16])
def test_paged_latent_pool_is_token_identical_to_the_slot_engine(budget):
    """The paged engine (latent pool, block tables, fused retrieval over
    the incremental histogram) serves the same tokens as the contiguous
    slot engine, with solo prefill (admit_paged installs the latent) and
    with chunked fill. The 150-token prompt and the 40 decoded tokens
    pass several promotions; the hist invariant is audited between
    steps."""
    cfg, params = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=(n,)) for n in (150, 40)]
    paged = _serve(PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                                      block_size=32, chunk_size=4,
                                      prefill_budget=budget),
                   prompts, 40, audit=True)
    slots = _serve(ServingEngine(cfg, params, n_max=256, max_batch=2,
                                 chunk_size=4, prefill_budget=budget),
                   prompts, 40)
    for uid in slots:
        np.testing.assert_array_equal(paged[uid], slots[uid])
    assert len(set(paged[0].tolist())) > 5         # not a collapsed model


def test_chunked_fill_matches_solo_prefill():
    """The absorbed-form chunked fill (online softmax over latent blocks)
    serves the tokens of the decompressed solo prefill."""
    cfg, params = _model()
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab_size, size=(100,))
    solo = _serve(ServingEngine(cfg, params, n_max=256, max_batch=1,
                                chunk_size=4), [prompt], 8)
    fill = _serve(ServingEngine(cfg, params, n_max=256, max_batch=1,
                                chunk_size=4, prefill_budget=24), [prompt], 8)
    np.testing.assert_array_equal(solo[0], fill[0])


def test_shared_latent_prefix_serves_the_same_tokens():
    """Prefix sharing maps a live request's latent blocks into a second
    request's table (its histogram rebuilt from their metadata) and fills
    only the suffix: the tokens are those of an unshared run."""
    cfg, params = _model()
    rng = np.random.RandomState(3)
    base = rng.randint(0, cfg.vocab_size, size=(100,))
    prompts = [np.concatenate([base, rng.randint(0, cfg.vocab_size,
                                                 size=(n,))])
               for n in (20, 30)]
    kw = dict(n_max=256, max_batch=2, block_size=32, chunk_size=4,
              prefill_budget=16)
    eng = PagedServingEngine(cfg, params, share_prefixes=True, **kw)
    shared = _serve(eng, prompts, 12, audit=True)
    assert eng.shared_block_hits == 3
    alone = _serve(PagedServingEngine(cfg, params, **kw), prompts, 12)
    for uid in alone:
        np.testing.assert_array_equal(shared[uid], alone[uid])


def test_latent_pool_holds_one_row_per_token():
    cfg = configs.smoke("deepseek-v2-lite-16b")
    caches = SV.make_paged_caches(cfg, 2, 16, 32, 256, as_spec=True)
    kv = caches[1]["l0"]["kv"]
    assert isinstance(kv, CC.PagedLayerKVCache) and kv.v is None
    D = cfg.kv_lora_rank + cfg.rope_head_dim
    B = cfg.pariskv.num_subspaces(D)
    assert kv.k.shape == (2, 16, 32, 1, D)
    assert kv.meta_ids.shape == (2, 16, 1, 32, B)
    assert caches[1]["l0"]["hist"].shape == (2, 2, 1, B, 256)


def test_mla_support_reasons():
    cfg = configs.smoke("deepseek-v2-lite-16b")
    assert SV.fill_supported(cfg)
    for reason in (SV.offload_support_reason(cfg),
                   SV.sharded_support_reason(cfg)):
        assert "latent" in reason and "contiguous" not in reason
    with pytest.raises(SV.UnsupportedPagedConfig, match="host tier"):
        SV.make_paged_caches(cfg, 1, 8, 32, 256, as_spec=True,
                             num_device_blocks=4)
