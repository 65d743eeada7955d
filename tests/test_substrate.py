"""Substrate correctness: SSD math, MoE routing, optimizer, data, ckpt, serving."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:          # optional dev dep — seeded fallback
    HAS_HYPOTHESIS = False

from repro import configs
from repro.models import ssm as SSM
from repro.models import moe as MOE


# ----------------------------------------------------------------- SSD ----
def _naive_ssd(x, dt, A, B, C):
    """Literal recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Br = np.repeat(np.asarray(B), rep, 2)
    Cr = np.repeat(np.asarray(C), rep, 2)
    xn, dtn, An = np.asarray(x), np.asarray(dt), np.asarray(A)
    state = np.zeros((b, h, p, n))
    ys = np.zeros((b, l, h, p))
    for t in range(l):
        decay = np.exp(dtn[:, t] * An[None, :])[..., None, None]
        upd = np.einsum("bh,bhn,bhp->bhpn", dtn[:, t], Br[:, t], xn[:, t])
        state = state * decay + upd
        ys[:, t] = np.einsum("bhpn,bhn->bhp", state, Cr[:, t])
    return ys, state


@pytest.mark.parametrize("l,chunk", [(64, 16), (128, 32), (96, 96)])
def test_ssd_chunked_matches_recurrence(l, chunk):
    b, h, p, g, n = 2, 4, 8, 2, 16
    k = jax.random.PRNGKey(l)
    ks = jax.random.split(k, 4)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, g, n)) * 0.3
    C = jax.random.normal(ks[0], (b, l, g, n)) * 0.3
    y, final = SSM.ssd_chunked(x, dt, A, B, C, chunk)
    y_ref, final_ref = _naive_ssd(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), final_ref, rtol=2e-4,
                               atol=2e-4)


def test_ssm_prefill_matches_decode_continuation():
    """state from ssm_prefill must continue identically to running
    ssm_decode over the same tokens one by one."""
    cfg = configs.smoke("mamba2-780m")
    p = SSM.init_ssm(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 0.1
    y_full, cache_pre = SSM.ssm_prefill(p, x, cfg, chunk=32)

    cache = SSM.init_ssm_cache(2, cfg, jnp.float32)
    for t in range(64):
        y_t, cache = SSM.ssm_decode(p, x[:, t], cache, cfg)
    np.testing.assert_allclose(np.asarray(cache.state),
                               np.asarray(cache_pre.state), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_full[:, -1]),
                               rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------- MoE ----
def test_moe_routing_respects_topk_and_gates():
    cfg = configs.smoke("grok-1-314b")
    p = MOE.init_moe(jax.random.PRNGKey(0), 64, 128, 4, 0, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    out, aux = MOE.moe_fwd(p, x, top_k=2)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0.5  # E·Σ f·p ≥ 1 at uniform routing

    # dropless: moe equals per-token math
    xt = x.reshape(-1, 64)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    g, idx = jax.lax.top_k(probs, 2)
    g = g / g.sum(-1, keepdims=True)
    want = np.zeros_like(np.asarray(xt))
    for i in range(xt.shape[0]):
        for j in range(2):
            e = int(idx[i, j])
            h = jax.nn.silu(xt[i] @ p["experts_gate"][e]) * (
                xt[i] @ p["experts_up"][e])
            want[i] += float(g[i, j]) * np.asarray(h @ p["experts_down"][e])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 64), want,
                               rtol=2e-3, atol=2e-3)


def test_moe_decode_matches_fwd():
    p = MOE.init_moe(jax.random.PRNGKey(2), 32, 64, 4, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 32))
    out_d = MOE.moe_decode(p, x, top_k=2)
    out_f, _ = MOE.moe_fwd(p, x[:, None], 2)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f[:, 0]),
                               rtol=2e-3, atol=2e-3)


def _moe_per_token(p, xt, top_k, norm_topk_prob):
    """Each token alone through its top-k experts (and the shared MLP)."""
    probs = jax.nn.softmax(xt @ p["router"], -1)
    g, idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        g = g / g.sum(-1, keepdims=True)
    want = np.zeros_like(np.asarray(xt))
    for i in range(xt.shape[0]):
        for j in range(top_k):
            e = int(idx[i, j])
            h = jax.nn.silu(xt[i] @ p["experts_gate"][e]) * (
                xt[i] @ p["experts_up"][e])
            want[i] += float(g[i, j]) * np.asarray(h @ p["experts_down"][e])
        if "shared" in p:
            want[i] += np.asarray(
                jax.nn.silu(xt[i] @ p["shared"]["wi_gate"])
                * (xt[i] @ p["shared"]["wi_up"]) @ p["shared"]["wo_mlp"])
    return want, np.asarray(g)


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_moe_dropless_when_every_token_picks_one_expert(norm_topk_prob):
    """Every token of a 64-token batch is routed to experts 0 and 1 (a
    capacity buffer would drop most of them): each output still equals
    the token's own expert math, and equals the token served alone."""
    p = MOE.init_moe(jax.random.PRNGKey(4), 16, 32, 4, 1, jnp.float32)
    p["router"] = jnp.zeros((16, 4)).at[0, 0].set(50.0).at[0, 1].set(25.0)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 16)) * 0.1
    x = x.at[..., 0].set(1.0)
    out, _ = MOE.moe_fwd(p, x, top_k=2, norm_topk_prob=norm_topk_prob)
    want, g = _moe_per_token(p, x[0], 2, norm_topk_prob)
    assert (np.asarray(jax.lax.top_k(x[0] @ p["router"], 2)[1])
            == [0, 1]).all()
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=2e-4,
                               atol=1e-5)
    alone = MOE.moe_decode(p, x[0, 5:6], 2, norm_topk_prob=norm_topk_prob)
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(out[0, 5]),
                               rtol=1e-5, atol=1e-6)


def test_moe_norm_topk_prob_false_keeps_softmax_gates():
    """``norm_topk_prob=False`` (DeepSeek-V2): the gates are the top-k
    softmax probabilities as they are, summing to less than 1."""
    p = MOE.init_moe(jax.random.PRNGKey(6), 32, 48, 8, 0, jnp.float32)
    p["router"] = p["router"] * 10.0          # spread the probabilities
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 32))
    out, _ = MOE.moe_fwd(p, x, top_k=3, norm_topk_prob=False)
    want, g = _moe_per_token(p, x.reshape(-1, 32), 3, False)
    assert (g.sum(-1) < 0.999).all()
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want,
                               rtol=2e-3, atol=2e-4)
    renorm, _ = MOE.moe_fwd(p, x, top_k=3, norm_topk_prob=True)
    assert not np.allclose(np.asarray(renorm), np.asarray(out), atol=1e-4)


# ----------------------------------------------------------- optimizer ----
def test_adamw_descends_quadratic():
    from repro.optim import adamw_init, adamw_update
    w = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw_init(w)
    for _ in range(200):
        g = {"w": 2 * w["w"]}  # ∇‖w‖²
        w, opt = adamw_update(w, g, opt, lr=jnp.float32(0.05),
                              weight_decay=0.0)
    assert float(jnp.abs(w["w"]).max()) < 0.05


def test_clip_by_global_norm():
    from repro.optim import clip_by_global_norm
    g = {"a": jnp.ones((10,)) * 3.0}
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(gn), 3.0 * np.sqrt(10), rtol=1e-5)
    total = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def _check_cosine_schedule_bounded(step):
    from repro.optim import cosine_schedule
    lr = float(cosine_schedule(jnp.int32(step), 1e-3, 100, 5000))
    assert 0.0 <= lr <= 1e-3 + 1e-9


if HAS_HYPOTHESIS:
    @given(st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_property_cosine_schedule_bounded(step):
        _check_cosine_schedule_bounded(step)
else:
    @pytest.mark.parametrize("step", [0, 1, 99, 100, 2500, 5000, 10_000])
    def test_property_cosine_schedule_bounded(step):
        _check_cosine_schedule_bounded(step)


# ----------------------------------------------------------------- data ----
def test_data_stream_deterministic_and_sharded():
    from repro.data import SyntheticLMStream, make_batch
    s1 = SyntheticLMStream(1000, seed=4)
    s2 = SyntheticLMStream(1000, seed=4)
    a, la = make_batch(s1, 8, 64)
    b, lb = make_batch(s2, 8, 64)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[:, 1:], la[:, :-1])  # labels = shift
    assert a.max() < 1000 and a.min() >= 0
    # host sharding: 2 hosts each get batch/2 rows
    rows, _ = make_batch(SyntheticLMStream(1000, seed=5), 8, 32,
                         host_id=0, num_hosts=2)
    assert rows.shape == (4, 32)


# ----------------------------------------------------------------- ckpt ----
def test_checkpoint_roundtrip_bf16():
    from repro.ckpt import load_checkpoint, save_checkpoint
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.float32),
                       "c": [jnp.zeros((2,), jnp.int32)]}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        save_checkpoint(path, tree, step=7)
        like = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        back = load_checkpoint(path, like)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


# -------------------------------------------------------------- serving ----
@pytest.mark.parametrize("engine_cls", ["slots", "wave"])
def test_serving_engine_completes_requests(engine_cls):
    """Both schedulers (slot-based continuous batching + legacy waves)
    complete 3 requests on a max_batch=2 pool — the slot engine by evicting
    and reusing a slot mid-flight, the wave engine with two waves."""
    from repro.data import SyntheticLMStream
    from repro.models import model as M
    from repro.serving import Request, ServingEngine, WaveServingEngine
    cfg = configs.smoke("stablelm-1.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if engine_cls == "slots":
        eng = ServingEngine(cfg, params, n_max=256, max_batch=2,
                            chunk_size=2)
    else:
        eng = WaveServingEngine(cfg, params, n_max=256, max_batch=2)
    stream = SyntheticLMStream(cfg.vocab_size, seed=9)
    for i in range(3):  # 3 requests > max_batch → slot reuse / two waves
        eng.submit(Request(uid=i, prompt=stream.sequence(48),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 3
    for r in done:
        assert r.output.shape == (4,)
        assert r.ttft_s > 0 and r.decode_s > 0
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()
