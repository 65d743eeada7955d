"""Unit + property tests: SRHT rotation, centroids, quantizer (paper §4.1).

Property tests use ``hypothesis`` when available, with a fixed seed sweep
as fallback (hypothesis is an optional dev dep — requirements-dev.txt).
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

from repro.core import ParisKVConfig, srht
from repro.core import centroids, quantizer
from repro.core.encode import encode_keys, encode_query, rotate_split

jax.config.update("jax_enable_x64", False)

CFG = ParisKVConfig()


# ------------------------------------------------------- encode sums ----
@pytest.mark.parametrize("n", [1, 8, 64, 100, 128])
def test_sum_last_is_a_fixed_halving_tree(n):
    """The encode norms sum as elementwise halvings, zero-padded to a
    power of two — the same order whatever shape or layout the operand
    has, and equal to a plain sum up to f32 rounding."""
    from repro.core.encode import _norm, _sum_last
    x = np.random.RandomState(n).randn(3, n).astype(np.float32)
    want = np.pad(x, ((0, 0), (0, (1 << (n - 1).bit_length()) - n)))
    while want.shape[-1] > 1:
        h = want.shape[-1] // 2
        want = want[:, :h] + want[:, h:]
    got = np.asarray(jax.jit(_sum_last)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want[:, 0])
    np.testing.assert_allclose(got, x.sum(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(_norm(jnp.asarray(x))),
                               np.linalg.norm(x, axis=-1), rtol=1e-6)


# ---------------------------------------------------------------- SRHT ----
@pytest.mark.parametrize("d", [64, 80, 128, 240, 256, 576, 1024])
def test_srht_orthogonal_preserves_ip(d):
    dp = CFG.padded_dim(d)
    signs = jnp.asarray(srht.rademacher_signs(dp, 1))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, d))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, d))
    rx, ry = srht.srht_rotate(x, signs), srht.srht_rotate(y, signs)
    np.testing.assert_allclose(np.asarray(jnp.sum(rx * ry, -1)),
                               np.asarray(jnp.sum(x * y, -1)), rtol=2e-4, atol=2e-4)
    # norms preserved too
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(rx, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-4)


def test_srht_matches_explicit_hadamard():
    dp = 16
    signs = jnp.asarray(srht.rademacher_signs(dp, 3))
    # explicit H via Sylvester construction
    H = np.array([[1.0]])
    while H.shape[0] < dp:
        H = np.block([[H, H], [H, -H]])
    x = np.random.RandomState(0).randn(5, dp).astype(np.float32)
    want = (x * np.asarray(signs)) @ H.T / np.sqrt(dp)
    got = np.asarray(srht.srht_rotate(jnp.asarray(x), signs))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_srht_inverse_roundtrip():
    d = 100
    dp = CFG.padded_dim(d)
    signs = jnp.asarray(srht.rademacher_signs(dp, 5))
    x = jax.random.normal(jax.random.PRNGKey(2), (7, d))
    y = srht.srht_rotate(x, signs)
    back = srht.srht_rotate_t(y, signs, d)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-4, atol=1e-4)


def test_rotation_isotropizes_coordinates():
    """Prop 4.1 sanity: rotated unit-vector coordinate energy ≈ uniform."""
    d = 128
    dp = CFG.padded_dim(d)
    signs = jnp.asarray(srht.rademacher_signs(dp, 9))
    x = jax.random.normal(jax.random.PRNGKey(3), (4096, d)) * jnp.linspace(3, 0.1, d)
    sub = rotate_split(x, CFG, signs)  # (n, B, m) of unit vectors
    energy = jnp.sum(sub ** 2, axis=(0, 2))  # per-subspace
    frac = energy / energy.sum()
    assert float(jnp.abs(frac - 1 / frac.shape[0]).max()) < 0.02


# ------------------------------------------------------------ centroids ----
def test_assignment_is_nearest_centroid():
    """The sign-pack assignment must equal brute-force argmax over Ω."""
    m = 8
    u = jax.random.normal(jax.random.PRNGKey(0), (512, m))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    ids = centroids.assign(u)
    omega = jnp.asarray(centroids.codebook(m))
    brute = jnp.argmax(u @ omega.T, axis=-1)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(brute))


def test_codebook_unit_norm_and_uniform():
    for m in (4, 8):
        om = centroids.codebook(m)
        assert om.shape == (1 << m, m)
        np.testing.assert_allclose(np.linalg.norm(om, axis=-1), 1.0, rtol=1e-6)
        # uniform coverage: mean of centroids is zero
        np.testing.assert_allclose(om.mean(axis=0), 0.0, atol=1e-7)


def test_centroid_scores_match_einsum():
    q_sub = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
    cs = centroids.centroid_scores(q_sub, 8)
    om = jnp.asarray(centroids.codebook(8))
    want = jnp.einsum("abm,cm->abc", q_sub, om)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(want), rtol=1e-5)


def _check_new_keys_always_near_a_centroid(seed):
    """Drift-robustness invariant: ANY unit direction has cosine ≥ 1/√m to
    its assigned analytic centroid (sign alignment bound)."""
    m = 8
    u = jax.random.normal(jax.random.PRNGKey(seed), (64, m))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    ids = centroids.assign(u)
    c = centroids.decode_centroid(ids, m)
    cos = jnp.sum(u * c, axis=-1)
    # ⟨u, sign(u)/√m⟩ = ‖u‖₁/√m ≥ ‖u‖₂/√m = 1/√m
    assert float(cos.min()) >= 1 / np.sqrt(m) - 1e-6


if HAS_HYPOTHESIS:
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=20, deadline=None)
    def test_property_new_keys_always_near_a_centroid(seed):
        _check_new_keys_always_near_a_centroid(seed)
else:
    @pytest.mark.parametrize("seed", [0, 3, 999, 2**32 - 1])
    def test_property_new_keys_always_near_a_centroid(seed):
        _check_new_keys_always_near_a_centroid(seed)


# ------------------------------------------------------------ quantizer ----
def test_lloyd_max_levels_monotone_in_unit_interval():
    tau, levels = quantizer.lloyd_max_levels(8, 3)
    assert np.all(np.diff(levels) > 0) and np.all(np.diff(tau) > 0)
    assert 0 < levels[0] < levels[-1] < 1
    np.testing.assert_allclose(tau, 0.5 * (levels[:-1] + levels[1:]), rtol=1e-5)


def test_code_roundtrip_sign_and_bucket():
    m = 8
    u = jax.random.normal(jax.random.PRNGKey(0), (256, 4, m))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    codes = quantizer.encode_directions(u, m)
    v = quantizer.decode_directions(codes, m)
    # signs must match exactly; magnitudes within the coarsest bucket width
    np.testing.assert_array_equal(np.asarray(jnp.sign(v)),
                                  np.asarray(jnp.where(u >= 0, 1.0, -1.0)))
    assert float(jnp.abs(jnp.abs(v) - jnp.abs(u)).max()) < 0.45
    # alignment is strictly positive (guarantees α > 0 in Eq. 7)
    align = jnp.sum(u * v, axis=-1)
    assert float(align.min()) > 0.5


def test_quantizer_is_data_independent():
    """Same (τ, a) regardless of when/where derived — the drift-robust prop."""
    t1, l1 = quantizer.lloyd_max_levels(8, 3)
    quantizer.lloyd_max_levels.cache_clear()
    t2, l2 = quantizer.lloyd_max_levels(8, 3)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(l1, l2)


def test_quantizer_matches_beta_prior_stats():
    """Empirical |u_j| from rotated unit vectors should hit the analytic
    Lloyd–Max buckets roughly uniformly by prior mass (validates Prop 4.1
    being used correctly)."""
    cfg = ParisKVConfig()
    d = 128
    signs = jnp.asarray(srht.rademacher_signs(cfg.padded_dim(d), 11))
    x = jax.random.normal(jax.random.PRNGKey(4), (8192, d))
    sub = rotate_split(x, cfg, signs)
    r = jnp.linalg.norm(sub, axis=-1, keepdims=True)
    u = sub / jnp.maximum(r, 1e-20)
    buckets = quantizer.quantize_magnitudes(jnp.abs(u), cfg.m)
    hist = np.bincount(np.asarray(buckets).ravel(), minlength=8) / buckets.size
    # Lloyd–Max on the true prior gives non-degenerate mass in every bucket
    assert hist.min() > 0.01, hist


# ------------------------------------------------------------- encode ----
def test_weights_formula():
    """w = ‖k‖ r / α exactly (Eq. 9/23)."""
    cfg = ParisKVConfig()
    d = 64
    signs = jnp.asarray(srht.rademacher_signs(cfg.padded_dim(d), 2))
    keys = jax.random.normal(jax.random.PRNGKey(5), (32, d)) * 3.0
    meta = encode_keys(keys, cfg, signs)
    sub = rotate_split(keys, cfg, signs)
    r = jnp.linalg.norm(sub, axis=-1)
    u = sub / r[..., None]
    v = quantizer.decode_directions(meta.codes, cfg.m)
    alpha = jnp.sum(u * v, axis=-1)
    norm = jnp.linalg.norm(keys, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(meta.weights),
                               np.asarray(norm * r / alpha), rtol=1e-4)


def _check_estimator_is_calibrated(seed, d):
    """RSQ-IP estimate correlates >0.97 with the exact inner product and is
    approximately unbiased (|mean err| << std of scores) for random data."""
    from repro.core.encode import estimate_inner_products
    cfg = ParisKVConfig()
    signs = jnp.asarray(srht.rademacher_signs(cfg.padded_dim(d), cfg.srht_seed))
    kk = jax.random.normal(jax.random.PRNGKey(seed), (1024, d))
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (d,))
    meta = encode_keys(kk, cfg, signs)
    qt = encode_query(q, cfg, signs)
    est = estimate_inner_products(meta, qt, cfg)
    exact = kk @ q
    corr = np.corrcoef(np.asarray(est), np.asarray(exact))[0, 1]
    assert corr > 0.97, corr
    bias = float(jnp.mean(est - exact))
    assert abs(bias) < 0.2 * float(jnp.std(exact))


if HAS_HYPOTHESIS:
    @given(st.integers(0, 2**31 - 1), st.sampled_from([64, 128, 256]))
    @settings(max_examples=10, deadline=None)
    def test_property_estimator_is_calibrated(seed, d):
        _check_estimator_is_calibrated(seed, d)
else:
    @pytest.mark.parametrize("seed,d", [(0, 64), (1, 128), (2, 256)])
    def test_property_estimator_is_calibrated(seed, d):
        _check_estimator_is_calibrated(seed, d)
