"""Plain reference of ParisKV decode, in float32: the library that the
architecture files (``bench/archs/<arch>.py``) build their reference
forward from. An arch file holds its layer equations as served (see the
config file's ``departures``); what is here is ParisKV's decode as the
paper and the configuration's ``pariskv`` group define it:

* prompt positions attend causally to every earlier position (the
  chunked fill is a prefill done in pieces);
* a decoded position ``p`` attends to the sink ``[0, sink)``, the dense
  window ``[max(p+1-W, 0), p]`` clipped below at the retrieval-region end
  ``e``, and the ``top_k`` keys of ``[sink, e)`` that two-stage retrieval
  selects: Stage I scores every key by the tier weights of its centroid
  buckets (buckets ranked by the query's centroid score, tiers cut at
  shares of ``rho · (e - sink)`` keys) and keeps the top
  ``candidate_count(n_max)`` (ties to the lower position); Stage II ranks
  those by the RSQ-IP estimate from 4-bit codes and keeps ``top_k``.
  ``e`` starts at ``max(min(sink, L), L - local)`` after a prompt of ``L``
  and moves up by ``update_interval`` after every step at which
  ``p - e >= W`` (``W = local + update_interval``).

Nothing here imports the program or reads an array it made: weights come
from ``bench.weights`` and the same seed, the rotation signs and the code
levels are worked out here. An arch file runs the computation layer by
layer, one request at a time, with matrix products at ``highest``
precision.

``mm(..., lo=True)`` and ``round8`` are the control: every matrix
product's operands, and the queries, keys and values, rounded to float8
e4m3 with a scale per row (weights: per output column) — the step below
the bfloat16 the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = np.float32(-1e30)


# ----------------------------------------------------------- constants ----
def rotation_signs(dim_padded: int, seed: int) -> np.ndarray:
    """Rademacher signs of the shared randomized Hadamard rotation."""
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    return (rng.randint(0, 2, size=(dim_padded,)) * 2 - 1).astype(np.float32)


def magnitude_levels(m: int, bits: int, iters: int = 200):
    """Lloyd-Max quantizer of |u_j| for a unit direction in m dims, whose
    squared coordinate follows Beta(1/2, (m-1)/2)."""
    grid = 1 << 14
    x = (np.arange(grid) + 0.5) / grid
    a, b = 0.5, (m - 1) / 2.0
    lb = math.lgamma(a) + math.lgamma(b) - math.lgamma(a + b)
    y = np.clip(x * x, 1e-12, 1 - 1e-12)
    f = 2.0 * x * np.exp(-lb + (a - 1) * np.log(y) + (b - 1) * np.log(1 - y))
    f /= f.sum()
    n = 1 << bits
    cdf = np.cumsum(f)
    lv = x[np.searchsorted(cdf, (np.arange(n) + 0.5) / n).clip(0, grid - 1)]
    for _ in range(iters):
        th = 0.5 * (lv[:-1] + lv[1:])
        idx = np.searchsorted(th, x)
        new = lv.copy()
        for t in range(n):
            w = f[idx == t]
            if w.sum() > 0:
                new[t] = float((x[idx == t] * w).sum() / w.sum())
        if np.allclose(new, lv, atol=1e-9):
            lv = new
            break
        lv = new
    th = 0.5 * (lv[:-1] + lv[1:])
    return th.astype(np.float32), lv.astype(np.float32)


def candidate_count(pk: dict, n: int) -> int:
    c = int(math.ceil(pk["beta"] * n))
    c = max(pk["min_candidates"], min(pk["max_candidates"], c))
    return min(max(c, pk["top_k"]), n)


def decode_region_ends(pk: dict, prompt_len: int, n_decode: int) -> np.ndarray:
    """Retrieval-region end seen by each of ``n_decode`` decoded positions
    after a prompt of ``prompt_len`` tokens."""
    sink, local, upd = pk["sink_size"], pk["local_size"], pk["update_interval"]
    W = local + upd
    e = max(min(sink, prompt_len), prompt_len - local)
    out = np.empty(n_decode, np.int32)
    for i in range(n_decode):
        p = prompt_len + i
        out[i] = e
        if p - e >= W:
            e += upd
    return out


# --------------------------------------------------------------- pieces ----
def round8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, w, lo: bool):
    if lo:
        a, w = round8(a, -1), round8(w, 0)
    return a @ w


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fwht(x):
    n = x.shape[-1]
    h = 1
    while h < n:
        y = x.reshape(x.shape[:-1] + (n // (2 * h), 2, h))
        x = jnp.concatenate([y[..., 0, :] + y[..., 1, :],
                             y[..., 0, :] - y[..., 1, :]], -1)
        x = x.reshape(y.shape[:-3] + (n,))
        h *= 2
    return x


def _subspaces(x, signs, m):
    """x (..., D) → unit-normalized, rotated, split (..., B, m); and ‖x‖."""
    norm = jnp.sqrt(jnp.sum(x * x, -1))
    xh = x / jnp.maximum(norm, 1e-20)[..., None]
    dp = signs.shape[0]
    xh = jnp.pad(xh, [(0, 0)] * (x.ndim - 1) + [(0, dp - x.shape[-1])])
    rot = _fwht(xh * signs) / np.sqrt(dp)
    return rot.reshape(x.shape[:-1] + (dp // m, m)), norm


def key_metadata(k, signs, pk, th, lv):
    """Centroid ids (..., B) and the RSQ-IP key vector (..., B·m) whose
    inner product with the rotated query gives the Stage-II estimate."""
    m = pk["m"]
    sub, norm = _subspaces(k, signs, m)
    r = jnp.sqrt(jnp.sum(sub * sub, -1))
    u = sub / jnp.maximum(r, 1e-20)[..., None]
    ids = jnp.sum((u >= 0).astype(jnp.int32) * (1 << jnp.arange(m)), -1)
    mag = jnp.searchsorted(jnp.asarray(th), jnp.abs(u))
    v = jnp.where(u >= 0, 1.0, -1.0) * jnp.asarray(lv)[mag]
    alpha = jnp.maximum(jnp.sum(v * u, -1), 1e-4)
    w = norm[..., None] * r / alpha
    kt = (w[..., None] * v).reshape(k.shape[:-1] + (-1,))
    return ids, kt


def tier_table(cs, counts, n_valid, pk):
    """Tier weight per (subspace, bucket): buckets ranked by centroid score,
    each weighted by where its first key falls among rho·n_valid keys."""
    order = jnp.argsort(-cs, axis=-1)
    c_sorted = jnp.take_along_axis(counts, order, -1)
    before = jnp.cumsum(c_sorted, -1) - c_sorted
    denom = jnp.maximum(pk["rho"] * n_valid, 1.0)
    frac = before / denom
    tier = jnp.searchsorted(jnp.asarray(pk["tier_pcts"], jnp.float32), frac,
                            side="right")
    wts = jnp.asarray(list(pk["tier_weights"]) + [0], jnp.float32)
    w_sorted = wts[jnp.minimum(tier, len(pk["tier_weights"]))]
    return jnp.take_along_axis(w_sorted, jnp.argsort(order, -1), -1)


# ------------------------------------------------------------ attention ----
def decode_attention(q, k, v, onehot, kt, qpos, enc, *, pk, signs,
                     scale, n_cand, centroids):
    """ParisKV attention of decoded positions. q (T, G, Hg, hd) at
    positions ``qpos`` (T,) with region ends ``enc`` (T,); k, v (S, G, hd);
    onehot (S, G, B, 2^m) bucket of every key; kt (S, G, B·m)."""
    S = k.shape[0]
    T, G, Hg, _ = q.shape
    sink, W = pk["sink_size"], pk["local_size"] + pk["update_interval"]
    j = jnp.arange(S)
    in_region = (j[None] >= sink) & (j[None] < enc[:, None])      # (T, S)
    counts = jnp.einsum("ts,sgbc->tgbc", in_region.astype(jnp.bfloat16),
                        onehot, preferred_element_type=jnp.float32)
    qsub, qn = _subspaces(q, signs, pk["m"])                        # T,G,Hg,B,m
    cs = jnp.einsum("tghbm,cm->tghbc", qsub, centroids)
    n_valid = (enc - sink).astype(jnp.float32)[:, None, None, None, None]
    table = tier_table(cs, counts[:, :, None], n_valid, pk)
    stage1 = jnp.einsum("tghbc,sgbc->tghs", table.astype(jnp.bfloat16),
                        onehot, preferred_element_type=jnp.float32)
    valid = in_region[:, None, None, :]
    stage1 = jnp.where(valid, stage1, -1.0)
    thr = jax.lax.top_k(stage1, n_cand)[0][..., -1:]
    tie = stage1 == thr
    quota = n_cand - jnp.sum(stage1 > thr, -1, keepdims=True)
    cand = (stage1 > thr) | (tie & (jnp.cumsum(tie, -1) <= quota))
    est = qn[..., None] * jnp.einsum(
        "tghx,sgx->tghs", qsub.reshape(T, G, Hg, -1), kt)
    est = jnp.where(cand & valid, est, NEG)
    top_est, top = jax.lax.top_k(est, pk["top_k"])
    ret = jnp.zeros(est.shape, bool)
    ret = jax.vmap(jax.vmap(jax.vmap(lambda r, i, e: r.at[i].set(e > NEG / 2)
                                     )))(ret, top, top_est)
    p = qpos[:, None]
    ws = jnp.maximum(p + 1 - W, 0)
    dense = ((j[None] < sink) & (j[None] <= p)) | (
        (j[None] >= ws) & (j[None] < ws + W) & (j[None] >= enc[:, None])
        & (j[None] >= sink) & (j[None] <= p))
    mask = ret | dense[:, None, None, :]
    s = jnp.einsum("tghd,sgd->tghs", q, k) * scale
    s = jnp.where(mask, s, NEG)
    return jnp.einsum("tghs,sgd->tghd", jax.nn.softmax(s, -1), v)


def causal_attention(q, k, v, *, scale):
    """Causal attention of every row q (S, G, Hg, hd) at positions 0..S-1
    against k, v (S, G, hd), in blocks of 512 rows."""
    S = k.shape[0]
    blk = 512

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        pos = i * blk + jnp.arange(blk)
        s = jnp.einsum("tghd,sgd->tghs", qb, k) * scale
        s = jnp.where((jnp.arange(S)[None] <= pos[:, None])[:, None, None],
                      s, NEG)
        return jnp.einsum("tghs,sgd->tghd", jax.nn.softmax(s, -1), v)

    return jax.lax.map(one, jnp.arange(S // blk)).reshape(q.shape)


# ------------------------------------------------------------ requests ----
def shapes_for(requests):
    """(sequence rows, decoded rows) that hold every (prompt, served) pair
    of ``requests``, so one compile serves them all."""
    n_dec = -(-max(max(len(s) - 1 for _, s in requests), 1) // 256) * 256
    seq = max(len(p) for p, _ in requests) + n_dec
    return -(-seq // 512) * 512, n_dec


def gaps(ref_logits: jax.Array, tokens: np.ndarray) -> np.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    ref = np.asarray(ref_logits)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]
