"""Key summarization / query transform (paper §4.1, Fig. 2 blocks A.1-A.3).

``encode_keys`` builds the GPU-resident (here: accelerator-resident) per-key
metadata used by both retrieval stages:

  * ``centroid_ids`` — Stage-I sign-pattern bucket ids, (n, B) uint8
  * ``codes``        — Stage-II 4-bit direction codes, (n, B) uint32 (packed)
  * ``weights``      — w_{i,b} = ‖k_i‖ · r_{i,b} / α_{i,b}, (n, B) float32

Total: B·(1 + 4 + 4) = 9·B bytes per key vs 2·D bytes for a bf16 key —
for D=128, B=16: 144 B vs 256 B (and weights can be cast to bf16 for 112 B).
``encode_query`` applies the *same* normalize→rotate→split transform online.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import centroids, phases, quantizer, srht
from repro.core.config import ParisKVConfig

_EPS = 1e-20


class KeyMetadata(NamedTuple):
    centroid_ids: jax.Array  # (..., n, B) uint8
    codes: jax.Array         # (..., n, B) uint32
    weights: jax.Array       # (..., n, B) float32


class QueryTransform(NamedTuple):
    q_norm: jax.Array  # (...,)    ‖q‖₂
    q_sub: jax.Array   # (..., B, m) rotated subspace components q̃_b


def _sum_last(x: jax.Array) -> jax.Array:
    """Sum over the last axis as a fixed tree of elementwise halvings.

    A ``reduce`` sums in an order that the backend derives from the
    operand's shape and layout, which XLA picks per program: a batched
    row and the same row alone, or one device and a head-sharded mesh,
    then round the same keys differently, and a one-ulp change of a norm
    reorders near-tied candidates. Adding halves elementwise fixes the
    order in every program. Zero-pads to a power of two (exact).
    """
    n = x.shape[-1]
    p = 1 << (n - 1).bit_length()
    if p != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - n)])
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def _norm(x: jax.Array) -> jax.Array:
    """‖x‖₂ over the last axis in float32, summed by ``_sum_last``."""
    x = x.astype(jnp.float32)
    return jnp.sqrt(_sum_last(x * x))


def rotate_split(x: jax.Array, cfg: ParisKVConfig, signs: jax.Array) -> jax.Array:
    """normalize → SRHT rotate → split into (..., B, m) subspaces."""
    d = x.shape[-1]
    norm = _norm(x)[..., None]
    x_hat = x.astype(jnp.float32) / jnp.maximum(norm, _EPS)
    x_rot = srht.srht_rotate(x_hat, signs)
    dp = x_rot.shape[-1]
    return x_rot.reshape(x.shape[:-1] + (dp // cfg.m, cfg.m))


def encode_keys(keys: jax.Array, cfg: ParisKVConfig, signs: jax.Array) -> KeyMetadata:
    """Summarize raw keys (..., n, D) into retrieval metadata (A.2 + A.3)."""
    norm = _norm(keys)                                          # (..., n)
    sub = rotate_split(keys, cfg, signs)                        # (..., n, B, m)

    # polar decomposition per subspace
    r = _norm(sub)                                              # (..., n, B)
    u = sub / jnp.maximum(r[..., None], _EPS)                   # unit directions

    ids = centroids.assign(u)                                   # (..., n, B) uint8
    codes = quantizer.encode_directions(u, cfg.m, cfg.magnitude_bits)

    # alignment factor α = ⟨v, u⟩ (Eq. 7) and weight w = ‖k‖ r / α (Eq. 9)
    v = quantizer.decode_directions(codes, cfg.m, cfg.magnitude_bits)
    alpha = _sum_last(v * u)                                    # (..., n, B)
    alpha = jnp.maximum(alpha, 1e-4)  # v shares u's signs ⇒ α > 0; guard anyway
    weights = norm[..., None] * r / alpha
    return KeyMetadata(ids, codes, weights.astype(jnp.float32))


def encode_query(q: jax.Array, cfg: ParisKVConfig, signs: jax.Array) -> QueryTransform:
    """Transform an online query (..., D) identically to the keys (the
    first step of building the Stage-I tier table)."""
    with jax.named_scope(phases.TIER_TABLE):
        q_norm = _norm(q)
        q_sub = rotate_split(q, cfg, signs)
        return QueryTransform(q_norm, q_sub)


def estimate_inner_products(meta: KeyMetadata, qt: QueryTransform,
                            cfg: ParisKVConfig) -> jax.Array:
    """RSQ-IP estimator over *all* keys (Eq. 24) — oracle-grade reference.

    Returns (..., n) estimates of ⟨k_i, q⟩. The production path only does this
    for the Stage-I candidate subset (see core.retrieval / kernels.rerank).
    """
    v = quantizer.decode_directions(meta.codes, cfg.m, cfg.magnitude_bits)
    # ⟨v_{i,b}, q̃_b⟩ summed with weights over subspaces
    dots = jnp.einsum("...nbm,...bm->...nb", v, qt.q_sub)
    return qt.q_norm[..., None] * jnp.sum(meta.weights * dots, axis=-1)
