"""Wrappers: gather candidates (XLA), then the fused Pallas kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantizer
from repro.kernels.rerank.rerank import rerank_pallas


def _levels(m: int, bits: int) -> tuple:
    _, levels = quantizer.lloyd_max_levels(m, bits)
    return tuple(float(x) for x in levels)


def rerank_kernel(codes: jax.Array, weights: jax.Array, cand_idx: jax.Array,
                  q_sub: jax.Array, q_norm: jax.Array, m: int = 8,
                  bits: int = 3, block_c: int = 512) -> jax.Array:
    """Full Stage-II: gather + fused unpack/score.

    codes/weights: (n, B); cand_idx: (C,); q_sub: (B, m); q_norm: scalar
    → (C,) float32 RSQ-IP estimates.
    """
    g_codes = codes[cand_idx].T[None]        # XLA gather → (1, B, C)
    g_w = weights[cand_idx].T[None]
    est = rerank_pallas(g_codes, g_w, q_sub[None], m=m, bits=bits,
                        levels=_levels(m, bits), block_c=block_c)
    return est[0] * q_norm


def rerank_paged_kernel(pool_codes: jax.Array, pool_w: jax.Array,
                        phys_rows: jax.Array, q_sub: jax.Array,
                        q_norm: jax.Array, m: int = 8, bits: int = 3,
                        block_c: int = 512) -> jax.Array:
    """Paged Stage-II: gather the ≤C candidates' codes/weights from the
    pool by *physical row id* (never the full logical view), then the
    fused unpack/score kernel.

    pool_codes: (num_blocks, G, block_size, B) uint32 (pool layout)
    pool_w:     (num_blocks, G, block_size, B) float32
    phys_rows:  (b, G, Hg, C) int32 flat pool row ids (block · block_size
                + offset) — core.retrieval.retrieve_paged_fused addressing
    q_sub:      (b, G, Hg, B, m) rotated query subspaces; q_norm (b, G, Hg)
    → (b, G, Hg, C) float32 RSQ-IP estimates.
    """
    bs = pool_codes.shape[2]
    b, G, Hg, Cn = phys_rows.shape
    B = pool_codes.shape[-1]
    blk, off = phys_rows // bs, phys_rows % bs
    g = jnp.arange(G)[None, :, None, None]

    def gather(pool):                        # → (b·G·Hg, B, C)
        rows = pool[blk, g, off]                               # (b,G,Hg,C,B)
        return jnp.swapaxes(rows, -1, -2).reshape(-1, B, Cn)

    est = rerank_pallas(gather(pool_codes), gather(pool_w),
                        q_sub.reshape((-1,) + q_sub.shape[-2:]), m=m,
                        bits=bits, levels=_levels(m, bits), block_c=block_c)
    return est.reshape(b, G, Hg, Cn) * q_norm[..., None]
