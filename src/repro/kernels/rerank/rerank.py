"""Pallas TPU kernel: fused RSQ-IP reranking (paper §4.3 kernel iii).

Consumes the *gathered* candidate metadata — packed 4-bit direction codes
and weights, laid out subspace-major (R, B, C) so candidates sit on lanes
— plus the rotated query subspaces, and estimates ⟨k, q⟩ per Eq. 24
(without the ‖q‖ factor, which the wrapper applies):

    est_c = Σ_b w_{c,b} · Σ_j v(code_{c,b})_j · q̃_{b,j}

Fusion inside the kernel: nibble unpack (shift/mask, all m nibbles of a
subspace at once as an (m, C) tile) → sign split → 3-bit level lookup
(8-way select chain — the level table is a compile-time constant) → dot
with the subspace's q̃ column (a sublane reduction) → weighted
accumulate. The subspace loop is unrolled over the static B, so no
dynamic slicing happens inside the kernel. One pass over the candidate
tile in VMEM; no intermediate (C, B, m) tensor ever hits HBM (the
paper's motivation for fusing gather+unpack+score).

The row gather itself (candidates from the metadata store) is left to
XLA's native gather in ops.py — keeping it outside lets the same kernel
serve both the serving path and the standalone benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(codes_ref, w_ref, qt_ref, out_ref, *, num_subspaces: int,
            m: int, bits: int, levels: tuple):
    """codes_ref/w_ref (1, B, bc) int32/f32; qt_ref (1, m, B) f32;
    out_ref (1, 1, bc) f32."""
    bc = codes_ref.shape[2]
    shifts = 4 * jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    mag_mask = (1 << bits) - 1
    acc = jnp.zeros((1, bc), jnp.float32)
    for b in range(num_subspaces):
        word = codes_ref[0, b:b + 1, :]                       # (1, bc)
        nib = jax.lax.shift_right_logical(word, shifts) & 0xF  # (m, bc)
        sign = jnp.where((nib >> bits) & 1, 1.0, -1.0)
        mag_idx = nib & mag_mask
        val = jnp.full((m, bc), levels[0], jnp.float32)
        for t in range(1, 1 << bits):
            val = jnp.where(mag_idx == t, levels[t], val)
        q_col = qt_ref[0, :, b:b + 1]                          # (m, 1)
        dot = jnp.sum(sign * val * q_col, axis=0, keepdims=True)
        acc = acc + w_ref[0, b:b + 1, :] * dot
    out_ref[0] = acc


def rerank_pallas(codes: jax.Array, weights: jax.Array, q_sub: jax.Array,
                  *, m: int, bits: int, levels: tuple, block_c: int = 512,
                  interpret=None) -> jax.Array:
    """codes/weights (R, B, C) subspace-major, q_sub (R, B, m) → (R, C)
    f32 estimates before the ‖q‖ scale; R independent (query, candidate
    set) rows.

    C is zero-padded to a multiple of the candidate tile, which is
    ``block_c`` rounded to whole 128-lane vectors and capped at the padded
    C. Interpret-mode resolves outside the jitted body (env override
    honored per call, not frozen into the first trace)."""
    return _rerank_pallas(codes, weights, q_sub, m=m, bits=bits,
                          levels=levels, block_c=block_c,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("m", "bits", "levels", "block_c",
                                             "interpret"))
def _rerank_pallas(codes, weights, q_sub, *, m: int, bits: int,
                   levels: tuple, block_c: int, interpret: bool):
    R, B, Cn = codes.shape
    tile = -(-min(block_c, Cn) // 128) * 128
    pad = (-Cn) % tile
    codes = jax.lax.bitcast_convert_type(codes.astype(jnp.uint32), jnp.int32)
    weights = weights.astype(jnp.float32)
    if pad:
        widths = ((0, 0), (0, 0), (0, pad))
        codes = jnp.pad(codes, widths)
        weights = jnp.pad(weights, widths)
    q_t = jnp.swapaxes(q_sub.astype(jnp.float32), 1, 2)      # (R, m, B)
    out = pl.pallas_call(
        functools.partial(_kernel, num_subspaces=B, m=m, bits=bits,
                          levels=levels),
        grid=(R, (Cn + pad) // tile),
        in_specs=[
            pl.BlockSpec((1, B, tile), lambda r, i: (r, 0, i)),
            pl.BlockSpec((1, B, tile), lambda r, i: (r, 0, i)),
            pl.BlockSpec((1, m, B), lambda r, i: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda r, i: (r, 0, i)),
        out_shape=jax.ShapeDtypeStruct((R, 1, Cn + pad), jnp.float32),
        interpret=interpret,
    )(codes, weights, q_t)
    return out[:, 0, :Cn]
