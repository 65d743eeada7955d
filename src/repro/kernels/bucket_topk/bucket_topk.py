"""Pallas TPU kernel: histogram for small-range integer scores
(paper §4.3 kernel i — bucket_topk, step (i): the histogram).

Collision scores live in [0, 6B] (≤ 96 for B=16), so Top-β selection never
needs a sort: build a histogram (this kernel, tiled over the key stream,
one partial histogram per grid block, summed by XLA), walk it from the top
to find the threshold score, and compact indices by a prefix-sum of the
mask (steps (ii)/(iii), done with O(n) vector ops in ops.py).

TPU adaptation of the histogram: instead of scatter-increments (slow on
VPU), each block compares its scores against a broadcasted iota of the
score range and row-sums the one-hot — a (block_n, range) compare + reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(scores_ref, hist_ref, *, score_range: int):
    s = scores_ref[...].astype(jnp.int32)          # (1, bn)
    iota = jax.lax.broadcasted_iota(jnp.int32, (score_range, s.shape[1]), 0)
    onehot = (s == iota).astype(jnp.int32)         # (range, bn)
    hist_ref[0] = onehot.sum(axis=1, keepdims=True)


def histogram_pallas(scores: jax.Array, *, score_range: int,
                     block_n: int = 2048, interpret=None) -> jax.Array:
    """scores (n,) int32 in [0, score_range) → histogram (score_range,).

    Interpret-mode resolves outside the jitted body (env override honored
    per call, not frozen into the first trace)."""
    return _histogram_pallas(scores, score_range=score_range,
                             block_n=block_n,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("score_range", "block_n",
                                             "interpret"))
def _histogram_pallas(scores, *, score_range: int, block_n: int,
                      interpret: bool):
    n = scores.shape[0]
    assert n % block_n == 0
    grid = (n // block_n,)
    # scores as one (1, n) row and partial histograms as (range, 1)
    # columns: every block's last two dims are then legal TPU tiles
    partial = pl.pallas_call(
        functools.partial(_kernel, score_range=score_range),
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, score_range, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n // block_n, score_range, 1),
                                       jnp.int32),
        interpret=interpret,
    )(scores.reshape(1, n))
    return partial.sum((0, 2))
