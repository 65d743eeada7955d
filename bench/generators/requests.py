"""The general request generator: every traffic mix under ``bench/traffic``
is a file of parameters for this one function.

Parameters (``bench/traffic/<mix>.json``):

* ``loop``: ``"closed"`` — ``concurrency`` sessions are kept in flight and
  the next one is sent when one finishes; or ``"open"`` — requests arrive
  at ``rate_per_s`` whatever the server does, with exponential gaps.
* ``prompt_len`` / ``output_len``: ``{"fixed": n}`` or
  ``{"lognormal": {"median", "sigma", "min", "max"}}``.
* ``max_total``: cap on prompt + output (the server's slot context).
* ``requests``: how many to make for a closed loop; an open loop makes
  as many as ``rate_per_s`` brings in the window.
* ``block`` (optional): lengths come as ``block`` fixed (prompt, output)
  pairs, and every ``block`` consecutive requests hold each pair once.
  Where the server serves only the first few requests of the plan, as
  above its capacity, every seed then serves the same pairs.
* ``shuffle`` (default true): false keeps the sizes and the gaps in one
  order for every seed, which then draws only the prompt tokens. Above
  the server's capacity, where only the first few requests are served,
  the order decides the work of a run.

Every seed gets the same multiset of lengths and gaps, in another order
(or, with ``shuffle`` false, in the same order): lengths and gaps are
taken at evenly spaced quantiles of their distributions and shuffled. The seed also draws the prompt
tokens (a Zipf unigram with repeated motifs, so prompts have the
recurring phrases that retrieval finds). The work of a run is therefore
the same from seed to seed, and only its order and contents change.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    due_s: Optional[float]   # offset from window start; None = closed loop
    prompt: np.ndarray       # (n,) int32
    max_new_tokens: int


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]), np.int64)
    ln = spec["lognormal"]
    mu, sigma = math.log(ln["median"]), float(ln["sigma"])
    nd = NormalDist()
    vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), ln["min"], ln["max"]).astype(np.int64)


class _Tokens:
    """Seeded prompt content: Zipf unigram with repeated motifs."""

    def __init__(self, vocab: int, rng: np.random.Generator,
                 motif_len: int = 16, num_motifs: int = 64,
                 motif_prob: float = 0.5):
        self.rng = rng
        self.vocab = vocab
        cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
        self.cdf = cdf / cdf[-1]
        self.motifs = rng.integers(0, vocab, size=(num_motifs, motif_len))
        self.motif_prob = motif_prob

    def sequence(self, length: int) -> np.ndarray:
        out = np.empty(length, np.int32)
        i = 0
        while i < length:
            if self.rng.random() < self.motif_prob:
                m = self.motifs[self.rng.integers(len(self.motifs))]
                n = min(len(m), length - i)
                out[i:i + n] = m[:n]
            else:
                n = min(int(self.rng.integers(4, 32)), length - i)
                u = self.rng.random(n)
                out[i:i + n] = np.minimum(
                    np.searchsorted(self.cdf, u), self.vocab - 1)
            i += n
        return out


def generate(params: dict, *, vocab_size: int, seed: int,
             seconds: float) -> List[Planned]:
    """Plan the requests of one run: for an open loop, those due in a
    window of ``seconds``; for a closed loop, ``requests`` sessions."""
    rng = np.random.default_rng(seed)
    # what orders the sizes and gaps: the seed, or one fixed order
    order_rng = rng if params.get("shuffle", True) \
        else np.random.default_rng(0)
    loop = params["loop"]
    if loop == "open":
        rate = float(params["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        # exponential gaps at evenly spaced quantiles, shuffled: the same
        # gaps every seed, summing to exactly n / rate
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps = gaps / gaps.sum() * (n / rate)
        gaps = order_rng.permutation(gaps)
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif loop == "closed":
        n = int(params["requests"])
        dues = [None] * n
    else:
        raise ValueError(f"unknown loop {loop!r}")
    k = params.get("block")
    if k:
        plens = _quantile_lengths(params["prompt_len"], k)
        # a fixed pairing of the quantiles, the same for every seed
        olens = _quantile_lengths(params["output_len"], k)[
            np.random.default_rng(0).permutation(k)]
        order = np.concatenate([order_rng.permutation(k)
                                for _ in range(-(-n // k))])[:n]
        plens, olens = plens[order], olens[order]
    else:
        plens = order_rng.permutation(
            _quantile_lengths(params["prompt_len"], n))
        olens = order_rng.permutation(
            _quantile_lengths(params["output_len"], n))
    cap = params.get("max_total")
    if cap is not None:
        olens = np.minimum(olens, cap - plens)
        if (olens < 1).any():
            raise ValueError("max_total leaves no room for an output")
    toks = _Tokens(vocab_size, rng)
    return [Planned(i, None if dues[i] is None else float(dues[i]),
                    toks.sequence(int(plens[i])), int(olens[i]))
            for i in range(n)]
