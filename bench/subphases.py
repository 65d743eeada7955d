"""Device time of what a model adds inside a decode phase: the names the
program opens within ``model.dense`` (``moe.route``, ``moe.experts``,
``moe.shared``, ``mla.latent``), which split that phase without changing
``bench/phases.py``'s partition (an op keeps its outermost phase).

An op is charged here when its outermost phase is ``model.dense`` and its
scope path holds one of the names asked for; ops of the chunked fill sit
under ``model.fill`` and never count. The instruction -> scope map comes
from ``bench/phases.py`` (``scope_map`` of ``chunk_text``), computed once
a run and kept on it. A program that opens none of these names reads
nothing (None), and so does a run with no trace.
"""
from __future__ import annotations

from typing import Optional, Sequence

from bench import phases

DENSE = "model.dense"


def _scopes(run) -> dict:
    if "sub_scopes" not in vars(run):
        run.sub_scopes = phases.scope_map(phases.chunk_text(run))
    return run.sub_scopes


def seconds(run, names: Sequence[str]) -> Optional[float]:
    """Own device seconds in the traced window of the ``model.dense`` ops
    whose scope path holds one of ``names``; None where the trace holds
    no device op or the program opens none of them."""
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    scopes = _scopes(run)

    def ours(op_name: str) -> bool:
        return (phases.outermost(op_name) == DENSE
                and any(p in names for p in op_name.split("/")))

    if not any(ours(n) for n in scopes.values()):
        return None
    return sum(t for op, t in run.trace["op_s"].items()
               if ours(scopes.get(op, "")))


def ms_per_token(run, names: Sequence[str]) -> Optional[float]:
    """``seconds`` per output token decoded in the traced window (ms)."""
    from bench.counts import traced_work
    secs = seconds(run, names)
    if secs is None:
        return None
    tokens = traced_work(run)["decode_tokens"]
    return 1e3 * secs / tokens if tokens else None
