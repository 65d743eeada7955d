"""The comparison that decides ``correct``.

Once the window has closed, a sample of the served requests, drawn from
the seed and always holding the longest, is run through the plain
float32 reference of the configuration's architecture file
(``bench/archs/<arch>.py``, built on ``bench.reference``) with its
served tokens fed back. At each served position the reading is the gap
by which the served token's reference logit lies below the reference's
best logit there (0 where the served token is the reference's argmax).
Three numbers are read: the widest gap (``logit_gap``), the widest gap
of the first tokens (made by the prompt's fill, ``first_token_gap``)
and the mean gap (``mean_logit_gap``). The cell file's
``correctness.limits`` names the numbers compared and their limits,
which ``PERF.md`` derives from the program's readings and the float8
control's.
"""
from __future__ import annotations

import json
import sys
from typing import List, Tuple

import numpy as np

NONE_SERVED = 1e9   # the value compared when nothing could be compared


def sample(run, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(prompt, served tokens) of the requests to compare: those that
    finished in the run and those still being served at the window's
    close, with the tokens they were served."""
    cor = run.cell.cell["correctness"]
    cands = []
    for rec in run.recs:
        if rec.req is None or rec.req.failed:
            continue
        toks = rec.tokens()
        if len(toks) > 0:
            cands.append((rec.prompt, toks[:cor["max_tokens"]]))
    if not cands:
        return []
    longest = max(range(len(cands)),
                  key=lambda i: len(cands[i][0]) + len(cands[i][1]))
    rest = [i for i in range(len(cands)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(rest)[:max(cor["requests"] - 1, 0)].tolist()
    return [cands[i] for i in [longest] + sorted(pick)]


STATS = ("logit_gap", "first_token_gap", "mean_logit_gap")


def _stats(per_request) -> dict:
    """The readings over a sample: per request the gaps of its served
    tokens, the first of which comes from the prompt's fill."""
    if not per_request:
        return {k: NONE_SERVED for k in STATS}
    allg = np.concatenate(per_request)
    return {"logit_gap": float(allg.max()),
            "first_token_gap": float(max(g[0] for g in per_request)),
            "mean_logit_gap": float(allg.mean())}


def readings(run, reqs, seed: int, control: bool = False) -> dict:
    """Readings of the served tokens over ``reqs`` and, with ``control``,
    of the tokens the float8 control puts first at the same positions."""
    import jax
    from bench import reference as R
    from bench import weights
    out = {"tokens": sum(len(s) for _, s in reqs)}
    if not reqs:
        out.update(served=_stats([]), control=_stats([]))
        return out
    arch, pk = run.arch, run.cell.config["pariskv"]
    dims = arch.dims(run.mc)
    n_max = run.cell.cell["engine"]["n_max"]
    params = weights.make(arch.layout(run.mc), run.mc, seed)
    seq_rows, dec_rows = R.shapes_for(reqs)
    layer = arch.make_layer(dims, pk, n_max, lo=False)
    layer_lo = (arch.make_layer(dims, pk, n_max, lo=True) if control
                else None)
    served, ctrl = [], []
    for prompt, toks in reqs:
        ref = arch.request_logits(params, dims, pk, prompt, toks, seq_rows,
                                  dec_rows, layer)
        g = R.gaps(ref, toks)
        served.append(g)
        line = (f"[check] prompt {len(prompt)} served {len(toks)}: gap "
                f"max {g.max():.6f} first {g[0]:.6f} mean {g.mean():.6f}, "
                f"{int((g > 0).sum())} tokens not the reference's argmax")
        if control:
            lo = arch.request_logits(params, dims, pk, prompt, toks,
                                     seq_rows, dec_rows, layer_lo, lo=True)
            cg = R.gaps(ref, np.asarray(jax.numpy.argmax(lo, -1)))
            ctrl.append(cg)
            line += (f"; control gap max {cg.max():.6f} first "
                     f"{cg[0]:.6f} mean {cg.mean():.6f}")
        print(line, file=sys.stderr, flush=True)
        del ref
    del params
    out.update(served=_stats(served), control=_stats(ctrl))
    print(f"[readings] {json.dumps(out)}", file=sys.stderr, flush=True)
    return out


def check(run, reqs, seed: int, control: bool = False):
    """The numbers compared, each with its limit (the cell file's
    ``correctness.limits``). Returns the program's checks, the float8
    control's checks at the same limits (``control`` only, else None: the
    benchmark's own runs do not run it) and the readings."""
    cor = run.cell.cell["correctness"]
    r = readings(run, reqs, seed, control=control)

    def judged(who):
        out = {k: {"value": r[who][k], "limit": lim, "rule": "<="}
               for k, lim in cor["limits"].items()}
        out["tokens_compared"] = {"value": r["tokens"],
                                  "limit": cor["min_tokens"], "rule": ">="}
        return out
    return judged("served"), judged("control") if control else None, r


def passed(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    return v <= lim if c["rule"] == "<=" else v >= lim
