"""90th percentile, over every request with two or more tokens in the
window, of (last - first token time) / (tokens - 1) (ms)."""
import numpy as np


def read(run):
    v = []
    for rec in run.recs:
        t = [x for x in rec.token_times() if run.t0 < x <= run.t_end]
        if len(t) >= 2:
            v.append((t[-1] - t[0]) / (len(t) - 1))
    return float(np.percentile(np.asarray(v) * 1e3, 90)) if v else None
