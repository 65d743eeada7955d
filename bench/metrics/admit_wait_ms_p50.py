"""Median, over requests due in the window, of the engine's own stamps
from ``submit()`` to the queue pop, ``Request.t_admit - t_submit`` (ms);
a request still queued at the window's end enters at the window's end.
Nothing where the program does not stamp its requests."""
import numpy as np


def read(run):
    v = []
    for r in run.recs:
        t_submit = getattr(r.req, "t_submit", None)
        if t_submit is None:
            continue
        t_admit = r.req.t_admit
        v.append((run.t_end if t_admit is None else t_admit) - t_submit)
    return float(np.percentile(np.asarray(v) * 1e3, 50)) if v else None
