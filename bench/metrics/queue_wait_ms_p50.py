"""Median, over requests due in the window, of due time to the chunk
boundary at which the request left the engine's queue (ms); a request
still queued at the window's end enters at the window's end."""
import math

import numpy as np


def read(run):
    v = [((run.t_end if math.isnan(r.left_queue) else r.left_queue) - r.due)
         for r in run.recs]
    return float(np.percentile(np.asarray(v) * 1e3, 50)) if v else None
