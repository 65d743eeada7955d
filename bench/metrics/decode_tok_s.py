"""Output tokens emitted in the window over its length (tokens/s). The
window runs from the first chunk boundary to the end of the last chunk
started inside ``--seconds``, so no chunk is cut."""


def read(run):
    n = sum(1 for rec in run.recs for t in rec.token_times()
            if run.t0 < t <= run.t_end)
    return n / (run.t_end - run.t0)
