"""Own device time of the ops under the program's ``model.fill`` scope in
the traced window, per output token decoded in it (ms): the chunked
prefill inside the mixed steps. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "model.fill")
