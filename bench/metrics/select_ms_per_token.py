"""Own device time of the ops under the program's ``pariskv.select`` scope
in the traced window, per output token decoded in it (ms): choosing the
top-C Stage-I candidates and their pool rows. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "pariskv.select")
