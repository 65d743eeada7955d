"""Own device time of the ops under the program's ``pariskv.tier_table``
scope in the traced window, per output token decoded in it (ms):
building the Stage-I tier table: the query transform, centroid scores
and tier weights. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "pariskv.tier_table")
