"""Own device time of the ops under the program's ``pariskv.stage1`` scope
in the traced window, per output token decoded in it (ms): Stage-I
collision scoring: the ``_collision_paged_pallas`` kernel (or its twin)
and its glue. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "pariskv.stage1")
