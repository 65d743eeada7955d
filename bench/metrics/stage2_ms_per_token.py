"""Own device time of the ops under the program's ``pariskv.stage2`` scope
in the traced window, per output token decoded in it (ms): Stage II: the
candidates' rerank (``_rerank_pallas`` or its twin), the final top-k and
the winner rows. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "pariskv.stage2")
