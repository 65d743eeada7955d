"""Own device time of the ops under the program's ``pariskv.attend`` scope
in the traced window, per output token decoded in it (ms): the token's
K/V append, the winners' K/V gather and sink + window + retrieved
attention. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "pariskv.attend")
