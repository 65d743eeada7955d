"""Own device time of the MoE FFN of the decode step in the traced
window, per output token decoded in it (ms): the ops under the program's
``moe.route``, ``moe.experts`` and ``moe.shared`` scopes inside
``model.dense``. See ``bench/subphases.py``."""
from bench import subphases

NAMES = ("moe.route", "moe.experts", "moe.shared")


def read(run):
    return subphases.ms_per_token(run, NAMES)
