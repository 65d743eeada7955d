"""Own device time of the ops under none of the program's phase scopes in
the traced window, per output token decoded in it (ms): the loops' own
time, copies, and other programs' ops. See ``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, phases.UNSCOPED)
