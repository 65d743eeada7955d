"""Own device time of the ops under the program's ``model.dense`` scope in
the traced window, per output token decoded in it (ms): the dense model:
embed, norms, q/k/v/o projections, MLP, LM head, argmax. See
``bench/phases.py``."""
from bench import phases


def read(run):
    return phases.ms_per_token(run, "model.dense")
