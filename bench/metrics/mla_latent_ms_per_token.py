"""Own device time of MLA's latent projections in the decode step in
the traced window, per output token decoded in it (ms): the ops under
the program's ``mla.latent`` scope inside ``model.dense`` (the latent
down-projection, the W_UK absorb into the query and the W_UV
decompression of the attended latent). See ``bench/subphases.py``."""
from bench import subphases


def read(run):
    return subphases.ms_per_token(run, ("mla.latent",))
