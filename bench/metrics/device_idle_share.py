"""1 - (union of device op intervals) / (traced window) (%)."""
from bench import counts


def read(run):
    return counts.idle_share(run)
