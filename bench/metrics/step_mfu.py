"""Model FLOPs of the tokens the traced window processed, over the
window's length at the chip's bf16 peak (%)."""
from bench import counts


def read(run):
    return counts.step_mfu(run)
