"""Model operations of the live rows (2 per parameter a token uses, plus
attention over each row's context; the output layer for sampled rows
only) over the traced window at the bf16 peak, in %."""
from bench.readings import mfu


def read(run):
    return mfu(run)
