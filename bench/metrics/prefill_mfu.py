"""Model operations of the live rows of mixed steps over those steps'
device time at the bf16 peak, in %."""
from bench.readings import mfu


def read(run):
    return mfu(run, "mixed")
