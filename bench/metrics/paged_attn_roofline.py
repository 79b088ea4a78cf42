"""Roofline share of the paged attention kernel: the least time its work
needs (bench/counts/attention.py) over its device time, in %."""
from bench.readings import roofline


def read(run):
    return roofline(run, "paged_attn")
