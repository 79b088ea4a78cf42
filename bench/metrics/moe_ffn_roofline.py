"""Roofline share of the ragged expert FFN kernel: the least time its
work needs (bench/counts/moe_ffn.py) over its device time, in %."""
from bench.readings import roofline


def read(run):
    return roofline(run, "moe_ffn")
