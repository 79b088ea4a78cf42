"""Mean device time of the decode-only step program."""
from bench.readings import step_ms


def read(run):
    return step_ms(run, "decode")
