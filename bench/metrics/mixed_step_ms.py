"""Mean device time of the mixed (prefill chunk + decode) step program."""
from bench.readings import step_ms


def read(run):
    return step_ms(run, "mixed")
