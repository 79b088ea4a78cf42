"""Device-idle ms a gap between consecutive step programs in which the
host was in the engine's admission (``engine_schedule``)."""
from bench.engine_trace import idle_ms


def read(run):
    return idle_ms(run, "schedule")
