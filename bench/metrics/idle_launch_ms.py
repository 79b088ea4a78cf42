"""Device-idle ms a gap between consecutive step programs in which the
host was dispatching the step (``engine_launch``)."""
from bench.engine_trace import idle_ms


def read(run):
    return idle_ms(run, "launch")
