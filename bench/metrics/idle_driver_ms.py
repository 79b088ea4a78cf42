"""Device-idle ms a gap between consecutive step programs in which the
host was in no engine span: the benchmark driver's own loop."""
from bench.engine_trace import idle_ms


def read(run):
    return idle_ms(run, "driver")
