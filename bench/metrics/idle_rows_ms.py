"""Device-idle ms a gap between consecutive step programs in which the
host was filling the step's row buffers (``engine_rows``)."""
from bench.engine_trace import idle_ms


def read(run):
    return idle_ms(run, "rows")
