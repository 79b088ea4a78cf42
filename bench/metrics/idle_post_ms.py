"""Device-idle ms a gap between consecutive step programs in which the
host was taking the step's samples home and publishing them
(``engine_wait``, ``engine_samples``, ``engine_publish``, or
``engine_step`` under none of its phases)."""
from bench.engine_trace import idle_ms


def read(run):
    return idle_ms(run, "post")
