"""99th percentile of the gap between consecutive output tokens, pooled
over every request due in the window; tokens are stamped on the host after
the engine step in which they materialize."""
from _stats import percentile, token_gaps

NAME, UNIT, BETTER, SOURCE = "itl_p99_ms", "ms", "lower", "host_clock"


def compute(rec):
    p = percentile(token_gaps(rec), 99)
    return None if p is None else p * 1e3
