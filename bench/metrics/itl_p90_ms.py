"""90th percentile of the gap between consecutive output tokens, pooled
over every request due in the window; tokens are stamped on the host after
the engine step in which they materialize.  In the decode-heavy cell it
is the gap of a decode step under the cohorts in flight: p99 there lies
among the ~2 % of gaps that also hold a prefill, and flips between the
two populations from run to run (PERF.md)."""
from _stats import percentile, token_gaps

NAME, UNIT, BETTER, SOURCE = "itl_p90_ms", "ms", "lower", "host_clock"


def compute(rec):
    p = percentile(token_gaps(rec), 90)
    return None if p is None else p * 1e3
