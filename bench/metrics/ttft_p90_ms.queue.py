"""90th percentile of time to first token, from each request's due time,
over every request due in the window (those finished in the drain
included).  Read per layer: at 0.8 x the knee the queue for the four
slots makes its runs spread too widely to hold an end-to-end bound."""
from _stats import percentile, ttfts

NAME, UNIT, BETTER, SOURCE = "ttft_p90_ms.queue", "ms", "lower", "host_clock"
LAYER = "engine and scheduler"
MOVES = "out_tok_s"


def compute(rec):
    p = percentile(ttfts(rec), 90)
    return None if p is None else p * 1e3
