"""Output tokens that materialized inside the window, per window second."""
NAME, UNIT, BETTER, SOURCE = "out_tok_s", "tokens/s", "higher", "host_clock"


def compute(rec):
    w = rec.window
    n = sum(1 for r in rec.requests for t in r.token_t if w.t0 <= t < w.end)
    return n / (w.end - w.t0)
