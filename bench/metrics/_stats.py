"""Shared arithmetic of the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """Linear-interpolated percentile, or None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def ttfts(rec) -> list[float]:
    """Seconds from each request's due time to its first token, over every
    request due in the window that got one."""
    t0 = rec.window.t0
    return [r.token_t[0] - (t0 + r.due) for r in rec.requests if r.token_t]


def token_gaps(rec) -> list[float]:
    """Seconds between consecutive output tokens, pooled over requests."""
    out = []
    for r in rec.requests:
        t = r.token_t
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def traced(rec, kind: str):
    """The dispatches of ``kind`` made inside the traced span."""
    if rec.trace is None or rec.window.trace_span is None:
        return []
    lo, hi = rec.window.trace_span
    return [d for d in rec.dispatches if d.kind == kind and lo <= d.t <= hi]
