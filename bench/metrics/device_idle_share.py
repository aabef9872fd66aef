"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / (traced window)."""
NAME, UNIT, BETTER, SOURCE = "device_idle_share", "%", "lower", "device_trace"
LAYER = "device"
MOVES = "out_tok_s"


def compute(rec):
    if rec.trace is None or rec.window.trace_span is None:
        return None
    lo, hi = rec.window.trace_span
    if hi is None or hi <= lo or rec.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / (hi - lo))
