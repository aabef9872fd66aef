"""The prefill program's share of the chip's bf16 peak: the analytic
operations of the prompt tokens it served (live rows only) over the
device time of its executions in the trace."""
from _mfu import peak_share

NAME, UNIT, BETTER, SOURCE = "mfu.prefill", "%", "higher", "device_trace"
LAYER = "model step"
MOVES = "itl_p99_ms"


def compute(rec):
    return peak_share(rec, "prefill")
