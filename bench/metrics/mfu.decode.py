"""The decode program's share of the chip's bf16 peak: the analytic
operations of the tokens it decoded (live rows only) over the device time
of its executions in the trace."""
from _mfu import peak_share

NAME, UNIT, BETTER, SOURCE = "mfu.decode", "%", "higher", "device_trace"
LAYER = "model step"
MOVES = "itl_p90_ms"


def compute(rec):
    return peak_share(rec, "decode")
