"""The BSR kernel's share of its roofline inside prefill programs."""
from _bsr import roofline_share

NAME, UNIT, BETTER, SOURCE = "bsr_roofline.prefill", "%", "higher", "device_trace"
LAYER = "BSR kernel"
MOVES = "itl_p99_ms"


def compute(rec):
    return roofline_share(rec, "prefill")
