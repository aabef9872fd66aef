"""The BSR kernel's share of its roofline inside decode programs."""
from _bsr import roofline_share

NAME, UNIT, BETTER, SOURCE = "bsr_roofline.decode", "%", "higher", "device_trace"
LAYER = "BSR kernel"
MOVES = "itl_p90_ms"


def compute(rec):
    return roofline_share(rec, "decode")
