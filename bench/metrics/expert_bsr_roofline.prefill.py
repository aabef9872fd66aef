"""The grouped expert BSR kernel's share of its roofline inside prefill
programs: the least time of its calls in the traced prefill dispatches
(two per layer, over each dispatch's tokens times top-k routed rows;
`counts_moe.grouped_bsr_call`) over the device time of the `_bsr_call`
kernels there, which in an expert model are the grouped calls alone."""
import sys

import counts
import counts_moe
from _stats import traced

NAME, UNIT, BETTER, SOURCE = (
    "expert_bsr_roofline.prefill", "%", "higher", "device_trace")
LAYER = "expert BSR kernel"
MOVES = "itl_p99_ms"


def compute(rec):
    conf = counts_moe.expert_conf(rec.shapes)
    ds = traced(rec, "prefill")
    if conf is None or not ds or rec.trace is None:
        return None
    secs = rec.trace.kernel_s.get(("prefill", "bsr"), 0.0)
    if secs <= 0:
        return None
    least, bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for d in ds:
        for which in ("up", "down"):
            t, b = counts.roofline_s(
                *counts_moe.grouped_bsr_call(conf, d.rows * d.length, which),
                rec.peak)
            least += rec.shapes.L * t
            bound[b] += rec.shapes.L * t
    print(f"{NAME}: {rec.trace.kernel_calls.get(('prefill', 'bsr'), 0)} "
          f"kernel calls in the trace for {len(ds)} dispatches x "
          f"{2 * rec.shapes.L}; least time {least!r} s "
          f"({bound['compute']!r} s compute-bound, {bound['memory']!r} s "
          f"memory-bound) over {secs!r} s", file=sys.stderr)
    return 100.0 * least / secs
