"""Roofline share of the BSR kernel inside one step program."""
import sys

import counts
from _stats import traced


def roofline_share(rec, program: str):
    """Sum over the program's traced dispatches of each BSR call's least
    time (two calls per layer, rows as dispatched, dummy rows included:
    the kernel runs them) over the kernel's device time inside that
    program.  Prints which bound sets the least time."""
    ds = traced(rec, program)
    if not ds or rec.trace is None:
        return None
    secs = rec.trace.kernel_s.get((program, "bsr"), 0.0)
    if secs <= 0:
        return None
    least, bound = 0.0, {"compute": 0.0, "memory": 0.0}
    for d in ds:
        m = d.rows * d.length if program == "prefill" else d.rows
        for which in ("up", "down"):
            t, b = counts.roofline_s(*counts.bsr_call(rec.shapes, m, which),
                                     rec.peak)
            least += rec.shapes.L * t
            bound[b] += rec.shapes.L * t
    calls = rec.trace.kernel_calls.get((program, "bsr"), 0)
    print(f"bsr_roofline.{program}: {calls} kernel calls in the trace for "
          f"{len(ds)} dispatches x {2 * rec.shapes.L}; least time "
          f"{least!r} s ({bound['compute']!r} s compute-bound, "
          f"{bound['memory']!r} s memory-bound) over {secs!r} s",
          file=sys.stderr)
    return 100.0 * least / secs
