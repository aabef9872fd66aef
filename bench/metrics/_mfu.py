"""A step program's share of the chip's bf16 peak."""
import counts
from _stats import traced


def peak_share(rec, program: str):
    """The analytic operations of the live rows the program's traced
    dispatches served (`counts.prefill_flops` / `counts.decode_flops`)
    over the device time of its executions in the trace, over the peak."""
    ds = traced(rec, program)
    secs = rec.trace.program_s.get(program, 0.0) if rec.trace else 0.0
    if not ds or secs <= 0:
        return None
    if program == "prefill":
        flops = sum(counts.prefill_flops(rec.shapes, d.length, d.live) for d in ds)
    else:
        flops = sum(counts.decode_flops(rec.shapes, [d.length] * d.live) for d in ds)
    return 100.0 * flops / (secs * rec.peak["bf16_flops"])
