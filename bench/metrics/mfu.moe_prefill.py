"""The prefill program's share of the chip's bf16 peak in an expert
model: the analytic operations of the prompt tokens it served (live rows
only; experts at top-k, attention windowed on sliding layers, the head
at the last token: `counts_moe.prefill_flops`) over the device time of
its executions in the trace."""
import counts_moe
from _stats import traced

NAME, UNIT, BETTER, SOURCE = "mfu.moe_prefill", "%", "higher", "device_trace"
LAYER = "model step"
MOVES = "itl_p99_ms"


def compute(rec):
    conf = counts_moe.expert_conf(rec.shapes)
    ds = traced(rec, "prefill")
    secs = rec.trace.program_s.get("prefill", 0.0) if rec.trace else 0.0
    if conf is None or not ds or secs <= 0:
        return None
    flops = sum(counts_moe.prefill_flops(conf, d.length, d.live) for d in ds)
    return 100.0 * flops / (secs * rec.peak["bf16_flops"])
