"""Per-request and engine-level serving metrics.

Wall-clock numbers on the CPU container are schedule-comparison signals
(batched vs unbatched, queueing behaviour), not TPU performance claims —
same caveat as `benchmarks/kernels_bench.py`.
"""
from __future__ import annotations

from collections import deque
from dataclasses import MISSING, dataclass, field, fields

# Bound on the retained queue-depth sample window.  Long-running engines
# sample once per step; an unbounded list grew host memory forever, so the
# engine keeps a recent window (for distribution telemetry) plus a running
# max scalar (so `summary()["max_queue_depth"]` still covers the whole
# lifetime, not just the window).
QUEUE_DEPTH_WINDOW = 1024


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1, max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


@dataclass(frozen=True)
class RequestMetrics:
    rid: int
    prompt_len: int
    n_generated: int
    ttft_s: float       # submit -> first token emitted
    latency_s: float    # submit -> finished
    finish_reason: str

    @property
    def decode_tok_s(self) -> float:
        dt = self.latency_s - self.ttft_s
        if self.n_generated <= 1 or dt <= 0:
            return float("nan")
        return (self.n_generated - 1) / dt


@dataclass
class EngineMetrics:
    """Aggregated over one engine lifetime (or between `reset()` calls)."""

    completed: list[RequestMetrics] = field(default_factory=list)
    n_prefill_batches: int = 0
    n_decode_batches: int = 0
    n_decode_rows: int = 0        # sum of cohort batch sizes over decode calls
    n_merges: int = 0
    n_padded_rows: int = 0        # dummy rows added for batch alignment
    n_rebalances: int = 0         # mesh cohorts re-packed on load skew
    # paging='paged' counters.  n_page_moves counts page-granular COPIES
    # (prefix publish snapshots + copy-on-write at the divergence page);
    # cohort merge/retire/rebalance are page-table edits and must add 0 —
    # the invariant the paging tests assert.
    n_page_moves: int = 0
    n_prefix_hits: int = 0        # requests admitted from the radix index
    n_prefix_tokens_reused: int = 0   # prompt tokens whose prefill was skipped
    # temporal='adaptive' counter: timestep planes of encoded spike batches
    # scoring below the policy's min_spikes — the planes whose MXU work the
    # kernel skips.  Counted host-side at encode (the engine's input-side
    # proxy for the device-side in-kernel skip, which cannot report out of
    # a jit trace); pipelined decode-step encodes stay on device and are
    # sampled only at flush, so this is a lower bound there.
    timesteps_skipped: int = 0
    # event-stream ingestion counters (serve/streaming.py): sessions
    # admitted through the scheduler's streaming lane, frames ingested
    # (admission frame + later chunks), and per-frame wait from window
    # completion to the session's first generated token — the streaming
    # latency observable (frame-to-first-token), reported as p50/p99.
    n_stream_sessions: int = 0
    n_stream_windows: int = 0
    stream_frame_latency_s: list = field(default_factory=list)
    # speculation=draft(...) counters: per live row, each round proposes
    # k_eff draft tokens; `acceptance_lengths` accepts a longest prefix and
    # the rest are rejected (proposed == accepted + rejected always).  The
    # round still emits accepted+1 verified tokens per row (the bonus token
    # is the target's own argmax, not a proposal, so it is never "accepted"
    # or "rejected").  acceptance_rate = accepted / proposed in `summary()`.
    n_speculative_rounds: int = 0
    n_draft_batches: int = 0      # fused k-step propose dispatches
    n_draft_prefills: int = 0     # lazy draft-cache (re)builds
    n_tokens_proposed: int = 0
    n_tokens_accepted: int = 0
    n_tokens_rejected: int = 0
    # fault-tolerance counters (serve/handoff.py + Engine.drain/remesh and
    # the pipelined executor's straggler fold)
    n_drained: int = 0            # requests handed off unfinished at drain
    n_remeshes: int = 0           # live serve-mesh re-plans (device loss/gain)
    n_straggler_events: int = 0   # StepTimer detections fed from stage_s
    queue_depth_samples: deque = field(
        default_factory=lambda: deque(maxlen=QUEUE_DEPTH_WINDOW)
    )
    max_queue_depth: int = 0      # running max over ALL samples (unbounded-safe)
    wall_s: float = 0.0
    # Per-stage wall time, filled by the step executor (serve/executor.py):
    # admit / prefill / merge / decode / sample_sync / encode / retire.
    # Under execution='sync' the per-step host wait lands in sample_sync;
    # under 'pipelined' decode is dispatch-only and sample_sync is the
    # deferred drain that overlaps in-flight device work — the breakdown
    # that makes the pipelined-vs-sync difference attributable.
    stage_s: dict[str, float] = field(default_factory=dict)
    # Bytes, in the source dtype, of the param leaves the engine's placement
    # cast to the compute dtype once (`Model.serving_params`): what each
    # step program no longer converts.  0 where nothing was cast (params
    # already in the compute dtype, or a model without the hook).  Set once
    # per placement, so `reset()` keeps it.
    precast_bytes: int = 0

    def record(self, m: RequestMetrics) -> None:
        self.completed.append(m)

    def reset(self) -> None:
        """Zero every aggregate back to a fresh engine's state — the
        measurement-window boundary the class docstring promises; the
        placement's ``precast_bytes`` stays.  The instance is reset in place
        so `engine.metrics` references (executor stage clocks, CacheStore
        move counters) stay live."""
        for f in fields(self):
            if f.name == "precast_bytes":
                continue
            setattr(self, f.name,
                    f.default_factory() if f.default_factory is not MISSING
                    else f.default)

    def sample_queue_depth(self, depth: int) -> None:
        """Record one scheduler queue-depth observation (bounded window +
        running max) — called once per executor step."""
        depth = int(depth)
        self.queue_depth_samples.append(depth)
        if depth > self.max_queue_depth:
            self.max_queue_depth = depth

    @property
    def total_tokens(self) -> int:
        return sum(m.n_generated for m in self.completed)

    @property
    def throughput_tok_s(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s > 0 else float("nan")

    @property
    def mean_decode_batch(self) -> float:
        if not self.n_decode_batches:
            return 0.0
        return self.n_decode_rows / self.n_decode_batches

    def summary(self) -> dict:
        ttfts = sorted(m.ttft_s for m in self.completed)
        lats = sorted(m.latency_s for m in self.completed)
        return {
            "n_requests": len(self.completed),
            "total_tokens": self.total_tokens,
            "wall_s": self.wall_s,
            "throughput_tok_s": self.throughput_tok_s,
            "ttft_s_p50": _percentile(ttfts, 0.50),
            "ttft_s_p99": _percentile(ttfts, 0.99),
            "latency_s_p50": _percentile(lats, 0.50),
            "latency_s_p99": _percentile(lats, 0.99),
            "prefill_batches": self.n_prefill_batches,
            "decode_batches": self.n_decode_batches,
            "mean_decode_batch": self.mean_decode_batch,
            "cohort_merges": self.n_merges,
            "padded_rows": self.n_padded_rows,
            "rebalances": self.n_rebalances,
            "page_moves": self.n_page_moves,
            "prefix_hits": self.n_prefix_hits,
            "prefix_tokens_reused": self.n_prefix_tokens_reused,
            "timesteps_skipped": self.timesteps_skipped,
            "speculative_rounds": self.n_speculative_rounds,
            "draft_batches": self.n_draft_batches,
            "draft_prefills": self.n_draft_prefills,
            "tokens_proposed": self.n_tokens_proposed,
            "tokens_accepted": self.n_tokens_accepted,
            "tokens_rejected": self.n_tokens_rejected,
            "acceptance_rate": (
                self.n_tokens_accepted / max(1, self.n_tokens_proposed)
            ),
            "stream_sessions": self.n_stream_sessions,
            "stream_windows": self.n_stream_windows,
            "frame_to_first_token_s_p50": _percentile(
                sorted(self.stream_frame_latency_s), 0.50
            ),
            "frame_to_first_token_s_p99": _percentile(
                sorted(self.stream_frame_latency_s), 0.99
            ),
            "drained_requests": self.n_drained,
            "remeshes": self.n_remeshes,
            "straggler_events": self.n_straggler_events,
            "max_queue_depth": self.max_queue_depth,
            "precast_bytes": self.precast_bytes,
            "stage_s": {k: self.stage_s[k] for k in sorted(self.stage_s)},
        }
