"""Staged step executors: the engine's host loop, decomposed.

`Engine.step()` used to be a monolith that host-synced every cohort's
sampled tokens (`np.asarray(argmax)`) before the next decode could
dispatch, and ran the packed-spike encode strictly after decode — device
queues drained between steps, the step-level analogue of the serialized
timestep loop the paper's FTP dataflow removes (PAPER.md §4).  This module
makes the stages explicit and composable:

    admit -> prefill -> merge -> decode -> sample -> encode -> retire

Two executors share the stage vocabulary (selected by
``ExecutionPolicy.execution``):

* `SyncExecutor` (``execution='sync'``, the default) — the reference
  semantics: every stage completes (including the sample host sync) before
  the next begins.  Token emission, retirement and metrics are exactly the
  pre-executor engine's.

* `PipelinedExecutor` (``execution='pipelined'``) — keeps the device queue
  full:

  - **on-device token feedback**: the greedy argmax of decode step *t*
    stays on device and feeds the decode of step *t+1* directly; host
    materialization of emitted tokens is deferred behind an in-flight
    window (`Engine(pipeline_depth=...)`, default 2) and only forced when
    EOS checks or retirement actually need the values.  Token *counts* are
    host-known without a sync (each decode emits exactly one token per
    slot), so budget exhaustion never needs the values — with no
    ``eos_id`` the pipeline runs sync-free end to end; with one, EOS is
    discovered up to ``depth-1`` steps late and the speculative decodes
    are discarded by `RequestState.emit` (rows are independent; the
    admission bound ``prompt + max_new <= max_len`` keeps even speculative
    writes inside the cache).
  - **double-buffered spike encode**: the packed-spike encode of the token
    emitted at step *t* dispatches right after step *t*'s decode and
    overlaps the next decode's dispatch instead of trailing it behind a
    host sync (`PackedSpikeCache.update_async`); telemetry materializes it
    lazily.
  - **load-skew rebalancing**: when retirement shrinks a mesh cohort so
    its row count stops dividing the ``data`` axis, the cohort is
    re-packed with dummy rows up to the next multiple
    (`scheduler.rebalance_pad` + `batching.cache_pad_rows`) instead of
    falling back to replicated placement — rows stay sharded down the
    mesh.  Dummy rows are discarded outputs on independent rows, so this
    is a placement change, never a numerics change.

  Pipelining reorders HOST work only — every device computation consumes
  bit-identical inputs (the device argmax IS the token the sync path
  round-trips through the host) — so a bitwise pipelined policy keeps
  token identity and zero-retrace, asserted across the whole parity
  matrix (`tests/test_arch_parity_matrix.py`).

Every stage is timed into `EngineMetrics.stage_s` (surfaced by
`Engine.summary()`), so the pipelined-vs-sync win is attributable: under
``sync`` the per-step host wait shows up in ``sample_sync``; under
``pipelined`` the decode stage is dispatch-only and the deferred drain
overlaps in-flight device work.

The same stage clock marks each stage as a profiler span ``serve.<stage>``
(`jax.profiler.TraceAnnotation`, on the clock of the device trace), inside
one ``serve.step`` step span per `step()`.  Spans carry request identity
while a trace is active: ``prefill`` its ``rids``, ``rows`` and prompt
``length``; ``decode`` its ``rows``, ``live`` rows and attended ``length``;
``retire`` the ``rids`` it finishes.  The host's wait for device values has
child spans of its own: ``serve.sample_sync.wait`` (sampled tokens) and
``serve.encode.wait`` (packed spike words).  With no trace active a span
costs the profiler's enabled check and nothing else.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .batching import bucket_key, pad_batch
from .policy import acceptance_lengths
from .scheduler import Request, RequestState, rebalance_pad


@dataclass
class PendingStep:
    """One decode step whose sampled tokens are still on device.

    ``tokens``: (B,) int32 device argmax (all cohort rows, dummies
    included); ``logits``: (n_live, vocab) device slice of the
    last-position logits, kept only when the engine captures traces."""

    tokens: object
    logits: object | None = None


def _open_span(name: str, args=None, cls=TraceAnnotation):
    """Open a profiler span, or return None when no trace is active.
    ``args`` (a callable returning the span's arguments) runs only while
    tracing."""
    if not TraceAnnotation.is_enabled():
        return None
    s = cls(name, **(args() if args is not None else {}))
    s.__enter__()
    return s


class span:
    """A profiler span alone (no stage time): ``with span(name): ...``."""

    __slots__ = ("name", "_s")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._s = _open_span(self.name)
        return self

    def __exit__(self, *exc):
        if self._s is not None:
            self._s.__exit__(*exc)
        return False


class _StageClock:
    """Accumulate wall time per stage into `EngineMetrics.stage_s`, under a
    profiler span ``serve.<stage>`` whose arguments ``args()`` gives.
    ``trace`` is the open span while a trace is active (arguments known
    only inside the stage go to ``trace.set_metadata``), else None."""

    __slots__ = ("metrics", "name", "args", "t0", "trace")

    def __init__(self, metrics, name: str, args=None):
        self.metrics, self.name, self.args = metrics, name, args

    def __enter__(self):
        self.trace = _open_span("serve." + self.name, self.args)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.metrics.stage_s[self.name] = (
            self.metrics.stage_s.get(self.name, 0.0)
            + time.perf_counter() - self.t0
        )
        if self.trace is not None:
            self.trace.__exit__(*exc)
        return False


def _rids(requests) -> str:
    """Request ids as one span argument: space-separated."""
    return " ".join(str(r.rid) for r in requests)


def _decode_args(cohort, width: int = 1) -> dict:
    n = len(cohort.slots)
    return {"rows": n + cohort.n_dummy, "live": n,
            "length": cohort.length + width}


class SyncExecutor:
    """Reference staged executor: every stage host-completes in order.

    Holds no request state of its own — cohorts, scheduler, metrics and
    the jit'd prefill/decode/encode callables live on the engine; the
    executor owns the *order* and the stage boundaries.
    """

    name = "sync"

    def __init__(self, engine):
        self.engine = engine
        self.n_steps = 0

    def _clock(self, stage: str, args=None) -> _StageClock:
        return _StageClock(self.engine.metrics, stage, args)

    # -- the step loop (shared scaffold; executors differ only in the
    # per-cohort `decode_cohort` body) ---------------------------------------
    def step(self) -> dict:
        """One engine iteration: admit+prefill, merge, decode/sample/encode
        per cohort, retire; one ``serve.step`` span in a trace."""
        self.n_steps += 1
        s = _open_span("serve.step", lambda: {"step_num": self.n_steps},
                       cls=StepTraceAnnotation)
        try:
            return self._step()
        finally:
            if s is not None:
                s.__exit__(None, None, None)

    def _step(self) -> dict:
        e = self.engine
        t0 = time.perf_counter()
        e.metrics.sample_queue_depth(e.scheduler.queue_depth)
        with self._clock("admit"):
            # prefix hits first: they are prefill-free admissions, so they
            # use free slots at page-table cost before any prefill batch
            hit_groups = (e.scheduler.schedule_prefix_hits()
                          if e.prefix_index is not None else [])
            groups = e.scheduler.schedule()
            streams = e.scheduler.schedule_streams()
        for group in hit_groups:
            with self._clock("admit_hits"):
                e.admit_prefix_hits(group)
        for group in groups:
            self.prefill(group)
        for session, req in streams:
            self.admit_stream(session, req)
        with self._clock("ingest"):
            self.ingest()  # stream frames -> chunked incremental prefill
        with self._clock("merge"):
            self.merge()  # flushes merging cohorts (pipelined)
        with self._clock("retire", self._retire_args):
            self.retire()  # requests finished at prefill never enter decode
        for cohort in e.cohorts:
            if cohort.stream is not None:
                continue  # ingesting: generation starts at go-live
            self.decode_cohort(cohort)
        with self._clock("retire", self._retire_args):
            self.retire()
        e.metrics.wall_s += time.perf_counter() - t0
        return {
            "active": e.n_active,
            "queued": e.scheduler.queue_depth,
            "cohorts": len(e.cohorts),
        }

    def _retire_args(self) -> dict:
        """Span arguments of ``retire``: the requests it finishes."""
        return {"rids": _rids(st.request for c in self.engine.cohorts
                              if not c.pending for st in c.slots if st.done)}

    # -- stages -------------------------------------------------------------
    def prefill(self, group: list[Request]) -> None:
        """Batched prefill of one same-bucket group; emits each request's
        first token (TTFT is inherently a host event) and opens a cohort."""
        e = self.engine
        with self._clock("prefill") as clk:
            # bucket_align > 1 (approximate mode): right-pad ragged prompts
            # to the shared bucket length with token 0 — pad tokens are
            # attended, so outputs are approximate; exact mode (align=1)
            # never pads
            P = bucket_key(
                max(r.prompt_len for r in group), e.scheduler.bucket_align
            )
            tokens = np.zeros((len(group), P), np.int32)
            for i, r in enumerate(group):
                tokens[i, : r.prompt_len] = r.prompt
            tokens, n_dummy = pad_batch(tokens, e.batch_align)
            if clk.trace is not None:
                clk.trace.set_metadata(rids=_rids(group),
                                       rows=tokens.shape[0], length=P)
            e.metrics.n_padded_rows += n_dummy
            logits, cache = e.dispatch_prefill(tokens)
            e.metrics.n_prefill_batches += 1
            first_dev = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            first = np.asarray(first_dev)
            slots = [RequestState(r) for r in group]
            e._capture(slots, logits)
            for st, tok in zip(slots, first):
                st.emit(int(tok), e.eos_id)
            cohort = e.new_cohort(
                slots=slots, cache=cache, length=P, n_dummy=n_dummy
            )
            cohort.next_tokens = first_dev  # device feedback for pipelining
            if e.spiking_packed:
                cohort.spikes = e.new_spike_cache()
                cohort.spikes.append(e._slot_spikes(cohort))
            e.cohorts.append(cohort)
            # publish prompts into the radix index NOW, before any decode
            # writes the rows' tail pages (no-op without a prefix index)
            e.publish_prefix(cohort)

    # -- streaming stages (serve/streaming.py) --------------------------------
    def admit_stream(self, session, req: Request) -> None:
        """Admit a stream session into its own cohort: prefill over ONLY
        the first frame's token — a constant (B, 1) shape, so every stream
        admission after the first hits the same jit trace — and emit
        NOTHING.  The argmax of each ingested chunk rides in
        ``cohort.pending`` as the go-live candidate (it only becomes the
        first generated token if no further frame arrives)."""
        e = self.engine
        with self._clock("prefill", lambda: {"rids": str(req.rid), "rows": 1,
                                             "length": 1}):
            f0 = session.frames[0]
            req.prompt = np.asarray([f0.token], np.int32)
            tokens, n_dummy = pad_batch(
                np.asarray([[f0.token]], np.int32), e.batch_align
            )
            e.metrics.n_padded_rows += n_dummy
            logits, cache = e.dispatch_prefill(tokens)
            e.metrics.n_prefill_batches += 1
            cohort = e.new_cohort(
                slots=[RequestState(req)], cache=cache, length=1,
                n_dummy=n_dummy, stream=session,
            )
            cohort.pending.append(PendingStep(
                tokens=jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
                logits=(logits[:1, -1] if e.capture_logits else None),
            ))
            e.record_timestep_skips(f0.words[None])
            e.metrics.n_stream_sessions += 1
            e.metrics.n_stream_windows += 1
            e.cohorts.append(cohort)

    def ingest(self) -> None:
        """Chunked incremental prefill: each newly complete frame of every
        ingesting cohort appends as one (B, 1) decode-shaped dispatch —
        bitwise-identical to the same position of a monolithic prefill
        (cached attention always reduces over the full cache extent with
        position masking) and the same jit trace as a normal decode, so
        streaming adds zero retraces.  Once the stream's close watermark
        lands and every frame is in, the cohort goes live."""
        e = self.engine
        for cohort in e.cohorts:
            session = cohort.stream
            if session is None:
                continue
            session.poll()
            frames = session.frames
            while cohort.length < len(frames):
                f = frames[cohort.length]
                row = [f.token] + [0] * cohort.n_dummy
                tokens = jnp.asarray(row, jnp.int32)[:, None]
                logits, cohort.cache = e.dispatch_decode(
                    tokens, cohort.cache
                )
                cohort.length += 1
                cohort.pending = [PendingStep(
                    tokens=jnp.argmax(
                        logits[:, -1], axis=-1
                    ).astype(jnp.int32),
                    logits=(logits[:1, -1] if e.capture_logits else None),
                )]
                e.record_timestep_skips(f.words[None])
                e.metrics.n_stream_windows += 1
            cohort.slots[0].request.prompt = session.prompt_tokens()
            if session.delivered:
                self._go_live(cohort)

    def _go_live(self, cohort) -> None:
        """The stream closed and every frame is ingested — the prompt is
        final.  Emit the first generated token (the argmax the LAST ingest
        chunk produced, exactly what a monolithic prefill's last position
        yields) and convert the cohort to the normal decode lifecycle."""
        e = self.engine
        session = cohort.stream
        st = cohort.slots[0]
        p = cohort.pending.pop()
        cohort.pending = []
        toks = np.asarray(p.tokens)
        if p.logits is not None:
            e._capture(cohort.slots, np.asarray(p.logits)[:, None])
        st.emit(int(toks[0]), e.eos_id)
        cohort.next_tokens = p.tokens  # device feedback for the next decode
        cohort.stream = None
        if e.spiking_packed:
            cohort.spikes = e.new_spike_cache()
            cohort.spikes.append(e._slot_spikes(cohort))
        # frame-to-first-token latency: every frame of this session waited
        # from its completion until this emit
        now = st.first_token_time
        for f in session.frames:
            e.metrics.stream_frame_latency_s.append(now - f.t_wall)

    def merge(self) -> None:
        """Merge cohorts at the same sequence position (continuous
        batching): caches concat along their batch axes, alignment rows are
        dropped so live rows stay a prefix.  Ingesting stream cohorts never
        merge — their length is still moving."""
        e = self.engine
        if not e.merge_cohorts or len(e.cohorts) < 2:
            return
        by_len: dict[int, list] = {}
        merged = []
        for c in e.cohorts:
            if c.stream is not None:
                merged.append(c)
                continue
            by_len.setdefault(c.length, []).append(c)
        for length, group in by_len.items():
            if len(group) == 1:
                merged.append(group[0])
                continue
            for c in group:
                self.flush(c)  # host state authoritative before re-batching
            caches = [e._live_cache(c) for c in group]
            cache = e.cache_ops.concat(caches)
            slots = [s for c in group for s in c.slots]
            cohort = e.new_cohort(slots=slots, cache=cache, length=length)
            if e.spiking_packed:
                cohort.spikes = group[0].spikes
                for c in group[1:]:
                    cohort.spikes.merge(c.spikes)
            if e.speculative:
                # draft caches ride the merge only when every member has
                # one at the SAME catch-up offset (locals must agree for
                # concat); otherwise drop them — lazily rebuilt
                if (all(c.draft_cache is not None for c in group)
                        and len({c.draft_behind for c in group}) == 1):
                    cohort.draft_cache = e.cache_ops.concat(
                        [c.draft_cache for c in group]
                    )
                    cohort.draft_behind = group[0].draft_behind
                else:
                    for c in group:
                        e.release_draft(c)
            merged.append(cohort)
            e.metrics.n_merges += len(group) - 1
        e.cohorts = merged

    def decode_cohort(self, cohort) -> None:
        """decode -> sample -> encode for one cohort (sync: the sample
        host-sync completes before the next cohort/step dispatches)."""
        e = self.engine
        if self._maybe_speculative(cohort):
            return
        with self._clock("decode", lambda: _decode_args(cohort)):
            logits = self._dispatch_decode(cohort)
        with self._clock("sample_sync"):
            with span("serve.sample_sync.wait"):
                nxt = np.asarray(cohort.next_tokens)
            e._capture(cohort.slots, logits)
            for st, tok in zip(cohort.slots, nxt):
                st.emit(int(tok), e.eos_id)
        with self._clock("encode"):
            self.encode(cohort)

    def _dispatch_decode(self, cohort):
        """Dispatch one decode step; leaves the greedy argmax ON DEVICE in
        ``cohort.next_tokens`` and returns the step's logits (device)."""
        e = self.engine
        if cohort.next_tokens is not None:
            tokens = cohort.next_tokens[:, None]
        else:  # membership changed since the last step: host-built tokens
            last = [st.generated[-1] for st in cohort.slots]
            last += [0] * cohort.n_dummy
            tokens = jnp.asarray(last, jnp.int32)[:, None]
        logits, cohort.cache = e.dispatch_decode(tokens, cohort.cache)
        e.metrics.n_decode_batches += 1
        e.metrics.n_decode_rows += len(cohort.slots)
        cohort.next_tokens = jnp.argmax(
            logits[:, -1], axis=-1
        ).astype(jnp.int32)
        cohort.length += 1
        return logits

    # -- speculative decoding (``ExecutionPolicy.speculation``) --------------
    def _spec_k(self, cohort) -> int:
        """Largest useful proposal length this round.  Bounded by the
        policy's ``k``, by the furthest live row's remaining token budget
        (the verify step always lands at least one bonus target token,
        hence the ``- 1``; shorter rows clip their surplus in
        `RequestState.emit_many`), and by the cache extent (the verify
        window writes ``k + 1`` positions; the scheduler's
        ``speculation_slack`` reserved room for exactly this)."""
        e = self.engine
        budgets = [
            st.request.max_new_tokens - len(st.generated)
            for st in cohort.slots if not st.done
        ]
        if not budgets:
            return 0
        k = min(
            e.policy.speculation.k,
            max(budgets) - 1,
            e.max_len - 1 - cohort.length,
        )
        return max(k, 0)

    def _maybe_speculative(self, cohort) -> bool:
        """Run one propose/verify round instead of a normal decode when
        the policy speculates and the cohort can still use a proposal
        window.  A normal decode desynchronizes the draft cache (the
        draft never sees that token), so falling back releases the draft
        — it lazily rebuilds if a later round speculates again."""
        e = self.engine
        if not e.speculative or cohort.stream is not None:
            return False
        k = self._spec_k(cohort)
        if k < 1:
            e.release_draft(cohort)
            return False
        self.speculative_round(cohort, k)
        return True

    def _ensure_draft(self, cohort) -> None:
        """(Re)build the draft cache from host-known history.  The draft
        state is a pure function of each row's prompt + ``generated[:-1]``
        (everything already FED to the target; the pending last token is
        what the propose chunk feeds), so it can be dropped at any point
        — merge mismatch, remesh, fallback — and reconstructed here with
        one batched draft prefill.  Done and dummy rows get zero-padded
        garbage rows: their proposals are discarded, never emitted."""
        e = self.engine
        if cohort.draft_cache is not None:
            return
        B = len(cohort.slots) + cohort.n_dummy
        L = cohort.length
        tokens = np.zeros((B, L), np.int32)
        for i, st in enumerate(cohort.slots):
            gen = st.generated[:-1] if st.generated else []
            gen = gen[-L:] if len(gen) > L else gen
            Pb = max(0, L - len(gen))
            prompt = np.asarray(st.request.prompt, np.int32)[:Pb]
            tokens[i, : len(prompt)] = prompt
            tokens[i, Pb : Pb + len(gen)] = gen
        cohort.draft_cache = e.dispatch_draft_prefill(tokens)
        cohort.draft_behind = 0

    def _draft_chunk(self, cohort, pending):
        """(B, catchup) token chunk for the propose dispatch: the pending
        token alone, or — when a fully accepted round left the draft one
        position behind — preceded by the previous emitted token so the
        draft catches up inside the same fused dispatch."""
        if cohort.draft_behind == 0:
            return pending[:, None]
        prev = [
            st.generated[-2] if len(st.generated) >= 2 else 0
            for st in cohort.slots
        ]
        prev += [0] * cohort.n_dummy
        return jnp.stack([jnp.asarray(prev, jnp.int32), pending], axis=1)

    def speculative_round(self, cohort, k: int) -> None:
        """One speculative round: draft proposes ``k`` tokens in a single
        fused dispatch (`Engine.dispatch_propose` — k chained decode steps
        with on-device argmax feedback), the target verifies all ``k + 1``
        positions in ONE batched decode, and the longest target-matching
        proposal prefix is emitted plus the bonus target token.

        Emitted tokens are always the TARGET's argmaxes, so the verified
        stream is bitwise identical to non-speculative decoding by
        construction — the draft only decides how many target tokens land
        per dispatch.  Cohort rows share scalar position locals, so the
        cohort advance is the MIN acceptance over live rows; rejected
        positions roll back via `Engine.rewind_cache` (a position/kv_pos
        edit — no page or slot data is copied).  Rounds are synchronous
        even under the pipelined executor (flush first, emit immediately):
        acceptance is a host decision, and only verified tokens ever reach
        `RequestState` — a drain/handoff can never capture half-verified
        speculative progress."""
        e = self.engine
        self.flush(cohort)  # host state authoritative (no-op in sync)
        with self._clock("propose"):
            self._ensure_draft(cohort)
            if cohort.next_tokens is not None:
                pending = cohort.next_tokens
            else:  # membership changed since the last step
                last = [st.generated[-1] for st in cohort.slots]
                last += [0] * cohort.n_dummy
                pending = jnp.asarray(last, jnp.int32)
            chunk = self._draft_chunk(cohort, pending)
            draft_dev, cohort.draft_cache = e.dispatch_propose(
                chunk, cohort.draft_cache, k
            )
            e.metrics.n_draft_batches += 1
        with self._clock("decode", lambda: _decode_args(cohort, k + 1)):
            verify = jnp.concatenate([pending[:, None], draft_dev], axis=1)
            logits, cohort.cache = e.dispatch_decode(verify, cohort.cache)
            e.metrics.n_decode_batches += 1
            e.metrics.n_decode_rows += len(cohort.slots)
        with self._clock("sample_sync"):
            with span("serve.sample_sync.wait"):
                tgt = np.asarray(
                    jnp.argmax(logits, axis=-1).astype(jnp.int32)
                )
                drafts = np.asarray(draft_dev)
            acc = acceptance_lengths(drafts, tgt)
            live = [i for i, st in enumerate(cohort.slots) if not st.done]
            A = int(min((int(acc[i]) for i in live), default=k))
            n_live = len(live)
            e.metrics.n_speculative_rounds += 1
            e.metrics.n_tokens_proposed += k * n_live
            e.metrics.n_tokens_accepted += A * n_live
            e.metrics.n_tokens_rejected += (k - A) * n_live
            if e.capture_logits:
                # one capture+emit per landed position, token-major: the
                # trace grows exactly one row per emitted token, same as
                # the step-at-a-time path
                lg = np.asarray(logits[:, : A + 1], np.float32)
                for j in range(A + 1):
                    e._capture(cohort.slots, lg[:, j : j + 1])
                    for i, st in enumerate(cohort.slots):
                        st.emit(int(tgt[i, j]), e.eos_id)
            else:
                for i, st in enumerate(cohort.slots):
                    st.emit_many(tgt[i, : A + 1], e.eos_id)
            cohort.cache = e.rewind_cache(cohort.cache, k - A)
            if A < k:
                # draft positions past the acceptance point consumed
                # rejected tokens; rewind to one short of the target (the
                # bonus token is pending, not yet fed anywhere)
                cohort.draft_cache = e.rewind_cache(
                    cohort.draft_cache, k - A - 1
                )
                cohort.draft_behind = 0
            else:
                # full acceptance: the draft never consumed its own last
                # proposal — the next propose chunk catches it up
                cohort.draft_behind = 1
            cohort.length += A + 1
            cohort.next_tokens = jnp.asarray(tgt[:, A], jnp.int32)
        with self._clock("encode"):
            self.encode(cohort)

    def encode(self, cohort) -> None:
        """Per-step packed-spike re-encode of each slot's newest token."""
        e = self.engine
        if not e.spiking_packed:
            return
        words = e._slot_spikes(cohort)
        cohort.spikes.update(words)
        e._last_spike_words = words

    def retire(self) -> None:
        """Drop finished requests, gather surviving cache rows, release
        scheduler slots, and (mesh) rebalance skewed cohorts."""
        e = self.engine
        kept = []
        for cohort in e.cohorts:
            if cohort.pending:
                # pipelined cohorts flush before any membership change, so
                # a cohort with in-flight steps has no *known*-done slot
                kept.append(cohort)
                continue
            done = [st for st in cohort.slots if st.done]
            if not done:
                kept.append(cohort)
                continue
            for st in done:
                e._finish(st)
            e.scheduler.release(len(done))
            alive_idx = [i for i, st in enumerate(cohort.slots) if not st.done]
            if not alive_idx:
                e.release_cohort(cohort)  # paged: pages back to the pool
                continue
            cohort.cache = e.cache_ops.take(cohort.cache, alive_idx)
            if cohort.draft_cache is not None:
                # same row set as the target cache: gather survivors (paged
                # draft rows for retired requests decref here)
                cohort.draft_cache = e.cache_ops.take(
                    cohort.draft_cache, alive_idx
                )
            cohort.slots = [cohort.slots[i] for i in alive_idx]
            cohort.n_dummy = 0
            cohort.next_tokens = None  # membership changed: host rebuilds
            if e.spiking_packed:
                cohort.spikes.take(alive_idx)
            self.rebalance(cohort)
            kept.append(cohort)
        e.cohorts = kept

    def rebalance(self, cohort) -> None:
        """Load-skew hook (no-op in sync: today's replicated fallback)."""

    # -- pipelining hooks (no-ops here) -------------------------------------
    def flush(self, cohort) -> None:
        """Materialize any deferred device state (none in sync mode)."""

    def drain(self) -> None:
        """Drain in-flight steps across cohorts (none in sync mode)."""


class PipelinedExecutor(SyncExecutor):
    """In-flight-window executor: decode dispatch never waits on the host.

    ``depth`` is the in-flight window: up to ``depth - 1`` decode steps may
    have un-materialized tokens at any time; each step's drain materializes
    the oldest pending step while the newest executes on device.
    """

    name = "pipelined"

    def __init__(self, engine, depth: int = 2,
                 straggler_threshold: float = 3.0):
        super().__init__(engine)
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        if not engine.row_independent:
            # MoE capacity routing couples batch rows: a done-but-not-yet-
            # materialized slot riding through a speculative decode would
            # change the OTHER rows' results vs sync (which retires it
            # first).  Window 1 materializes each step before the next
            # dispatches, so per-decode cohort membership — and therefore
            # every coupled-row computation — matches sync exactly, while
            # keeping the on-device token feedback (value-identical).
            depth = 1
        self.depth = depth
        # straggler fold (ft/straggler.py): the per-step decode-stage delta
        # from EngineMetrics.stage_s feeds the robust-median detector; a
        # detection forces every cohort through the rebalance re-pack at
        # the end of that step instead of letting a slow shard silently
        # stretch each subsequent decode
        from repro.ft.straggler import StepTimer

        self.step_timer = StepTimer(
            window=32, threshold=straggler_threshold,
            on_straggler=self._on_straggler,
        )
        self._force_repack = False

    def _on_straggler(self, event: dict) -> None:
        self.engine.metrics.n_straggler_events += 1
        self._force_repack = True

    def _step(self) -> dict:
        e = self.engine
        decode_before = e.metrics.stage_s.get("decode", 0.0)
        out = super()._step()
        decode_delta = e.metrics.stage_s.get("decode", 0.0) - decode_before
        if decode_delta > 0.0:  # only steps that actually decoded
            self.step_timer.observe(decode_delta)
        if self._force_repack:
            self._force_repack = False
            self.repack()
        return out

    def repack(self) -> None:
        """Straggler response: flush and re-pack every cohort through the
        load-skew rebalance path — dummy rows re-pad to the data-axis
        multiple so the next decode re-splits rows evenly across shards.
        Row-placement only (dummy rows are discarded outputs), so token
        identity is untouched."""
        e = self.engine
        for cohort in e.cohorts:
            if cohort.stream is not None:
                # ingesting: B is pinned to the admission shape (re-packing
                # would retrace every later ingest chunk); repack at go-live
                continue
            self.flush(cohort)
            cohort.cache = e._live_cache(cohort)
            cohort.next_tokens = None
            self.rebalance(cohort)

    def decode_cohort(self, cohort) -> None:
        """decode (dispatch-only) -> encode (double-buffered) -> drain
        (materialize beyond the in-flight window)."""
        e = self.engine
        if not self._count_alive(cohort):
            # every slot's token budget is (or may be) exhausted once the
            # in-flight steps land: materialize and let retire run
            with self._clock("sample_sync"):
                self.flush(cohort)
            return
        if self._maybe_speculative(cohort):
            # speculative rounds are synchronous (see `speculative_round`):
            # no PendingStep enters the window
            return
        with self._clock("decode", lambda: _decode_args(cohort)):
            logits = self._dispatch_decode(cohort)
            cohort.pending.append(PendingStep(
                tokens=cohort.next_tokens,
                logits=(logits[: len(cohort.slots), -1]
                        if e.capture_logits else None),
            ))
        with self._clock("encode"):
            self.encode(cohort)
        with self._clock("sample_sync"):
            self._drain_cohort(cohort)

    # -- pipelined stage overrides ------------------------------------------
    def _count_alive(self, cohort) -> bool:
        """Host-only liveness: could any slot still accept a token after
        every in-flight step lands?  Uses token COUNTS (deterministic on
        the host — one token per slot per step), never token values, so it
        costs no sync.  EOS (value-dependent) can only end a request
        EARLIER, making this an upper bound — a speculative decode past an
        un-materialized EOS is discarded work, never corruption."""
        window = len(cohort.pending)
        return any(
            not st.done
            and len(st.generated) + window < st.request.max_new_tokens
            for st in cohort.slots
        )

    def encode(self, cohort) -> None:
        """Double-buffered packed-spike encode: dispatched against the
        ON-DEVICE sampled tokens right after decode, so it overlaps the
        next decode's dispatch instead of trailing a host sync; the cache
        materializes it lazily (`PackedSpikeCache.update_async`)."""
        e = self.engine
        if not e.spiking_packed:
            return
        toks = cohort.next_tokens[: len(cohort.slots)]
        cohort.spikes.update_async(e._encode_pack(e.params, toks))

    def _drain_cohort(self, cohort) -> None:
        """Materialize pending steps beyond the in-flight window.  The
        np.asarray here is the host wait the window hides: it overlaps the
        decode steps still executing on device."""
        while len(cohort.pending) >= self.depth:
            if self._materialize(cohort):
                # a slot finished: flush so retire sees host-true state
                self.flush(cohort)

    def _materialize(self, cohort) -> bool:
        """Land the oldest pending step on the host: emit tokens, capture
        logits.  Returns True when a slot finished (EOS or budget)."""
        e = self.engine
        p = cohort.pending.pop(0)
        with span("serve.sample_sync.wait"):
            toks = np.asarray(p.tokens)
        if p.logits is not None:
            e._capture(cohort.slots, np.asarray(p.logits)[:, None])
        for st, tok in zip(cohort.slots, toks):
            st.emit(int(tok), e.eos_id)
        return any(st.done for st in cohort.slots)

    def flush(self, cohort) -> None:
        """Materialize ALL in-flight steps (forced before merge/retire and
        when the cohort's budget is exhausted).  An ingesting stream
        cohort's ``pending`` holds its go-live candidate, NOT an emitted
        step — only `_go_live` may land it."""
        if cohort.stream is not None:
            return
        while cohort.pending:
            self._materialize(cohort)
        if self.engine.spiking_packed and cohort.spikes is not None:
            self.engine._last_spike_words = cohort.spikes.latest()
            # decode-step encodes stayed on device (update_async); score the
            # flushed state so temporal='adaptive' telemetry reflects this
            # executor too (a sampled lower bound — see EngineMetrics)
            if self.engine.policy.temporal.enabled:
                self.engine.record_timestep_skips(
                    np.asarray(cohort.spikes.words)
                )

    def drain(self) -> None:
        for cohort in self.engine.cohorts:
            self.flush(cohort)

    def rebalance(self, cohort) -> None:
        """Re-pack a mesh cohort whose surviving rows stopped dividing the
        data axis: pad dummy rows (zero cache rows, discarded outputs) up
        to the next multiple so batch leaves stay sharded down the mesh
        instead of replicating — the load-skew half of this executor."""
        e = self.engine
        if e.mesh is None or not e.row_independent:
            return
        dn = e.mesh.shape.get("data", 1)
        pad = rebalance_pad(len(cohort.slots), dn)
        if pad == 0:
            return
        cohort.cache = e.cache_ops.pad_rows(cohort.cache, pad)
        if cohort.draft_cache is not None:
            # keep the draft's row set mirroring the target's (dummy draft
            # rows propose garbage that is never emitted)
            cohort.draft_cache = e.cache_ops.pad_rows(cohort.draft_cache, pad)
        cohort.n_dummy = pad
        e.metrics.n_rebalances += 1
        e.metrics.n_padded_rows += pad


def make_executor(engine, policy, *, depth: int = 2) -> SyncExecutor:
    """Build the executor the policy's ``execution`` axis names."""
    if policy.execution == "pipelined":
        return PipelinedExecutor(engine, depth=depth)
    return SyncExecutor(engine)
