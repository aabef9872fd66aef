"""Continuous-batching serving engine over the registry's Model interface.

One engine serves any registered arch (transformer / MoE / rwkv6 / zamba2 /
spiking-FFN LM): it only touches `model.prefill`, `model.decode`,
`model.init_cache` and `model.cache_axes`, and manipulates the cache pytree
through `serve.batching` (per-leaf batch axes located via the logical-axes
tree).

Execution model — each `step()` runs the staged executor
(`serve/executor.py`) the policy's ``execution`` axis selects:

    admit -> prefill -> merge -> decode -> sample -> encode -> retire

1. admit waiting requests: prefill groups (same prompt length, FIFO) run
   as one batched prefill each and emit their first token (TTFT);
2. cohorts at the same sequence position merge, so new prefills join
   in-flight decode (continuous batching, preemption-free);
3. every cohort advances one greedy decode step;
4. finished requests retire, their cache rows are dropped, and the freed
   slots admit more prefills on the next step.

Under ``execution='sync'`` (default) every stage host-completes in order —
the reference semantics, token-identical to the single-shot loop this
module replaced (`launch/serve.py`).  ``execution='pipelined'`` keeps the
device queue full: sampled tokens stay on device between decode steps
(step *t*'s argmax feeds step *t+1* directly), host materialization is
deferred behind an in-flight window (``pipeline_depth``), the packed-spike
encode double-buffers against the next decode, and mesh cohorts re-pack on
load skew — see `serve/executor.py`.  Pipelining reorders host work only,
so bitwise policies keep token identity in either mode.

MIGRATION NOTE (`step()` semantics under ``execution='pipelined'``): a
`step()` still dispatches one decode per cohort, but tokens land in
`RequestState.generated` up to ``pipeline_depth - 1`` steps later, when
their step materializes (EOS discovery and retirement lag by the same
window; `run()`/`generate_batch` drain fully, so their results are
unchanged).  External steppers that inspect `generated` mid-flight should
call `Engine.flush()` first.

Every execution choice is ONE declarative `ExecutionPolicy`
(`serve/policy.py`) — spike format, weight sparsity, placement, exactness,
execution — consumed here and by kernel dispatch:

* ``spike_format='packed'`` switches the in-model spiking FFN to the packed
  inference path (scoped to the engine's prefill/decode calls; training
  traces elsewhere in the process keep the differentiable float path), so
  SNN layers carry uint32 spike words (not unpacked (T, ...) float32
  planes) through every engine step, and keeps a `PackedSpikeCache` of each
  slot's direct-encoded current token between steps — spike-domain
  telemetry at the cost of one small jit'd encode per decode step.

* ``weight_sparsity='dual_sparse'`` (the `for_arch` default for LTH-pruned
  spiking archs): engine construction attaches per-layer weight join plans
  (`models.layers.attach_spiking_ffn_plans` — host work, once) and every
  spiking FFN GEMM runs through the BSR kernel, which joins the static
  weight plan with a device-computed spike activity map in-kernel.
  Requests only change spike values, never shapes, so serving steps hit
  the jit cache — no per-request host join and no recompilation.

* ``placement`` (serve/sharding.py) runs the whole engine
  data/model-parallel over a (data, model) device mesh: request batches and
  cohort caches shard down the `data` axis, weight join plans column-split
  across the `model` axis (each shard joins only its own slab against the
  device-local spike activity map), and the policy's `model_sharded_dims`
  pick which weight dims column-shard.  Per-request placement is
  canonicalized so zero-retrace-across-requests survives the mesh.  No
  mesh = exactly the unsharded engine.

* ``exactness='bitwise'`` (default) keeps every mesh mode token-identical
  to single-device serving (reduction-free placement only).
  ``exactness=approximate(tol)`` opts into psum-TP of attention/MLP on the
  model axis (the training rules in `repro.sharding`, throughput over
  exactness): greedy tokens may flip, logit drift is bounded by ``tol``
  (`serve.policy.check_parity`), and the engine captures per-request logit
  traces so drift is measurable.

* ``execution='sync'|'pipelined'`` picks the step executor (above) —
  orthogonal to exactness, so bitwise/approximate parity gating composes
  with pipelining unchanged.

Prompts need not be complete at submit time: `submit_stream` queues a
`StreamSession` (serve/streaming.py) whose prompt materializes
incrementally from sensor event frames — the session is admitted once its
first window lands, later windows ingest into the in-flight cohort as
decode-shaped chunks, and generation starts at the stream's close
watermark, token-identical to submitting the same frames as one prompt.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lif import direct_encode
from repro.core.packing import pack_spikes
from repro.models.layers import rows_independent

from .batching import DenseCacheOps, PackedSpikeCache, spike_sparsity_of
from .executor import make_executor, span
from .metrics import EngineMetrics, RequestMetrics
from .policy import ExecutionPolicy
from .scheduler import AdmissionTicket, Request, RequestState, Scheduler


def _precast_bytes(base, served) -> int:
    """Bytes, in their source dtype, of the leaves `serving_params` cast."""
    return sum(
        int(a.nbytes)
        for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(served))
        if a.dtype != b.dtype
    )


@dataclass
class Cohort:
    """A set of in-flight requests sharing one batched cache.

    Cache rows: the first `len(slots)` batch rows are live requests (in
    slot order); `n_dummy` alignment rows follow and are dropped at the
    first membership change (or re-created by the pipelined executor's
    load-skew rebalancing).

    ``next_tokens`` is the ON-DEVICE greedy argmax of the last
    prefill/decode (all rows, dummies included) — the token feedback the
    next decode consumes without a host round-trip; None after any
    membership change (the executor rebuilds from host state).
    ``pending`` is the pipelined executor's in-flight window: decode steps
    dispatched but not yet host-materialized (always empty in sync mode).

    ``stream`` marks an INGESTING cohort (serve/streaming.py): its prompt
    is still arriving as event frames, so it is excluded from merge and
    decode, and ``pending`` holds the single un-emitted step the last
    ingest chunk produced — the first generated token once the stream
    closes (executor ``_go_live``).  None for normal cohorts and after
    go-live.

    ``draft_cache`` is the speculative draft policy's own cache for the
    cohort (same layout as ``cache``, paged rows from the same CacheStore
    under paging).  Built LAZILY at the cohort's first speculative round
    from host-known history and dropped (None) whenever keeping it in sync
    would need anything beyond a pure row edit — it is always
    reconstructible, never authoritative.  ``draft_behind=1`` marks the
    draft cache one position short of the target's (a fully-accepted round
    never fed the draft its own last proposal); the next propose feeds a
    2-token catch-up chunk.
    """

    slots: list[RequestState]
    cache: object
    length: int                 # tokens written per row (prompt + generated)
    n_dummy: int = 0
    spikes: PackedSpikeCache | None = None
    next_tokens: object | None = None
    pending: list = field(default_factory=list)
    stream: object | None = None
    draft_cache: object | None = None
    draft_behind: int = 0


class Engine:
    def __init__(
        self,
        model,
        params,
        *,
        max_len: int,
        max_slots: int = 8,
        max_queue: int = 256,
        batch_align: int = 1,
        bucket_align: int = 1,
        eos_id: int | None = None,
        merge_cohorts: bool = True,
        policy: ExecutionPolicy | None = None,
        capture_logits: bool | None = None,
        logit_trace_window: int | None = None,
        pipeline_depth: int = 2,
        page_pool_rows: int | None = None,   # paging='paged': pool capacity
        prefix_cache: bool | None = None,    # paging='paged': radix index
        preemption=None,                     # ft.preemption.PreemptionHandler
    ):
        cfg = model.cfg
        if not cfg.supports_decode or cfg.encoder_only:
            raise ValueError(f"{cfg.name} has no decode path; cannot serve")
        if policy is None:
            # default: the arch-independent float/dense policy (explicitly
            # opt into packed/dual-sparse/mesh via ExecutionPolicy.for_arch)
            policy = ExecutionPolicy()
        policy.validate_for(cfg)
        self.policy = policy
        mesh = policy.mesh
        self.model = model
        # the UNTRANSFORMED host param tree: `_configure_placement` derives
        # self.params (cast leaves, sharded, join plans attached) from it,
        # and `remesh` re-derives from it for a different mesh
        self._base_params = params
        self.cfg = cfg
        self.max_len = max_len
        self.eos_id = eos_id
        self.mesh = mesh
        # preemption drain (ft/preemption.py): when the handler's
        # should_stop flips, step() closes admission and run() returns so
        # the owner can call drain() -> Handoff (serve/handoff.py)
        self.preemption = preemption
        # Logit traces (rid -> [last-position logits per emitted token]):
        # captured by default under approximate exactness, where drift vs. a
        # bitwise reference is the contract being measured (check_parity).
        self.capture_logits = (
            not policy.token_identical
            if capture_logits is None else bool(capture_logits)
        )
        if logit_trace_window is not None and logit_trace_window < 1:
            raise ValueError(
                f"logit_trace_window must be >= 1 (got {logit_trace_window});"
                " use None for unbounded capture"
            )
        self.logit_trace_window = logit_trace_window
        self.logit_traces: dict[int, list[np.ndarray]] = {}
        # each row computed from that row alone: only capacity routing
        # couples rows (`layers.rows_independent`); it gates cohort merges,
        # batch alignment and the prefix cache
        self.row_independent = rows_independent(cfg)
        self._user_batch_align = batch_align
        self.merge_cohorts = merge_cohorts and self.row_independent
        self.metrics = EngineMetrics()
        self._axes = model.cache_axes()
        # -- speculative decoding (ExecutionPolicy.speculation) --------------
        # Rollback after a partially-accepted verify is a pure position
        # rewind: stale KV slots keep kv_pos > every later query position,
        # so absolute-position masking hides them until a genuine write
        # overwrites slot + kv_pos.  That only works for caches whose ONLY
        # cross-step carry is (seq slots, position counters) — a per-row
        # recurrent state ("batch" leaf without "cache_seq") has no rewind.
        self.speculative = policy.speculation.enabled
        if self.speculative:
            axes_leaves = jax.tree.leaves(
                self._axes, is_leaf=lambda x: isinstance(x, tuple)
            )
            stateful = [
                ax for ax in axes_leaves
                if isinstance(ax, tuple) and "batch" in ax
                and "cache_seq" not in ax
            ]
            if stateful:
                raise ValueError(
                    f"{cfg.name} carries non-rewindable per-row cache state "
                    f"(leaf axes {stateful[0]}); speculative rollback cannot "
                    "undo a recurrent update — use speculation='none'"
                )
            if not any(ax == () for ax in axes_leaves):
                raise ValueError(
                    f"{cfg.name}'s cache has no scalar position local to "
                    "rewind; speculation needs one"
                )
        # -- cache backend (ExecutionPolicy.paging) --------------------------
        # dense: per-cohort pytrees, eager concat/take/pad.  paged: page
        # tables into one engine-wide CacheStore; cohort membership changes
        # are table edits, and a radix prefix index can serve repeated
        # prompts without a prefill (serve/paging.py).
        self.paged = policy.paging.enabled
        self.store = None
        self.prefix_index = None
        if self.paged:
            from .paging import CacheStore, PageLayout, PagedCacheOps, RadixPrefixIndex

            template = model.init_cache(1, max_len)
            self._page_layout = PageLayout(
                template, self._axes, policy.paging.page_size
            )
            n_rows = (page_pool_rows if page_pool_rows is not None
                      else (2 * max_slots + 4)
                      * (2 if self.speculative else 1))
            self.store = CacheStore(
                self._page_layout, n_rows, mesh=mesh, metrics=self.metrics
            )
            self.cache_ops = PagedCacheOps(self.store)
            # prefix reuse needs: deterministic tokens (the entry caches the
            # first greedy token), independent rows, exact-length buckets
            # (a bucket-padded row's cache holds pad-token state), and no
            # logit capture (a hit emits its first token with no logits row)
            auto_prefix = (
                policy.token_identical and self.row_independent
                and bucket_align == 1 and not self.capture_logits
            )
            if prefix_cache is True and not auto_prefix:
                raise ValueError(
                    "prefix_cache=True needs a bitwise policy with "
                    "independent rows, bucket_align=1 and capture_logits "
                    "off — the hit path re-emits a cached greedy first "
                    "token and skips its prefill (no logits to capture)"
                )
            want_prefix = (auto_prefix if prefix_cache is None
                           else bool(prefix_cache))
            if want_prefix:
                self.prefix_index = RadixPrefixIndex(self.store)
        else:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True requires policy.paging='paged'"
                )
            self.cache_ops = DenseCacheOps(self._axes)
        self.scheduler = Scheduler(
            max_slots=max_slots, max_queue=max_queue, max_len=max_len,
            bucket_align=bucket_align, prefix_index=self.prefix_index,
            speculation_slack=(policy.speculation.k
                               if self.speculative else 0),
        )
        self.cohorts: list[Cohort] = []
        self.results: dict[int, RequestState] = {}
        # resume replay ledger (serve/handoff.py): rid -> the tokens the
        # predecessor already emitted; _finish asserts the replayed prefix
        self._resume_expect: dict[int, np.ndarray] = {}
        self.handoff_prefix_keys: list[np.ndarray] = []
        self.spiking_packed = policy.spike_format == "packed"
        # Dual-sparse packed-spike serving (the `for_arch` default for
        # pruned spiking archs): at load time (once per placement) the LTH
        # hard zeros in the stored params become per-layer weight join
        # plans; per-request only the spike side of the join runs, on
        # device, inside the kernel.
        self.spiking_dual_sparse = policy.weight_sparsity == "dual_sparse"
        # the newest packed spike words an encode produced (host or device);
        # `summary()` scores them, so no step pays for the telemetry
        self._last_spike_words = None
        self._spike_pool = None
        if self.paged and self.spiking_packed:
            from .paging import SpikeSlotPool

            self._spike_pool = SpikeSlotPool(
                self.cfg.d_model,
                (page_pool_rows if page_pool_rows is not None
                 else 2 * max_slots + 4),
            )
        self._configure_placement(policy)
        self.executor = make_executor(self, policy, depth=pipeline_depth)

    def _configure_placement(self, policy: ExecutionPolicy) -> None:
        """(Re)derive every placement-dependent attribute from ``policy``:
        admission batch alignment, params placement (`serving_params`
        casts, then model-axis sharding BEFORE join plans attach, while the
        tree still matches the model's logical-axes tree), and the jitted
        dispatch callables — which capture the mesh at trace time and
        therefore must be rebuilt on `remesh`.  Always derives from
        `_base_params`, so re-configuring is idempotent and
        mesh-agnostic."""
        self.policy = policy
        mesh = policy.mesh
        self.mesh = mesh
        self.batch_align = (
            self._user_batch_align if self.row_independent else 1
        )
        if mesh is not None and self.row_independent:
            # admission alignment: pad prefill batches up to the data axis
            # so fresh cohorts shard evenly down the mesh from step one
            dn = mesh.shape.get("data", 1)
            self.batch_align = max(self.batch_align, dn)
        # leaves the step programs read only in the compute dtype are cast
        # once here, not in every call (`transformer.serving_params`)
        served = params = self.model.serving_params(self._base_params)
        self.metrics.precast_bytes = _precast_bytes(self._base_params, served)
        if mesh is not None:
            # weights on the model axis; the POLICY picks the dim set —
            # reduction-free under bitwise exactness, psum-TP attention/MLP
            # dims under approximate (see serve/sharding.py)
            from .sharding import shard_params

            params = shard_params(
                params, self.model.axes(), mesh,
                sharded_dims=policy.model_sharded_dims(),
            )
        if self.spiking_dual_sparse:
            from repro.models.layers import attach_spiking_ffn_plans

            shards = mesh.shape.get("model", 1) if mesh is not None else 1
            params = attach_spiking_ffn_plans(
                params, self.cfg, model_shards=shards
            )
            if mesh is not None:
                from .sharding import place_plans

                params = place_plans(params, mesh)
        self.params = params
        # cache donation: each call consumes its cache and returns the
        # successor, so the buffer can be updated in place on accelerators
        self._prefill = self._engine_scope(
            jax.jit(self.model.prefill, donate_argnums=(2,))
        )
        self._decode = self._engine_scope(
            jax.jit(self.model.decode, donate_argnums=(2,))
        )
        if self.spiking_packed:
            cfg = self.cfg
            self._encode_pack = jax.jit(
                lambda p, toks: pack_spikes(
                    direct_encode(
                        p["embed"][toks].astype(jnp.float32), cfg.spiking_T
                    )
                )
            )
        if self.paged:
            # paged model wrappers: gather page tables -> dense view ->
            # unchanged model fn -> scatter written pages (serve/paging.py).
            # Pools are donated so the scatter updates them in place.
            self._paged_prefill = self._engine_scope(jax.jit(
                self._page_layout.make_prefill(
                    self.model, self.max_len, mesh, self._axes
                ),
                donate_argnums=(2,),
            ))
            self._paged_decode = self._engine_scope(jax.jit(
                self._page_layout.make_decode(self.model, mesh, self._axes),
                donate_argnums=(2,),
            ))
        if self.speculative:
            self._configure_draft(policy, served)

    def _configure_draft(self, policy: ExecutionPolicy, served) -> None:
        """Derive the draft policy's params/plans/jits next to the target's.

        The draft runs the SAME base weights on the SAME mesh placement;
        what differs is the execution mode captured at trace time (spiking
        float vs packed path) and, under ``draft_weight_density``, a
        further-pruned FFN copy with its own (sparser) `WeightJoinPlan`s.
        Rebuilt by every `_configure_placement` call, so `remesh` re-shards
        the draft exactly like the target.  Propose jits are built lazily
        per (catchup, k) — at most two trace shapes per k in steady state.
        ``served`` is the target's `serving_params` tree, whose cast leaves
        the draft shares.
        """
        spec = policy.speculation
        mesh = self.mesh
        params = served
        if spec.draft_weight_density is not None:
            from repro.models.layers import derive_draft_params

            params = derive_draft_params(
                params, self.cfg, spec.draft_weight_density
            )
        if mesh is not None:
            from .sharding import shard_params

            params = shard_params(
                params, self.model.axes(), mesh,
                sharded_dims=policy.model_sharded_dims(),
            )
        if spec.draft.weight_sparsity == "dual_sparse":
            from repro.models.layers import attach_spiking_ffn_plans

            shards = mesh.shape.get("model", 1) if mesh is not None else 1
            params = attach_spiking_ffn_plans(
                params, self.cfg, model_shards=shards
            )
            if mesh is not None:
                from .sharding import place_plans

                params = place_plans(params, mesh)
        self.draft_params = params
        self._propose_jits: dict[tuple[int, int], object] = {}
        self._draft_prefill = self._draft_scope(
            jax.jit(self.model.prefill, donate_argnums=(2,))
        )
        if self.paged:
            self._paged_draft_prefill = self._draft_scope(jax.jit(
                self._page_layout.make_prefill(
                    self.model, self.max_len, mesh, self._axes
                ),
                donate_argnums=(2,),
            ))

    def _draft_scope(self, fn):
        """`_engine_scope`'s draft-policy twin: installs the DRAFT policy's
        spiking mode at trace time (float drafts run the surrogate float
        path even when the target serves packed — the forward values are
        identical, which is what makes a float-dense draft a perfect-
        acceptance proposal source) plus the shared serve mesh."""
        draft = self.policy.speculation.draft

        def scoped(*args):
            from repro.kernels import ops
            from repro.models import layers as model_layers

            prev = model_layers.get_spiking_ffn_mode()
            prev_mesh = ops.get_serve_mesh()
            model_layers.set_spiking_ffn_mode(
                "infer" if draft.spike_format == "packed" else "train"
            )
            if self.mesh is not None:
                ops.set_serve_mesh(self.mesh)
            try:
                return fn(*args)
            finally:
                model_layers.set_spiking_ffn_mode(prev)
                ops.set_serve_mesh(prev_mesh)

        return scoped

    def _engine_scope(self, fn):
        """Run `fn` with the engine's trace-time context installed: the
        spiking FFN in packed-inference mode (restoring the previous —
        training — mode afterwards, so a later train-step trace in the same
        process keeps the differentiable float path) and, under a mesh, the
        serve mesh the sharded kernel entries dispatch on.  Both are read at
        trace time, so scoping them to the engine's calls is enough."""
        if not self.spiking_packed and self.mesh is None:
            return fn

        def scoped(*args):
            from repro.kernels import ops
            from repro.models import layers as model_layers

            prev = model_layers.get_spiking_ffn_mode()
            prev_mesh = ops.get_serve_mesh()
            if self.spiking_packed:
                model_layers.set_spiking_ffn_mode("infer")
            if self.mesh is not None:
                ops.set_serve_mesh(self.mesh)
            try:
                return fn(*args)
            finally:
                model_layers.set_spiking_ffn_mode(prev)
                ops.set_serve_mesh(prev_mesh)

        return scoped

    # -- request API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int) -> AdmissionTicket:
        """Queue one request; returns its `AdmissionTicket` (outcome,
        prefix-hit info).  Raises `AdmissionError` (carrying a rejected
        ticket) when the request cannot be accepted."""
        return self.scheduler.submit(prompt, max_new_tokens)

    def submit_stream(self, session, max_new_tokens: int) -> AdmissionTicket:
        """Queue a `StreamSession` (serve/streaming.py): a request whose
        prompt arrives incrementally as event frames.  The session waits in
        the scheduler's streaming lane until its first window completes,
        then is admitted into its own cohort; later frames ingest into the
        in-flight cohort and generation starts at the stream's close
        watermark.  Binds the session's frame budget to this engine's
        geometry (``max_len - max_new_tokens``), so over-long streams
        surface as `streaming.Backpressure` instead of cache overflow."""
        if self.spiking_packed and session.T != self.cfg.spiking_T:
            raise ValueError(
                f"stream session T={session.T} != engine spiking_T="
                f"{self.cfg.spiking_T}; frame words must score against the "
                "policy's temporal axis"
            )
        ticket = self.scheduler.submit_stream(session, max_new_tokens)
        session.max_frames = self.max_len - max_new_tokens
        return ticket

    @property
    def n_active(self) -> int:
        return sum(len(c.slots) for c in self.cohorts)

    @property
    def idle(self) -> bool:
        return not self.cohorts and self.scheduler.queue_depth == 0

    @property
    def stopping(self) -> bool:
        """True once a preemption notice landed (or admission was closed
        by `drain`): `run()` returns and the owner should `drain()`."""
        return (
            (self.preemption is not None and self.preemption.should_stop)
            or self.scheduler.closed
        )

    # -- engine steps -------------------------------------------------------
    def new_cohort(self, **kw) -> Cohort:
        """Cohort factory for the executor (keeps `Cohort` engine-owned)."""
        return Cohort(**kw)

    def step(self) -> dict:
        """One engine iteration — delegated to the policy's executor.
        When a preemption notice is pending, admission closes first so the
        step only advances in-flight cohorts (new submits are rejected
        with a ``draining`` ticket).

        With an empty queue and no in-flight cohorts the step is a
        guaranteed cheap no-op: no dispatch, no retrace, no metrics
        sample.  Streaming drivers tick the engine between frames and
        trace replays (`benchmarks.fig13_14_traffic.replay_trace`) step it
        as an arrival clock — idle ticks must stay free."""
        if (self.preemption is not None and self.preemption.should_stop
                and not self.scheduler.closed):
            self.scheduler.close()
        if self.idle:
            return {"active": 0, "queued": 0, "cohorts": 0}
        return self.executor.step()

    def flush(self) -> None:
        """Materialize every in-flight pipelined step (no-op under sync):
        after this, `RequestState.generated` reflects all dispatched
        decodes.  `run()` drains implicitly; external steppers that read
        results mid-flight call this."""
        self.executor.drain()

    def run(self) -> dict[int, np.ndarray]:
        """Drive steps until drained; returns {rid: generated tokens}.
        Returns early (with partial results) once `stopping` flips — the
        preemption path; the owner then calls `drain()` for the handoff."""
        while not self.idle and not self.stopping:
            self.step()
        return {
            rid: np.asarray(st.generated, np.int32)
            for rid, st in sorted(self.results.items())
        }

    def generate_batch(
        self, prompts, max_new_tokens: int
    ) -> list[np.ndarray]:
        """Convenience: submit prompts, drain, return outputs in order."""
        reqs = [self.submit(p, max_new_tokens) for p in prompts]
        out = self.run()
        return [out[r.rid] for r in reqs]

    # -- preemption drain / handoff / resume (serve/handoff.py) --------------
    def drain(self, *, step_budget: int | None = None):
        """Preemption drain: close admission, run in-flight cohorts to
        completion (or for at most ``step_budget`` more steps — the drain
        grace), then tear down and return the `Handoff` a successor
        engine resumes from.

        Zero tokens are lost: every dispatched decode is materialized
        (`flush`) before in-flight progress is captured, finished results
        ride the handoff as data, and unfinished/waiting requests are
        re-queued on the successor for deterministic replay.  Mid-ingest
        stream cohorts cannot finish (their streams stay open), so they
        hand off best-effort: the frames completed so far become the
        successor request's prompt."""
        from .handoff import capture_handoff

        self.scheduler.close()
        budget = step_budget
        while (
            self.cohorts
            and any(c.stream is None for c in self.cohorts)
            and (budget is None or budget > 0)
        ):
            self.step()
            if budget is not None:
                budget -= 1
        self.flush()           # land every in-flight pipelined step
        self.executor.retire()  # requests that finished during the grace
        inflight: list[RequestState] = []
        for cohort in self.cohorts:  # grace expired with live requests
            inflight.extend(cohort.slots)
            self.scheduler.release(len(cohort.slots))
            self.release_cohort(cohort)
        self.cohorts = []
        drained = self.scheduler.drain()
        self.metrics.n_drained += len(inflight) + len(drained)
        return capture_handoff(self, drained, inflight)

    @classmethod
    def resume(cls, model, params, handoff, **engine_kwargs) -> "Engine":
        """Build a successor engine from a drain handoff.

        Engine geometry (max_len/max_slots/max_queue/bucket_align/eos_id)
        defaults to the predecessor's recorded values; ``policy`` and any
        override ride ``engine_kwargs``.  Finished results are pre-loaded
        (they were already recorded by the predecessor — they are not
        re-counted in this engine's metrics); waiting and in-flight
        requests re-queue under their ORIGINAL rids with full budgets —
        deterministic replay, which under a bitwise policy reproduces the
        predecessor's tokens exactly.  Each in-flight request's handed-off
        progress is asserted against its replay at finish (`_finish`), so
        a lost token is an error, not a silent truncation."""
        meta = handoff.meta
        engine_kwargs.setdefault("max_len", meta["max_len"])
        engine_kwargs.setdefault("max_slots", meta["max_slots"])
        engine_kwargs.setdefault("max_queue", meta["max_queue"])
        engine_kwargs.setdefault("bucket_align", meta["bucket_align"])
        engine_kwargs.setdefault("eos_id", meta["eos_id"])
        eng = cls(model, params, **engine_kwargs)
        eng.handoff_prefix_keys = [
            np.asarray(k, np.int32) for k in handoff.prefix_keys
        ]
        eng.scheduler.reserve_ids(handoff.max_rid + 1)
        for hr in handoff.requests:
            req = Request(
                hr.rid, np.asarray(hr.prompt, np.int32), hr.max_new_tokens
            )
            if hr.state == "finished":
                st = RequestState(req)
                st.generated = [int(t) for t in hr.generated]
                st.finish_reason = hr.finish_reason
                st.first_token_time = st.finish_time = req.submit_time
                eng.results[hr.rid] = st
                continue
            eng.scheduler.restore(req)
            if (hr.state == "inflight" and hr.generated.size
                    and eng.policy.token_identical):
                eng._resume_expect[hr.rid] = np.asarray(
                    hr.generated, np.int32
                )
        return eng

    # -- elastic re-mesh (ft/elastic.py) -------------------------------------
    def remesh(self, devices=None, *, mesh=None,
               model_parallel: int | None = None) -> dict:
        """Re-plan the serve mesh for a changed device set and re-shard
        LIVE: params and `WeightJoinPlan` column slabs re-derive from the
        base tree through the same mesh-agnostic rules as construction,
        dispatch re-jits (the old traces captured the old mesh), and paged
        caches survive as page-table re-splits — pool arrays re-place, no
        page is copied (`EngineMetrics.n_page_moves` unchanged; the test
        asserts the zero delta).  Dense cohort caches re-place lazily at
        their next dispatch.  Bitwise policies stay token-identical across
        the re-mesh (reduction-free placement is mesh-size-invariant).

        Pass surviving ``devices`` (planned via `ft.elastic.plan_serve_mesh`
        at the current — or ``model_parallel`` — TP degree), or an explicit
        ``mesh`` (None = single-device).  Returns a summary dict."""
        from .policy import Placement
        from .sharding import mesh_summary

        if mesh is None and devices is not None:
            from repro.ft.elastic import plan_serve_mesh

            mp = model_parallel
            if mp is None:
                mp = (self.mesh.shape.get("model", 1)
                      if self.mesh is not None else 1)
            mesh = plan_serve_mesh(list(devices), model_parallel=mp)
        elif mesh is None and devices is None:
            raise ValueError("remesh needs devices=... or mesh=...")
        old = self.mesh
        unchanged = (
            (mesh is None and old is None)
            or (mesh is not None and old is not None
                and dict(mesh.shape) == dict(old.shape)
                and list(mesh.devices.flat) == list(old.devices.flat))
        )
        if unchanged:
            return {"remeshed": False, **mesh_summary(old)}
        import dataclasses

        new_policy = dataclasses.replace(
            self.policy,
            placement=Placement(
                mesh=mesh, model_dims=self.policy.placement.model_dims
            ),
        )
        new_policy.validate_for(self.cfg)
        # host-truth every deferred device artifact before placement flips:
        # pending pipelined steps, device token feedback, async spike words
        self.flush()
        for cohort in self.cohorts:
            cohort.next_tokens = None  # rebuilt from host state next decode
            # draft caches are lazily reconstructible from host history;
            # dropping them beats round-tripping a second cache per cohort
            self.release_draft(cohort)
            if cohort.spikes is not None:
                cohort.spikes._sync()
            # cohort device state still lives on the OLD device set; a jit
            # on the new mesh cannot mix the two, so hop through the host.
            # Paged cohorts only carry their position locals (tables are
            # host arrays, pages live in the re-placed pools); dense
            # cohorts round-trip the cache itself (dense remesh cannot
            # avoid moving cache bytes — that's what paging buys).
            if self.paged:
                cohort.cache.locals = [
                    jnp.asarray(np.asarray(x)) for x in cohort.cache.locals
                ]
            else:
                cohort.cache = jax.tree.map(
                    lambda a: jnp.asarray(np.asarray(a)), cohort.cache
                )
        moves_before = self.metrics.n_page_moves
        self._configure_placement(new_policy)
        if self.paged:
            # page-table re-split: pool arrays re-place onto the new mesh
            # (or back to single-device); tables/refcounts/free lists are
            # host state and survive untouched — zero page copies
            from .sharding import place_pool

            self.store.mesh = mesh
            self.store.pools = {
                k: (place_pool(jnp.asarray(np.asarray(v)), mesh)
                    if mesh is not None
                    else jnp.asarray(np.asarray(v)))
                for k, v in self.store.pools.items()
            }
        assert self.metrics.n_page_moves == moves_before, (
            "remesh must not copy cache pages"
        )
        self.metrics.n_remeshes += 1
        return {"remeshed": True, **mesh_summary(mesh)}

    # -- executor services --------------------------------------------------
    def _slot_spikes(self, cohort: Cohort) -> np.ndarray:
        toks = jnp.asarray(
            [st.generated[-1] for st in cohort.slots], jnp.int32
        )
        words_dev = self._encode_pack(self.params, toks)
        with span("serve.encode.wait"):
            words = np.asarray(words_dev)
        self.record_timestep_skips(words)
        return words

    def record_timestep_skips(self, words: np.ndarray) -> None:
        """Count the timestep planes of one packed batch that the policy's
        temporal scorer marks skippable (`EngineMetrics.timesteps_skipped`).

        Host-side replica of `core.packing.timestep_activity_map`'s rule
        over words already materialized for dispatch — the in-kernel skip
        happens on device inside a jit trace and cannot report back, so the
        engine scores the same planes at the encode boundary instead.
        """
        if not self.policy.temporal.enabled or words.size == 0:
            return
        T = self.cfg.spiking_T
        bits = np.unpackbits(
            np.ascontiguousarray(words, dtype=np.uint32).view(np.uint8),
            bitorder="little",
        )
        counts = bits.reshape(-1, 32)[:, :T].sum(axis=0)
        skipped = int((counts < self.policy.temporal.min_spikes).sum())
        self.metrics.timesteps_skipped += skipped

    def new_spike_cache(self):
        """Per-cohort packed-spike store matching the cache backend."""
        if self._spike_pool is not None:
            from .paging import PagedSpikeCache

            return PagedSpikeCache(
                self.cfg.spiking_T, self.cfg.d_model, self._spike_pool
            )
        return PackedSpikeCache(self.cfg.spiking_T, self.cfg.d_model)

    def _live_cache(self, cohort: Cohort):
        if cohort.n_dummy == 0:
            return cohort.cache
        idx = list(range(len(cohort.slots)))
        cohort.n_dummy = 0
        if cohort.draft_cache is not None:
            # the draft cache mirrors the target's row set exactly (built
            # with the same dummy rows), so dummy-dropping edits both
            cohort.draft_cache = self.cache_ops.take(cohort.draft_cache, idx)
        return self.cache_ops.take(cohort.cache, idx)

    # -- model dispatch (cache-backend aware) -------------------------------
    def dispatch_prefill(self, tokens: np.ndarray):
        """Run one batched prefill over host tokens (B, P); returns
        (device logits, cohort cache) — a dense pytree or a `PagedCache`
        whose freshly allocated pages the prefill scattered in full."""
        if not self.paged:
            cache = self.model.init_cache(tokens.shape[0], self.max_len)
            tokens_dev = jnp.asarray(tokens)
            if self.mesh is not None:
                from .sharding import place_cache, place_tokens

                cache = place_cache(cache, self._axes, self.mesh)
                tokens_dev = place_tokens(tokens_dev, self.mesh)
            return self._prefill(
                self.params, {"tokens": tokens_dev}, cache
            )
        from .paging import PagedCache

        seq_t, state_t = self.store.alloc_rows(tokens.shape[0])
        tokens_dev = jnp.asarray(tokens)
        if self.mesh is not None:
            from .sharding import place_tokens

            tokens_dev = place_tokens(tokens_dev, self.mesh)
        seq_dev, state_dev = self._tables_dev(seq_t, state_t)
        logits, pools, locals_ = self._paged_prefill(
            self.params, tokens_dev, self.store.pools, seq_dev, state_dev
        )
        self.store.pools = pools
        return logits, PagedCache(self.store, seq_t, state_t, locals_)

    def dispatch_decode(self, tokens, cache):
        """One decode step for a cohort; returns (device logits, cache').
        Owns mesh placement in both backends, so the executor never
        branches on the cache layout."""
        if not self.paged:
            if self.mesh is not None:
                # re-normalize placement: merge/retire build caches with
                # eager concat/gather whose output layout is ad hoc; one
                # canonical sharding per cache shape keeps the jit warm
                from .sharding import place_cache, place_tokens

                cache = place_cache(cache, self._axes, self.mesh)
                tokens = place_tokens(tokens, self.mesh)
            return self._decode(self.params, tokens, cache)
        if self.mesh is not None:
            from .sharding import place_tokens

            tokens = place_tokens(tokens, self.mesh)
        seq_dev, state_dev = self._tables_dev(
            cache.seq_table, cache.state_table
        )
        logits, pools, locals_ = self._paged_decode(
            self.params, tokens, self.store.pools, seq_dev, state_dev,
            cache.locals,
        )
        self.store.pools = pools
        cache.locals = locals_
        return logits, cache

    def _tables_dev(self, seq_t: np.ndarray, state_t: np.ndarray):
        if self.mesh is not None:
            from .sharding import place_replicated

            return (place_replicated(seq_t, self.mesh),
                    place_replicated(state_t, self.mesh))
        return jnp.asarray(seq_t), jnp.asarray(state_t)

    # -- speculative dispatch (ExecutionPolicy.speculation) ------------------
    def _make_propose_fn(self, catchup: int, k: int):
        """Dense fused propose: ``catchup - 1`` feed positions + ``k``
        chained greedy draft steps, argmax token feedback staying on device,
        all in ONE dispatch (the Python loop unrolls at trace time — k and
        catchup are static)."""
        model = self.model

        def propose(params, chunk, cache):
            if catchup > 1:
                _, cache = model.decode(params, chunk[:, : catchup - 1], cache)
            tok = chunk[:, catchup - 1]
            out = []
            for _ in range(k):
                logits, cache = model.decode(params, tok[:, None], cache)
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                out.append(tok)
            return jnp.stack(out, axis=1), cache

        return propose

    def dispatch_propose(self, chunk, draft_cache, k: int):
        """Draft-propose ``k`` tokens per row; returns ((B, k) device draft
        tokens, draft_cache').  ``chunk`` is the (B, 1) pending token, or
        (B, 2) [last-verified, pending] when the draft cache is one behind.
        """
        catchup = int(chunk.shape[1])
        key = (catchup, k)
        fn = self._propose_jits.get(key)
        if not self.paged:
            if fn is None:
                fn = self._draft_scope(jax.jit(
                    self._make_propose_fn(catchup, k), donate_argnums=(2,)
                ))
                self._propose_jits[key] = fn
            if self.mesh is not None:
                from .sharding import place_cache, place_tokens

                draft_cache = place_cache(draft_cache, self._axes, self.mesh)
                chunk = place_tokens(chunk, self.mesh)
            return fn(self.draft_params, chunk, draft_cache)
        if fn is None:
            fn = self._draft_scope(jax.jit(
                self._page_layout.make_propose(
                    self.model, k, catchup, self.mesh, self._axes
                ),
                donate_argnums=(2,),
            ))
            self._propose_jits[key] = fn
        if self.mesh is not None:
            from .sharding import place_tokens

            chunk = place_tokens(chunk, self.mesh)
        seq_dev, state_dev = self._tables_dev(
            draft_cache.seq_table, draft_cache.state_table
        )
        draft_tokens, pools, locals_ = fn(
            self.draft_params, chunk, self.store.pools, seq_dev, state_dev,
            draft_cache.locals,
        )
        self.store.pools = pools
        draft_cache.locals = locals_
        return draft_tokens, draft_cache

    def dispatch_draft_prefill(self, tokens: np.ndarray):
        """Build a draft cache by prefilling host-known history under the
        draft policy (the lazy draft-cache rebuild — see `Cohort`).  Returns
        the cache only; the prefill logits are the draft's opinion of the
        NEXT token and the verified stream never consults it outside a
        propose."""
        self.metrics.n_draft_prefills += 1
        if not self.paged:
            cache = self.model.init_cache(tokens.shape[0], self.max_len)
            tokens_dev = jnp.asarray(tokens)
            if self.mesh is not None:
                from .sharding import place_cache, place_tokens

                cache = place_cache(cache, self._axes, self.mesh)
                tokens_dev = place_tokens(tokens_dev, self.mesh)
            _, cache = self._draft_prefill(
                self.draft_params, {"tokens": tokens_dev}, cache
            )
            return cache
        from .paging import PagedCache

        seq_t, state_t = self.store.alloc_rows(tokens.shape[0])
        tokens_dev = jnp.asarray(tokens)
        if self.mesh is not None:
            from .sharding import place_tokens

            tokens_dev = place_tokens(tokens_dev, self.mesh)
        seq_dev, state_dev = self._tables_dev(seq_t, state_t)
        _, pools, locals_ = self._paged_draft_prefill(
            self.draft_params, tokens_dev, self.store.pools, seq_dev,
            state_dev,
        )
        self.store.pools = pools
        return PagedCache(self.store, seq_t, state_t, locals_)

    def rewind_cache(self, cache, steps: int):
        """Rewind a cache's position counters by ``steps`` — the rollback
        of rejected speculative writes.  Stale KV *content* past the
        rewound position needs no copy-back: the next genuine decode
        overwrites slot data and kv_pos alike.  Rejected PAGES need no
        decref either: the rewound position re-covers the same pages the
        over-write touched (span-clamped, row-private), so the row's page
        set is unchanged.

        The ``kv_pos`` ring-slot vectors ARE restored, not just masked:
        entries ``>= new_pos`` are reset to ``-1`` (the empty-slot init
        marker).  That is an *exact* rollback, not an approximation — the
        scheduler's admission bound keeps every position below ``max_len
        == seq_extent``, so the ring never wraps and a slot above the
        rewound position can only have been written by the rejected
        round itself (it held ``-1`` before, inductively).  Restoring it
        keeps cache locals a pure function of sequence length, which is
        what lets `CacheOps.concat`'s locals-equality check merge
        cohorts with different speculative acceptance histories."""
        if steps <= 0:
            return cache

        def _is_int(x, nd):
            return (getattr(x, "ndim", None) == nd
                    and jnp.issubdtype(x.dtype, jnp.integer))

        if self.paged:
            new_pos = next(x - steps for x in cache.locals if _is_int(x, 0))
            cache.locals = [
                x - steps if _is_int(x, 0)
                else jnp.where(x >= new_pos, -1, x) if _is_int(x, 1)
                else x
                for x in cache.locals
            ]
            return cache

        al = jax.tree.leaves(self._axes, is_leaf=lambda x: isinstance(x, tuple))
        new_pos = next(
            leaf - steps
            for leaf, ax in zip(jax.tree.leaves(cache), al)
            if ax == () and _is_int(leaf, 0)
        )

        def fix(leaf, ax):
            if ax == () and _is_int(leaf, 0):
                return leaf - steps
            if ax == (None,) and _is_int(leaf, 1):
                return jnp.where(leaf >= new_pos, -1, leaf)
            return leaf

        return jax.tree.map(
            fix, cache, self._axes,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def release_draft(self, cohort: Cohort) -> None:
        """Drop a cohort's draft cache (paged rows decref'd).  Cheap and
        always safe — the draft cache is a pure function of host-known
        history and lazily rebuilds at the next speculative round."""
        if cohort.draft_cache is None:
            cohort.draft_behind = 0
            return
        if self.paged:
            cohort.draft_cache.release()
        cohort.draft_cache = None
        cohort.draft_behind = 0

    # -- prefix reuse -------------------------------------------------------
    def publish_prefix(self, cohort: Cohort) -> None:
        """Publish each just-prefilled row's full prompt into the radix
        index (before any decode writes the row's tail page — the index
        snapshots that page plus the state page and position locals)."""
        if self.prefix_index is None:
            return
        cache = cohort.cache
        locals_np = [np.asarray(x) for x in cache.locals]
        for i, st in enumerate(cohort.slots):
            if st.request.prompt_len != cohort.length:
                continue  # bucket-padded row: cache holds pad-token state
            self.prefix_index.publish(
                st.request.prompt,
                cache.seq_table[i],
                int(cache.state_table[i]),
                locals_np,
                st.generated[0],
            )

    def admit_prefix_hits(self, group: list) -> None:
        """Admit one same-length prefix-hit group [(Request, PrefixEntry)]
        as a cohort with the shared pages materialized: no prefill runs;
        each request's first token is the entry's cached greedy token.

        The scheduler's submit-time pins are held through admission and
        released in the ``finally`` — pool pressure from this admit (or an
        earlier group's, in the same step) must never evict an entry that
        a selected-but-not-yet-admitted hit still needs."""
        try:
            self._admit_prefix_hits_pinned(group)
        finally:
            self.scheduler.release_hit_pins(group)

    def _admit_prefix_hits_pinned(self, group: list) -> None:
        from .paging import PagedCache

        P = group[0][0].prompt_len
        rows = [self.prefix_index.admit(entry) for _, entry in group]
        seq_t = np.stack([r for r, _ in rows])
        state_t = np.concatenate([s for _, s in rows])
        n_dummy = (-len(group)) % max(1, self.batch_align)
        if n_dummy:
            dseq, dstate = self.store.alloc_rows_zeroed(n_dummy)
            seq_t = np.concatenate([seq_t, dseq], axis=0)
            state_t = np.concatenate([state_t, dstate], axis=0)
            self.metrics.n_padded_rows += n_dummy
        entry0 = group[0][1]
        cache = PagedCache(
            self.store, seq_t, state_t,
            [jnp.asarray(x) for x in entry0.locals_np],
        )
        slots = [RequestState(req) for req, _ in group]
        for st, (_, entry) in zip(slots, group):
            st.emit(int(entry.first_token), self.eos_id)
        cohort = self.new_cohort(
            slots=slots, cache=cache, length=P, n_dummy=n_dummy
        )
        if self.spiking_packed:
            cohort.spikes = self.new_spike_cache()
            cohort.spikes.append(self._slot_spikes(cohort))
        self.cohorts.append(cohort)
        self.metrics.n_prefix_hits += len(group)
        self.metrics.n_prefix_tokens_reused += P * len(group)

    def release_cohort(self, cohort: Cohort) -> None:
        """Return a fully-retired cohort's backing storage to the pools
        (dense cohorts are garbage-collected with their arrays)."""
        self.release_draft(cohort)
        if self.paged and cohort.cache is not None:
            cohort.cache.release()
        if self.paged and cohort.spikes is not None:
            cohort.spikes.take([])

    def drain_logit_traces(self) -> list[list[np.ndarray]]:
        """Per-request logit traces in rid order, CLEARING the store.

        The capture buffer grows by one vocab-sized row per emitted token
        (bounded per request by ``logit_trace_window`` when set; retirement
        intentionally keeps traces so post-run parity checks can read
        them) — so measurement windows must drain it: pass the result
        straight to `serve.policy.check_parity`.  rid order equals
        submission order, which is how the reference run's prompts line up.

        CAVEAT: `check_parity` / `drift_report` compare traces step-by-step
        from index 0, so parity measurement needs UNWINDOWED traces
        (``logit_trace_window=None``, the default) on both runs — a
        windowed trace keeps only the most recent W rows, shifting its
        indices by however many were dropped.  The window is for bounded-
        memory telemetry on long serves, not for parity runs.
        """
        out = [self.logit_traces[r] for r in sorted(self.logit_traces)]
        self.logit_traces = {}
        return out

    def _capture(self, slots: list[RequestState], logits) -> None:
        """Record each live slot's last-position logits (the vector whose
        argmax is the token emitted this step) for drift measurement —
        the observable that `serve.policy.check_parity` bounds under
        approximate exactness.  ``logit_trace_window`` (opt-in) caps each
        request's trace to its most recent W rows so long serves don't
        grow the buffer without bound."""
        if not self.capture_logits:
            return
        rows = np.asarray(logits[: len(slots), -1], np.float32)
        w = self.logit_trace_window
        for st, row in zip(slots, rows):
            if st.done:
                # a finished slot still riding in a cohort (pipelined
                # speculation past EOS): its tokens are discarded by emit,
                # and its trace must not grow either — one row per EMITTED
                # token, same as sync
                continue
            trace = self.logit_traces.setdefault(st.rid, [])
            trace.append(row)
            if w is not None and len(trace) > w:
                del trace[: len(trace) - w]

    def _finish(self, st: RequestState) -> None:
        expect = self._resume_expect.pop(st.rid, None)
        if expect is not None:
            # zero-tokens-lost gate: the replayed stream must extend the
            # predecessor's handed-off progress exactly (bitwise policies
            # only — `resume` records the ledger under that contract)
            got = np.asarray(st.generated[: expect.shape[0]], np.int32)
            if not np.array_equal(got, expect):
                from .policy import ParityError

                raise ParityError(
                    f"resumed request {st.rid} diverged from its handoff "
                    f"progress: replayed {got.tolist()} vs handed-off "
                    f"{expect.tolist()}"
                )
        self.results[st.rid] = st
        req = st.request
        self.metrics.record(RequestMetrics(
            rid=st.rid,
            prompt_len=req.prompt_len,
            n_generated=len(st.generated),
            ttft_s=st.first_token_time - req.submit_time,
            latency_s=st.finish_time - req.submit_time,
            finish_reason=st.finish_reason,
        ))

    # -- reporting ----------------------------------------------------------
    def summary(self) -> dict:
        from .sharding import mesh_summary

        s = self.metrics.summary()
        s["rejected"] = self.scheduler.n_rejected
        s["admission_closed"] = self.scheduler.closed
        s.update(mesh_summary(self.mesh))
        s["policy"] = self.policy.describe()
        s["exactness"] = self.policy.exactness.mode
        s["execution"] = self.policy.execution
        s["token_identical"] = self.policy.token_identical
        s["paging"] = self.policy.paging.describe()
        if self.paged:
            s["page_pool"] = self.store.summary()
            if self.prefix_index is not None:
                s["prefix_index"] = self.prefix_index.summary()
        if not self.policy.token_identical:
            s["drift_tol"] = self.policy.exactness.tol
        if self.spiking_packed:
            w = self._last_spike_words
            s["spike_sparsity"] = (
                float("nan") if w is None
                else spike_sparsity_of(np.asarray(w), self.cfg.spiking_T)
            )
            s["spike_bytes_packed_per_slot"] = self.cfg.d_model * 4
            s["spike_bytes_unpacked_f32_per_slot"] = (
                self.cfg.d_model * self.cfg.spiking_T * 4
            )
            s["dual_sparse"] = self.spiking_dual_sparse
        s["temporal"] = self.policy.temporal.describe()
        s["speculation"] = self.policy.speculation.describe()
        return s
