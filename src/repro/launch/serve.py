"""Serving launcher: continuous-batching engine over any registered arch.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Execution configuration is one declarative `ExecutionPolicy`
(`repro.serve.policy`): ``--spike-format`` / ``--weight-sparsity`` /
``--mesh`` (placement) / ``--exactness`` / ``--execution`` map 1:1 onto
its fields.  The staged pipelined executor (token-identical; see
`repro.serve.executor`):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --execution pipelined --pipeline-depth 2 --batch 4 --gen 16

Sharded serving (on CPU use fake XLA devices):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --spiking --mesh data,model --fake-devices 8 --batch 4 --gen 8

Approximate tensor parallelism (psum-TP attention/MLP on the model axis —
throughput over token identity; measured logit drift vs. the bitwise
reference is printed and bounded by ``--tol``):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --mesh data,model --fake-devices 8 --exactness approximate --batch 4

Adaptive temporal sparsity (skip silent timestep planes in-kernel — the
third sparsity axis; bitwise at the default --min-spikes 1):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --spiking --weight-density 0.3 --temporal adaptive --batch 4

Speculative decoding (`--speculation draft`): a cheap draft policy over
the same weights proposes ``--k`` tokens per round (one fused dispatch);
the target verifies all ``k+1`` positions in one batched decode and emits
the longest matching prefix — token-identical by construction, with
acceptance accounting in the summary:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --spiking --weight-density 0.3 --speculation draft --k 4 --batch 4

Event-stream serving (`--stream`): prompts arrive as DVS-style event
windows instead of token arrays — each request is a `StreamSession` fed
from a synthetic moving-blob sensor (`repro.data.events`), admitted once
its first ``--window-us`` window completes, ingested incrementally, and
closed either explicitly or by ``--idle-timeout`` of event-time silence.
``--prompt-len`` counts event WINDOWS (one frame token each):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --smoke \
        --spiking --weight-density 0.3 --stream --window-us 1000 \
        --temporal adaptive --batch 4 --prompt-len 8 --gen 8

Requests (`--batch` of them) are submitted to `repro.serve.Engine`, which
batches prefills, merges decode cohorts, and reports TTFT / throughput.
`generate` below is the original single-shot loop, kept as the reference
oracle the engine is tested token-identical against.

Deprecated flags (`--spiking-packed`, `--no-dual-sparse`) still work: they
map onto the policy and warn.
"""
from __future__ import annotations

import argparse
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np


def generate(model, params, tokens, cache, steps: int):
    """Greedy generation loop (jit'd prefill + decode) — reference oracle."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode, donate_argnums=(2,))
    logits, cache = prefill(params, {"tokens": tokens}, cache)
    out = [jnp.argmax(logits[:, -1], axis=-1)[:, None]]
    for _ in range(steps - 1):
        logits, cache = decode(params, out[-1], cache)
        out.append(jnp.argmax(logits[:, -1], axis=-1)[:, None])
    return jnp.concatenate(out, axis=1)


def build_policy(args, cfg):
    """Map CLI flags (and the deprecated ones) onto one ExecutionPolicy."""
    from repro.serve import (
        ExecutionPolicy,
        Placement,
        approximate,
        bitwise,
    )

    spike_format = args.spike_format
    weight_sparsity = args.weight_sparsity
    if args.spiking_packed:
        warnings.warn(
            "--spiking-packed is deprecated; use --spike-format packed",
            DeprecationWarning,
        )
        spike_format = spike_format or "packed"
    if args.no_dual_sparse:
        warnings.warn(
            "--no-dual-sparse is deprecated; use --weight-sparsity dense",
            DeprecationWarning,
        )
        weight_sparsity = weight_sparsity or "dense"
    placement = Placement.from_spec(args.mesh)
    exactness = (
        approximate(args.tol) if args.exactness == "approximate" else bitwise()
    )
    from repro.serve import Paging, Temporal, adaptive_t, paged

    paging = (paged(args.page_size) if args.paging == "paged" else Paging())
    temporal = (
        adaptive_t(args.min_spikes)
        if args.temporal == "adaptive"
        else Temporal()
    )
    speculation = None
    if getattr(args, "speculation", "none") == "draft":
        from repro.serve import Speculation, draft

        # the draft is its own full policy over the SAME arch: sync,
        # unsharded, unpaged (the engine pages its state), free to be
        # cheaper — harder-pruned weights (--draft-weight-density) and/or
        # lossier timestep skipping (--draft-min-spikes).  A lossy draft
        # only lowers acceptance; emitted tokens are always the target's.
        d_temporal = (
            adaptive_t(args.draft_min_spikes)
            if args.draft_min_spikes else Temporal()
        )
        d_exactness = (
            approximate(args.tol) if args.draft_min_spikes > 1 else bitwise()
        )
        draft_policy = ExecutionPolicy.for_arch(
            cfg,
            temporal=d_temporal,
            exactness=d_exactness,
        )
        speculation = draft(
            draft_policy, args.k,
            draft_weight_density=args.draft_weight_density or None,
        )
    return ExecutionPolicy.for_arch(
        cfg,
        spike_format=spike_format,
        weight_sparsity=weight_sparsity,
        placement=placement,
        exactness=exactness,
        execution=args.execution,
        paging=paging,
        temporal=temporal,
        speculation=speculation,
    )


def serve_streams(engine, cfg, args):
    """Feed ``--batch`` synthetic DVS streams through the engine, one event
    window per `engine.step()`, and return (outputs, sessions)."""
    from repro.data.events import moving_blob_events, split_into_windows
    from repro.serve import EventStream, StreamSession

    n_win = args.prompt_len
    sessions, tickets, feeds = [], [], []
    for i in range(args.batch):
        # every other stream goes dark for one window: the gap still emits
        # a frame (all-silent words) whose timestep planes --temporal
        # adaptive skips in-kernel
        silent = (n_win // 2,) if i % 2 and n_win > 1 else ()
        events = moving_blob_events(
            n_win, height=16, width=16, window_us=args.window_us,
            seed=i, silent=silent,
        )
        stream = EventStream(
            args.window_us,
            idle_timeout_us=args.idle_timeout or None,
        )
        session = StreamSession(
            stream, height=16, width=16, T=cfg.spiking_T, vocab=cfg.vocab,
        )
        tickets.append(engine.submit_stream(session, args.gen))
        sessions.append(session)
        feeds.append(split_into_windows(events, n_win, args.window_us))
    for w in range(n_win):
        for session, chunks in zip(sessions, feeds):
            session.stream.push(chunks[w])
        engine.step()
    for session in sessions:
        if args.idle_timeout:
            session.stream.tick(n_win * args.window_us + args.idle_timeout)
        else:
            session.stream.close()
    out = engine.run()
    return [out[t.rid] for t in tickets], sessions


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to submit")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=0,
                    help="engine slot budget (0 = one slot per request)")
    ap.add_argument("--batch-align", type=int, default=1,
                    help="pad prefill batches to a multiple of this")
    # -- ExecutionPolicy fields ---------------------------------------------
    ap.add_argument("--spike-format", choices=("float", "packed"),
                    default=None,
                    help="policy.spike_format (default: packed for spiking "
                         "archs, float otherwise)")
    ap.add_argument("--weight-sparsity", choices=("dense", "dual_sparse"),
                    default=None,
                    help="policy.weight_sparsity (default: dual_sparse for "
                         "packed + LTH-pruned archs)")
    ap.add_argument("--mesh", default=None,
                    help="policy.placement mesh spec, e.g. 'data,model' "
                         "(auto sizes), 'data=4,model=2' or '4,2'; omitted "
                         "= unsharded; single-device runs fall back "
                         "automatically")
    ap.add_argument("--exactness", choices=("bitwise", "approximate"),
                    default="bitwise",
                    help="policy.exactness: bitwise = token-identical to "
                         "the single-device loop; approximate = psum-TP "
                         "attention/MLP on the model axis, logit drift "
                         "bounded by --tol")
    ap.add_argument("--tol", type=float, default=0.05,
                    help="max logit drift allowed under --exactness "
                         "approximate")
    ap.add_argument("--execution", choices=("sync", "pipelined"),
                    default="sync",
                    help="policy.execution: sync = every decode step "
                         "host-syncs its sampled tokens; pipelined = the "
                         "staged executor keeps tokens on device between "
                         "steps, defers host materialization behind an "
                         "in-flight window (--pipeline-depth), overlaps "
                         "the packed-spike encode with the next decode, "
                         "and re-packs skewed mesh cohorts")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight decode window under --execution "
                         "pipelined (>= 1; 1 degenerates to sync cadence)")
    ap.add_argument("--paging", choices=("none", "paged"), default="none",
                    help="policy.paging: paged = cache state lives in "
                         "fixed pages owned by a CacheStore (cohort "
                         "merge/retire are page-table edits) with a radix "
                         "prefix index serving repeated prompts without a "
                         "prefill; none = per-cohort dense caches")
    ap.add_argument("--page-size", type=int, default=8,
                    help="cache positions per page under --paging paged "
                         "(multiple of 8; max_len is rounded up to a "
                         "multiple of it)")
    ap.add_argument("--temporal", choices=("full", "adaptive"),
                    default="full",
                    help="policy.temporal: adaptive = score each timestep "
                         "bit-plane of the packed payload on device and "
                         "skip planes below --min-spikes in-kernel (the "
                         "third sparsity axis); full = walk every timestep")
    ap.add_argument("--min-spikes", type=int, default=1,
                    help="minimum total spikes for a timestep plane to be "
                         "walked under --temporal adaptive; 1 (default) "
                         "skips only all-silent planes and stays bitwise, "
                         ">1 requires --exactness approximate")
    # -- speculative decoding (ExecutionPolicy.speculation) -------------------
    ap.add_argument("--speculation", choices=("none", "draft"),
                    default="none",
                    help="policy.speculation: draft = a cheap draft policy "
                         "over the SAME weights proposes --k tokens per "
                         "round in one fused dispatch; the target verifies "
                         "all k+1 positions in ONE batched decode and emits "
                         "the longest matching prefix plus its own bonus "
                         "token — bitwise token-identical to non-"
                         "speculative decoding by construction")
    ap.add_argument("--k", type=int, default=4,
                    help="proposal length per speculative round under "
                         "--speculation draft")
    ap.add_argument("--draft-weight-density", type=float, default=0.0,
                    help="prune the draft's FFN weights to this density "
                         "(must be <= the target's --weight-density; 0 = "
                         "share the target's weights unpruned)")
    ap.add_argument("--draft-min-spikes", type=int, default=0,
                    help="run the draft with temporal='adaptive' at this "
                         "min-spikes threshold (0 = full temporal walk; "
                         ">1 makes the DRAFT lossy, which only lowers "
                         "acceptance — the verified stream stays bitwise)")
    # -- event-stream ingestion (serve/streaming.py + data/events.py) --------
    ap.add_argument("--stream", action="store_true",
                    help="serve event streams instead of token prompts: "
                         "each request is a StreamSession fed one synthetic "
                         "DVS window per engine step, admitted on its first "
                         "complete window and ingested incrementally; "
                         "--prompt-len counts event windows (one frame "
                         "token each)")
    ap.add_argument("--window-us", type=int, default=1000,
                    help="event-time width of one stream window under "
                         "--stream; each window encodes to one frame "
                         "token")
    ap.add_argument("--idle-timeout", type=int, default=0,
                    help="under --stream: event-time microseconds of "
                         "silence after which tick() auto-closes a stream "
                         "(the idle watermark); 0 = close explicitly once "
                         "all windows are pushed")
    # -- arch surgery -------------------------------------------------------
    ap.add_argument("--spiking", action="store_true",
                    help="swap the arch's MLP blocks for dual-sparse "
                         "spiking FFNs (paper workload)")
    ap.add_argument("--weight-density", type=float, default=0.3,
                    help="LTH density for --spiking (plans built at load)")
    # -- deprecated (map onto the policy, with a warning) -------------------
    ap.add_argument("--spiking-packed", action="store_true",
                    help="DEPRECATED: use --spike-format packed")
    ap.add_argument("--no-dual-sparse", action="store_true",
                    help="DEPRECATED: use --weight-sparsity dense")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force this many fake XLA host devices (must be "
                         "set before the jax backend initializes; CPU-only "
                         "mesh testing)")
    # -- preemption / handoff (ft.preemption + serve/handoff.py) -------------
    ap.add_argument("--handoff-path", default=None,
                    help="directory for the drain handoff: a SIGTERM (or "
                         "--preempt-after) closes admission, drains "
                         "in-flight cohorts within --drain-grace steps, "
                         "and checkpoints scheduler state here; with "
                         "--resume, the directory to resume FROM")
    ap.add_argument("--drain-grace", type=int, default=0,
                    help="max engine steps granted to in-flight cohorts "
                         "after a preemption notice (0 = run them to "
                         "completion); unfinished requests ride the "
                         "handoff")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="testing hook: deliver the preemption notice via "
                         "PreemptionHandler.trigger() after this many "
                         "engine steps (0 = only real SIGTERM preempts)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a successor engine from --handoff-path "
                         "instead of submitting fresh requests")
    ap.add_argument("--verify-resume", action="store_true",
                    help="with --resume: replay ALL handoff requests on an "
                         "undisturbed reference engine and exit nonzero "
                         "unless the resumed results are token-identical")
    args = ap.parse_args(argv)

    if args.fake_devices:
        from repro.launch.mesh import force_fake_devices

        force_fake_devices(args.fake_devices)

    import dataclasses

    from repro.configs import get_config, smoke_variant
    from repro.launch.compile_cache import configure_compile_cache
    from repro.models.registry import build_model
    from repro.serve import Engine, check_parity

    configure_compile_cache(os.getcwd())  # run from the checkout's root
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if args.spiking:
        cfg = dataclasses.replace(
            cfg, spiking_ffn=True,
            spiking_weight_density=args.weight_density,
        )
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode path")
    if args.stream and (args.handoff_path or args.resume):
        raise SystemExit(
            "--stream does not compose with --handoff-path/--resume in this "
            "launcher (mid-ingest drain is exercised by the test suite)"
        )
    policy = build_policy(args, cfg)
    print(f"policy: {policy.describe()}")
    max_len = args.prompt_len + args.gen
    if policy.speculation.enabled:
        # verify windows may overhang a row's budget by up to k positions
        # (rejected writes roll back); the scheduler reserves this slack
        max_len += policy.speculation.k
    if policy.paging.enabled:
        # paged layout needs the cache sequence extent to divide into whole
        # pages; round capacity up (spare positions are masked, never read)
        ps = policy.paging.page_size
        max_len = -(-max_len // ps) * ps
    mesh = policy.mesh
    if args.mesh and mesh is None:
        print("mesh: single device — auto fallback to unsharded serving")
    elif mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} "
              f"devices ({jax.default_backend()})")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        np.asarray(rng.integers(0, cfg.vocab, size=(args.prompt_len,)),
                   np.int32)
        for _ in range(args.batch)
    ]
    if args.resume:
        if not args.handoff_path:
            raise SystemExit("--resume requires --handoff-path")
        from repro.serve import Handoff

        handoff = Handoff.load(args.handoff_path)
        c = handoff.counts()
        print(f"resuming from {args.handoff_path}: {c['waiting']} waiting + "
              f"{c['inflight']} in-flight ({c['tokens_in_flight']} tokens "
              f"already emitted) + {c['finished']} finished")
        engine = Engine.resume(
            model, params, handoff,
            policy=policy,
            batch_align=args.batch_align,
            pipeline_depth=args.pipeline_depth,
        )
        out = engine.run()
        s = engine.summary()
        print(f"resumed {len(out)} results "
              f"({sum(len(v) for v in out.values())} tokens total)")
        if args.verify_resume:
            ref = Engine(
                model, params,
                max_len=handoff.meta["max_len"],
                max_slots=handoff.meta["max_slots"],
                eos_id=handoff.meta["eos_id"],
                batch_align=args.batch_align,
                policy=policy,
                pipeline_depth=args.pipeline_depth,
            )
            tickets = [ref.submit(r.prompt, r.max_new_tokens)
                       for r in handoff.requests]
            ref_out = ref.run()
            for r, t in zip(handoff.requests, tickets):
                if not np.array_equal(out[r.rid], ref_out[t.rid]):
                    raise SystemExit(
                        f"RESUME IDENTITY FAILED: rid {r.rid} "
                        f"{out[r.rid][:8]} != {ref_out[t.rid][:8]}"
                    )
            print(f"resume identity: {len(tickets)} requests "
                  "token-identical to an undisturbed engine")
        print("summary:", json.dumps(
            {k: round(v, 4) if isinstance(v, float) else v
             for k, v in s.items()}))
        return 0

    preemption = None
    if args.handoff_path:
        from repro.ft import PreemptionHandler

        preemption = PreemptionHandler()
    engine = Engine(
        model,
        params,
        max_len=max_len,
        max_slots=args.max_slots or args.batch,
        batch_align=args.batch_align,
        policy=policy,
        pipeline_depth=args.pipeline_depth,
        preemption=preemption,
    )
    if preemption is not None:
        tickets = [engine.submit(p, args.gen) for p in prompts]
        n_steps = 0
        while not engine.idle and not engine.stopping:
            if args.preempt_after and n_steps == args.preempt_after:
                preemption.trigger()
                break
            engine.step()
            n_steps += 1
        if engine.stopping:
            handoff = engine.drain(step_budget=args.drain_grace or None)
            handoff.save(args.handoff_path)
            c = handoff.counts()
            print(f"preempted after {n_steps} steps; drained within "
                  f"grace {args.drain_grace or 'unbounded'}: "
                  f"{c['finished']} finished, {c['inflight']} in-flight "
                  f"({c['tokens_in_flight']} tokens preserved), "
                  f"{c['waiting']} waiting -> {args.handoff_path}")
            print("summary:", json.dumps(
                {k: round(v, 4) if isinstance(v, float) else v
                 for k, v in engine.summary().items()}))
            preemption.restore()
            return 0
        preemption.restore()
        out = engine.run()
        outs = [out[t.rid] for t in tickets]
    elif args.stream:
        outs, sessions = serve_streams(engine, cfg, args)
        # the materialized frame-token prompts — the approximate-drift
        # reference below replays these as ordinary requests
        prompts = [sess.prompt_tokens() for sess in sessions]
    else:
        outs = engine.generate_batch(prompts, args.gen)
    s = engine.summary()
    if not policy.token_identical:
        # measure drift against a bitwise single-device run of the same
        # prompts — the contract --tol bounds.  The reference keeps the SAME
        # spike format / weight sparsity (placement + exactness + temporal
        # reset), so the measured drift is pure psum-TP reassociation and/or
        # lossy timestep skipping — the approximations the policy opted
        # into — not float-vs-packed kernel arithmetic differences.
        import dataclasses as _dc

        from repro.serve import Placement, Temporal, bitwise

        ref_policy = _dc.replace(
            policy, placement=Placement(), exactness=bitwise(),
            temporal=Temporal(),
        )
        ref = Engine(
            model, params,
            max_len=max_len,
            max_slots=args.max_slots or args.batch,
            batch_align=args.batch_align,
            policy=ref_policy,
            capture_logits=True,
        )
        ref_outs = ref.generate_batch(prompts, args.gen)
        rep = check_parity(
            policy, ref_outs, outs,
            ref_logits=ref.drain_logit_traces(),
            got_logits=engine.drain_logit_traces(),
        )
        # s["token_identical"] stays the policy CONTRACT (False here);
        # the measured facts get their own keys
        s["max_logit_drift"] = rep["max_logit_drift"]
        s["token_match_fraction"] = rep["token_match_fraction"]
        print(f"approximate drift: max |logit drift| "
              f"{rep['max_logit_drift']:.3e} <= tol {policy.exactness.tol} "
              f"(token match {rep['token_match_fraction']:.0%})")
    if policy.temporal.enabled:
        print(f"temporal: {policy.temporal.describe()} — "
              f"{s['timesteps_skipped']} timestep planes skipped")
    if policy.speculation.enabled:
        print(f"speculation: {policy.speculation.describe()} — "
              f"{s['speculative_rounds']} rounds, "
              f"{s['tokens_accepted']}/{s['tokens_proposed']} proposals "
              f"accepted ({s['acceptance_rate']:.0%})")
    if args.stream:
        print(f"streamed {s['stream_sessions']} sessions / "
              f"{s['stream_windows']} frames — frame->first-token "
              f"p50 {s['frame_to_first_token_s_p50']*1e3:.1f}ms / "
              f"p99 {s['frame_to_first_token_s_p99']*1e3:.1f}ms")
    print(f"served {s['n_requests']} requests / {s['total_tokens']} tokens "
          f"in {s['wall_s']:.2f}s ({s['throughput_tok_s']:.1f} tok/s, "
          f"ttft_p50 {s['ttft_s_p50']*1e3:.0f}ms, "
          f"mean decode batch {s['mean_decode_batch']:.1f})")
    print("summary:", json.dumps({k: round(v, 4) if isinstance(v, float) else v
                                  for k, v in s.items()}))
    print("sample:", outs[0][:12])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
